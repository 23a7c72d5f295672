"""Model families, one module per family, found by the ``family`` key of
a configuration file (``bench/configs/<name>.json``); a file with no such
key is ``dense``. A new family is one new module here; no other file of
the harness names a family.

A family module ``bench/families/<family>.py`` defines:

- ``arch_config(conf)``: the program's ``ArchConfig`` for the file, or
  ``ValueError`` where the file asks for what the program does not model;
- ``layout(conf)``: every weight as ``(shape, init)`` or ``(shape, init,
  dtype)`` in the tree the program's model reads, which
  ``bench.model.make_weights`` draws from the seed (see there for the
  inits; the dtype is bf16 unless given);
- ``counts(conf, es)``: the model's ``bench.flops.Counts`` under the
  configuration's engine settings ``es``: the attention layers, the paged
  read's (operations, bytes) per call and layer, ``token_flops``,
  ``chunk_flops`` and the bytes of recurrent state per batch slot;
- ``reference(conf, control=False)``: the plain float32 reference as the
  jitted ``f(weights, tokens)`` of ``bench.reference.readings``, which
  imports nothing of the program.
"""
import importlib


def load(conf):
    """The family module of configuration ``conf``."""
    return importlib.import_module(
        f"bench.families.{conf.get('family', 'dense')}")
