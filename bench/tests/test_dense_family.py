"""The dense family is the harness's dense code as it was before families:
the weights drawn from a seed are the same bytes, and the decode and
chunk steps lowered for the pool fit plan the same bytes. The pinned
values were taken on the CPU before the model code moved into
bench/families/dense.py."""
import hashlib

import numpy as np
import pytest

from bench.tests.small import cell, cpu_only

SEED = 2**31 + 505
DIGESTS = {
    "tied": "52f98c3a91d0d95aba9719223d671356bea551a89e7de3c6231e73e679da9166",
    "untied": "058a0ad937cebe796eb7c488fccdd51fdc656a096d528b9136cca77edb3fa304",
}
PLANS = {("decode", 128, 4): 2132688, ("decode", 256, 8): 3705616,
         ("chunk", 128, 4): 2144671, ("chunk", 256, 8): 3717551}


def conf_of(kind):
    conf = cell()[2]
    if kind == "untied":
        conf.update(hidden_size=128, intermediate_size=256,
                    num_attention_heads=8, num_key_value_heads=4,
                    vocab_size=500, tie_word_embeddings=False)
    return conf


def digest(params):
    import jax
    h = hashlib.sha256()
    for path, a in sorted(jax.tree_util.tree_leaves_with_path(params),
                          key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(a).view(np.uint16).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("kind", sorted(DIGESTS))
def test_weights_are_the_same_bytes(kind):
    from bench import model
    cpu_only()
    assert digest(model.make_weights(conf_of(kind), SEED)) == DIGESTS[kind]


@pytest.fixture(scope="module")
def engine():
    from bench import families, model
    cpu_only()
    conf = conf_of("tied")
    cfg = families.load(conf).arch_config(conf)
    return model.build_engine(cfg, model.make_weights(conf, SEED),
                              conf["engine"], 64)


@pytest.mark.parametrize("kind,blocks,width", sorted(PLANS))
def test_lowered_steps_plan_the_same_bytes(engine, kind, blocks, width):
    import jax
    from bench import model
    compiled = model._lower(engine, kind, blocks, width,
                            jax.sharding.SingleDeviceSharding(cpu_only()[0]))
    assert model.plan_bytes(compiled) == PLANS[(kind, blocks, width)]
    assert compiled.args_info[0][2] == {}     # no recurrent state
