"""An untied configuration (its own output projection, ``head``): the
weights the harness draws have the tree the program's model reads, the
padded vocabulary columns of the head are zero, and the reference's
logits agree with the program's ``LM`` forward at float32 on those
weights."""
import numpy as np
import pytest

from bench.tests.small import cell

SEED = 2**31 + 303


def untied_conf():
    _, _, conf, _ = cell()
    conf.update(hidden_size=128, intermediate_size=256, num_attention_heads=8,
                num_key_value_heads=4, num_hidden_layers=2, vocab_size=500,
                tie_word_embeddings=False)
    return conf


def test_untied_layout_matches_the_model():
    import jax
    from bench import model
    from bench.families import dense

    conf = untied_conf()
    lay = dense.layout(conf)
    assert lay["head"][0] == (128, 512)
    params = model.make_weights(conf, SEED)
    model.check_layout(dense.arch_config(conf), params)
    head = np.asarray(params["head"], np.float32)
    assert not head[:, 500:].any() and head[:, :500].any()
    # the tied configuration's draw is untouched by the head's leaf
    tied = dict(conf, tie_word_embeddings=True)
    assert "head" not in dense.layout(tied)
    a = model.make_weights(tied, SEED)
    assert all(np.array_equal(np.asarray(a["blocks"]["pos0"]["mix"][k]),
                              np.asarray(params["blocks"]["pos0"]["mix"][k]))
               for k in ("wq", "wk", "bq"))
    assert jax.tree.structure(a) != jax.tree.structure(params)


def test_reference_matches_the_program_forward_at_float32():
    import jax
    import jax.numpy as jnp
    from bench import model, reference
    from bench.families import dense
    from repro.models.lm import LM

    conf = untied_conf()
    params = model.make_weights(conf, SEED)
    tokens = np.random.default_rng(SEED).integers(0, 500, reference.ROWS)
    ref = {k: np.asarray(v) for k, v in dense.reference(conf)(
        params, jnp.asarray(tokens, jnp.int32)).items()}

    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits = LM(dense.arch_config(conf)).forward(
            f32, {"tokens": jnp.asarray(tokens, jnp.int32)[None]})
    lg = np.asarray(logits, np.float64)[0, :, :500]
    nxt = np.concatenate([tokens[1:], tokens[:1]])
    want = {"max": lg.max(-1), "sd": lg.std(-1),
            "next": np.take_along_axis(lg, nxt[:, None], 1)[:, 0]}
    for k, v in want.items():
        assert ref[k] == pytest.approx(v, rel=1e-4, abs=1e-4), k
