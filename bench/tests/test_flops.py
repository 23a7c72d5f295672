"""bench/flops.py, through the dense family's counts, against counts made
by hand."""
import pytest

from bench import flops
from bench.families import dense

CONF = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 256}
S = dense.counts(CONF, {"block_size": 16, "kv_dtype": "bf16"})


def test_one_paged_read_call():
    ops, byt = S.paged_read(length=40, rows=1)
    assert ops == 4 * 1 * 4 * 16 * 40              # q.k and p.v, 4 heads
    # 3 whole pages of 16 positions, K and V, 2 kv heads of 16, bf16
    kv = 2 * 3 * 16 * 2 * 16 * 2
    # the query in bf16, and the f32 partials o (16), m and l out
    io = 4 * (16 * 2 + 18 * 4)
    assert byt == kv + io
    assert S.paged_read(0, 1) == (0.0, 0.0)


def test_roofline_of_calls_is_bound_per_call():
    calls = [(40, 1), (40, 1)]
    sec, by_ops, by_bytes = flops.roofline_seconds(S, calls, 197e12, 819e9)
    ops, byt = S.paged_read(40, 1)
    assert sec == pytest.approx(2 * 2 * byt / 819e9)    # two layers each
    assert by_ops == 0 and by_bytes == pytest.approx(sec)
    sec, by_ops, _ = flops.roofline_seconds(S, calls, 1e6, 1e6)
    assert sec == by_ops == pytest.approx(2 * 2 * ops / 1e6)


def test_one_decode_token_of_a_whole_step():
    attn = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64   # q, k, v and o
    ffn = 3 * 64 * 128
    weights = 2 * (attn + ffn) + 64 * 256         # two layers and the head
    assert S.matmul_params == weights
    assert S.token_flops(context=40) == 2 * weights + 4 * 2 * 4 * 16 * 41


def test_prefill_chunk():
    w = S.matmul_params - 64 * 256
    # 3 tokens after 5 cached: they attend to 6, 7 and 8 positions
    want = 2 * w * 3 + 4 * 2 * 4 * 16 * (6 + 7 + 8)
    assert S.chunk_flops(start=5, n=3, last=False) == want
    assert S.chunk_flops(5, 3, True) == want + 2 * 64 * 256
    assert S.state_bytes == 0 and S.attn_layers == 2
