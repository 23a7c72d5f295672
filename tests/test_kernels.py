"""Per-kernel allclose tests vs the pure-jnp oracles (interpret mode on CPU).

Shapes sweep block-boundary cases (single block, multi-block, GQA groups,
non-128 head dims that exercise padding) and dtypes bf16/f32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.ssd import ssd_chunked_ref


def rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32).astype(dtype)


TOL = {jnp.bfloat16: dict(atol=3e-2, rtol=3e-2),
       jnp.float32: dict(atol=2e-5, rtol=2e-5)}


# --------------------------------------------------------------------------
# flash attention forward
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,s,h,kv,d", [
    (1, 128, 128, 4, 4, 128),      # single block, MHA
    (2, 256, 256, 4, 2, 128),      # multi-block, GQA
    (1, 256, 256, 8, 1, 64),       # MQA + head-dim padding
    (2, 128, 384, 4, 4, 128),      # cross: S > T (non-causal)
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_fwd(b, t, s, h, kv, d, dtype, causal):
    if causal and t != s:
        pytest.skip("causal requires square for this contract")
    q = rand(0, (b, t, h, d), dtype)
    k = rand(1, (b, s, kv, d), dtype)
    v = rand(2, (b, s, kv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), **TOL[dtype])


def test_flash_attention_grads_match_reference():
    b, t, h, kv, d = 1, 128, 4, 2, 128
    q = rand(0, (b, t, h, d), jnp.float32)
    k = rand(1, (b, t, kv, d), jnp.float32)
    v = rand(2, (b, t, kv, d), jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, causal=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(ref.flash_attention_ref(q, k, v, causal=True) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(a, b_, atol=2e-3, rtol=2e-3)


# --------------------------------------------------------------------------
# flash decode
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kv,d", [
    (2, 256, 4, 4, 128),
    (3, 512, 8, 2, 128),
    (2, 256, 4, 1, 64),
])
def test_flash_decode(b, s, h, kv, d):
    q = rand(0, (b, h, d), jnp.float32)
    k = rand(1, (b, s, kv, d), jnp.float32)
    v = rand(2, (b, s, kv, d), jnp.float32)
    lengths = jnp.array([s // 2, s, max(s // 4, 1)][:b], jnp.int32)
    out = ops.flash_decode(q, k, v, lengths)
    want = ref.flash_decode_ref(q[:, None], k, v, lengths)[:, 0]
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)


def test_flash_decode_partials_merge():
    """Sequence-sharded decode: two half-cache partials LSE-merge to the
    full-cache answer (the model-axis sharded serving path)."""
    from repro.kernels.flash_decode import flash_decode_partial, merge_partials
    b, s, kv, h, d = 2, 512, 2, 4, 128
    q = rand(0, (b, h, d), jnp.float32)
    k = rand(1, (b, s, kv, d), jnp.float32)
    v = rand(2, (b, s, kv, d), jnp.float32)
    lengths = jnp.array([300, 512], jnp.int32)
    half = s // 2
    kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    p0 = flash_decode_partial(q, kt[:, :, :half], vt[:, :, :half],
                              jnp.minimum(lengths, half))
    p1 = flash_decode_partial(q, kt[:, :, half:], vt[:, :, half:],
                              jnp.maximum(lengths - half, 0))
    merged = merge_partials([p0, p1]).astype(jnp.float32)
    want = ref.flash_decode_ref(q[:, None], k, v, lengths)[:, 0]
    np.testing.assert_allclose(merged, want, atol=2e-4, rtol=2e-4)


def test_merge_partials_matches_full_softmax():
    """merge_partials is an exact LSE merge: combining per-segment
    unnormalized (o, m, l) triples reproduces the definitional
    full-sequence softmax, independent of how the sequence is cut."""
    from repro.kernels.flash_decode import merge_partials
    rng = np.random.default_rng(0)
    b, h, s, d = 2, 3, 40, 8
    scores = jnp.asarray(rng.normal(size=(b, h, s)) * 4.0, jnp.float32)
    values = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    # definitional softmax over the whole sequence
    p = jax.nn.softmax(scores, axis=-1)
    want = jnp.einsum("bhs,bhsd->bhd", p, values)
    # cut into ragged segments, build partials per segment
    parts = []
    for lo, hi in ((0, 7), (7, 16), (16, 40)):
        sc = scores[:, :, lo:hi]
        m = jnp.max(sc, -1, keepdims=True)
        e = jnp.exp(sc - m)
        l = jnp.sum(e, -1, keepdims=True)
        o = jnp.einsum("bhs,bhsd->bhd", e, values[:, :, lo:hi])
        parts.append((o, m, l))
    merged = merge_partials(parts)
    np.testing.assert_allclose(merged, want, atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# paged flash decode (block-table-indexed pages)
# --------------------------------------------------------------------------


N_LAYERS = 3     # layers of every test pool; reads name one of them


def _pool(k, v, table, bs, n_blocks, layer, kv="f32"):
    """Page dense (B, S, K, hd) k/v through ``table`` into layer ``layer``
    of a stacked (N_LAYERS, n_blocks, bs, K*hd) pool, stored as the
    serving cache stores it (f32, bf16, or int8 with (N_LAYERS, n_blocks,
    bs, K) scales). The other layers hold noise, so a read of the wrong
    layer shows. Returns (k_pool, v_pool, k_scale, v_scale)."""
    from repro.serving import cache as C
    b, s, n_kv, d = k.shape
    noise = np.random.default_rng(11)
    out, scales = [], []
    for x in (k, v):
        p = noise.standard_normal((N_LAYERS, n_blocks, bs, n_kv, d))
        p = p.astype(np.float32)
        p[layer] = 0.0
        for bi in range(b):
            for j in range(table.shape[1]):
                p[layer, table[bi, j]] = np.asarray(x[bi, j * bs:(j + 1) * bs])
        p, sc = C.quant_encode(jnp.asarray(p), "int8" if kv == "int8"
                               else "none")
        if kv == "bf16":
            p = p.astype(jnp.bfloat16)
        out.append(p.reshape(N_LAYERS, n_blocks, bs, n_kv * d))
        scales.append(None if sc is None
                      else sc.reshape(N_LAYERS, n_blocks, bs, n_kv))
    return (*out, *scales)


def _layer_view(pool, scale, layer, n_kv):
    """One layer of a folded pool, dequantized and viewed per head:
    (n_blocks, bs, K, hd) f32."""
    from repro.serving import cache as C
    x = pool[layer].reshape(pool.shape[1:3] + (n_kv, -1))
    sc = None if scale is None else scale[layer][..., None]
    return C.quant_decode(x, sc, jnp.float32)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (4, 1)])
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_flash_decode(impl, h, kv, layer):
    from repro.kernels.flash_decode import paged_flash_decode
    b, bs, mb, n_blocks, d = 2, 8, 3, 16, 32
    s = bs * mb
    q = rand(0, (b, h, d), jnp.float32)
    k = rand(1, (b, s, kv, d), jnp.float32)
    v = rand(2, (b, s, kv, d), jnp.float32)
    lengths = jnp.asarray([s - 3, bs + 1], jnp.int32)
    table = np.asarray([[5, 2, 9], [1, 12, 0]], np.int32)
    k_pool, v_pool, _, _ = _pool(k, v, table, bs, n_blocks, layer)
    out = paged_flash_decode(q, k_pool, v_pool, layer, jnp.asarray(table),
                             lengths, impl=impl, interpret=True)
    want = ref.flash_decode_ref(q[:, None], k, v, lengths)[:, 0]
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_flash_decode_int8(impl, layer):
    """Int8 pages dequantize in-kernel via the scale tensors; the result
    stays within int8 roundtrip error of the unquantized answer."""
    from repro.kernels.flash_decode import paged_flash_decode
    b, h, kv, bs, mb, n_blocks, d = 2, 4, 2, 8, 2, 8, 32
    s = bs * mb
    q = rand(0, (b, h, d), jnp.float32)
    k = rand(1, (b, s, kv, d), jnp.float32)
    v = rand(2, (b, s, kv, d), jnp.float32)
    lengths = jnp.asarray([s, s - 5], jnp.int32)
    table = np.asarray([[3, 1], [6, 4]], np.int32)
    kq, vq, ks, vs = _pool(k, v, table, bs, n_blocks, layer, kv="int8")
    out = paged_flash_decode(q, kq, vq, layer, jnp.asarray(table), lengths,
                             k_scale=ks, v_scale=vs, impl=impl,
                             interpret=True)
    want = ref.flash_decode_ref(q[:, None], k, v, lengths)[:, 0]
    np.testing.assert_allclose(out, want, atol=0.05, rtol=0.05)


# --------------------------------------------------------------------------
# paged multi-query kernel: T query rows per sequence share one page-tile
# fetch. One contract for fused decode (T=1), chunked prefill and
# speculative verify — Pallas (interpret mode: the fast lane) vs the
# bounded XLA fallback vs a dense softmax oracle.
# --------------------------------------------------------------------------


def _prefix_oracle(q, k_dense, v_dense, lengths):
    """Dense oracle: every window row attends the whole [0, lengths[b])
    prefix (no causal structure — the window's own tokens live in
    causal_self_partial, not here). Zero-length rows attend nothing."""
    b, t, h, d = q.shape
    n_kv = k_dense.shape[2]
    g = h // n_kv
    want = np.zeros((b, t, h, d), np.float32)
    for bi in range(b):
        ln = int(lengths[bi])
        if ln == 0:
            continue
        qg = np.asarray(q[bi], np.float32).reshape(t, n_kv, g, d)
        kk = np.asarray(k_dense[bi, :ln], np.float32)
        vv = np.asarray(v_dense[bi, :ln], np.float32)
        s = np.einsum("tkgd,skd->tkgs", qg, kk) / np.sqrt(d)
        p = np.asarray(jax.nn.softmax(jnp.asarray(s), axis=-1))
        want[bi] = np.einsum("tkgs,skd->tkgd", p, vv).reshape(t, h, d)
    return want


@pytest.mark.parametrize("t", [1, 4, 8])
@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (4, 1)])
def test_paged_mq_kernel_contract(t, kv_dtype, h, kv):
    """The Pallas multi-query kernel and the XLA fallback agree partial-
    for-partial, and their normalized output matches the dense oracle —
    across window widths, a padded table bucket (live columns < bucket),
    bf16 pages, int8 in-kernel dequant, MHA and GQA groups, a zero-length
    row, and a layer other than 0 of the stacked pool."""
    from repro.kernels import flash_decode as fd
    b, bs, mb, n_blocks, d, layer = 3, 4, 4, 16, 16, 1
    s = bs * mb
    q = rand(0, (b, t, h, d), jnp.float32)
    k = rand(1, (b, s, kv, d), jnp.float32)
    v = rand(2, (b, s, kv, d), jnp.float32)
    # full row / short row (trailing bucket columns dead) / zero-length row
    lengths = jnp.asarray([s, bs + 2, 0], jnp.int32)
    table = np.asarray([[5, 2, 9, 1], [3, 7, 0, 0], [0, 0, 0, 0]], np.int32)
    k_pool, v_pool, ks, vs = _pool(k, v, table, bs, n_blocks, layer,
                                   kv=kv_dtype)
    got = {}
    for impl in ("pallas", "xla"):
        got[impl] = fd.paged_flash_prefix_partial(
            q, k_pool, v_pool, layer, jnp.asarray(table), lengths,
            k_scale=ks, v_scale=vs, impl=impl, interpret=True)
    for a, b_ in zip(got["pallas"], got["xla"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-6, atol=1e-6)
    o, m, l = got["xla"]
    out = np.asarray(o / jnp.maximum(l, 1e-30))
    # the oracle reads the same (dequantized) pages through the table, so
    # tolerances stay tight even under bf16 and int8
    kd = _layer_view(k_pool, ks, layer, kv)[table].reshape(b, s, kv, d)
    vd = _layer_view(v_pool, vs, layer, kv)[table].reshape(b, s, kv, d)
    want = _prefix_oracle(q, kd, vd, lengths)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_paged_prefix_t1_matches_decode_partial(impl):
    """The multi-query read at T=1 IS the fused decode read: partials are
    bitwise-identical to paged_flash_decode_partial on both impls."""
    from repro.kernels import flash_decode as fd
    b, h, kv, bs, mb, n_blocks, d, layer = 2, 4, 2, 8, 2, 8, 32, 1
    s = bs * mb
    q = rand(0, (b, h, d), jnp.float32)
    k = rand(1, (b, s, kv, d), jnp.float32)
    v = rand(2, (b, s, kv, d), jnp.float32)
    lengths = jnp.asarray([s - 3, bs + 1], jnp.int32)
    table = np.asarray([[3, 1], [6, 4]], np.int32)
    k_pool, v_pool, _, _ = _pool(k, v, table, bs, n_blocks, layer)
    one = fd.paged_flash_decode_partial(
        q, k_pool, v_pool, layer, jnp.asarray(table), lengths, impl=impl,
        interpret=True)
    mq = fd.paged_flash_prefix_partial(
        q[:, None], k_pool, v_pool, layer, jnp.asarray(table), lengths,
        impl=impl, interpret=True)
    for a, b_ in zip(one, mq):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_[:, 0]))


@pytest.mark.parametrize("t", [None, 3])
def test_paged_bounded_scan_bitwise(t):
    """Bounding the XLA fallback at ceil(max(lengths)/block) live columns
    is bitwise-invisible: every partial equals the unbounded full-table
    scan (the skipped columns are provable no-ops), for the single-query
    (t=None) and multi-query paths alike."""
    from repro.kernels import flash_decode as fd
    b, h, kv, bs, mb, n_blocks, d, layer = 2, 4, 2, 4, 8, 16, 16, 2
    s = bs * mb
    qshape = (b, h, d) if t is None else (b, t, h, d)
    q = rand(0, qshape, jnp.float32)
    k = rand(1, (b, s, kv, d), jnp.float32)
    v = rand(2, (b, s, kv, d), jnp.float32)
    # all lengths end far before the last table column (and one row is 0)
    lengths = jnp.asarray([bs + 1, 0], jnp.int32)
    table = np.asarray([list(range(1, 9)), list(range(8, 0, -1))], np.int32)
    k_pool, v_pool, _, _ = _pool(k, v, table, bs, n_blocks, layer)
    fn = (fd.paged_flash_decode_partial if t is None
          else fd.paged_flash_prefix_partial)
    outs = {bound: fn(q, k_pool, v_pool, layer, jnp.asarray(table), lengths,
                      impl="xla", bound_scan=bound)
            for bound in (True, False)}
    for a, b_ in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


# --------------------------------------------------------------------------
# SSD
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,h,p,g,n,chunk", [
    (1, 128, 2, 64, 1, 128, 64),
    (2, 256, 4, 64, 2, 64, 128),
    (1, 96, 2, 64, 1, 16, 32),       # jamba-like small state + ragged T
])
def test_ssd_kernel_vs_sequential_ref(b, t, h, p, g, n, chunk):
    x = rand(0, (b, t, h, p), jnp.float32) * 0.5
    B = rand(1, (b, t, g, n), jnp.float32) * 0.5
    C = rand(2, (b, t, g, n), jnp.float32) * 0.5
    dt = jax.nn.softplus(rand(3, (b, t, h), jnp.float32))
    A = -jnp.exp(rand(4, (h,), jnp.float32) * 0.3)
    D = rand(5, (h,), jnp.float32)
    y, state = ops.ssd(x, B, C, dt, A, D, chunk=chunk)
    y_ref, state_ref = ref.ssd_ref(x, B, C, dt, A, D)
    np.testing.assert_allclose(y, y_ref, atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(state, state_ref, atol=2e-4, rtol=2e-3)


def test_ssd_chunked_jnp_matches_sequential():
    """models/ssd.py chunked reference == definitional sequential scan."""
    b, t, h, p, g, n = 2, 192, 4, 32, 1, 48
    x = rand(0, (b, t, h, p), jnp.float32) * 0.5
    B = rand(1, (b, t, g, n), jnp.float32) * 0.5
    C = rand(2, (b, t, g, n), jnp.float32) * 0.5
    dt = jax.nn.softplus(rand(3, (b, t, h), jnp.float32))
    A = -jnp.exp(rand(4, (h,), jnp.float32) * 0.3)
    D = rand(5, (h,), jnp.float32)
    y1, s1 = ssd_chunked_ref(x, B, C, dt, A, D, chunk=64)
    y2, s2 = ref.ssd_ref(x, B, C, dt, A, D)
    np.testing.assert_allclose(y1, y2, atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(s1, s2, atol=2e-4, rtol=2e-3)


# --------------------------------------------------------------------------
# rmsnorm / int8 matmul
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rows,d", [(64, 128), (1024, 512), (333, 256)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rmsnorm_kernel(rows, d, dtype):
    x = rand(0, (rows, d), dtype)
    w = rand(1, (d,), dtype) + 1.0
    out = ops.rmsnorm(x, w)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), **TOL[dtype])


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (64, 512, 384)])
def test_int8_matmul_kernel(m, k, n):
    from repro.quant.qtensor import quantize_int8
    x = rand(0, (m, k), jnp.bfloat16)
    w = rand(1, (k, n), jnp.bfloat16)
    qt = quantize_int8(w)
    out = ops.int8_matmul(x, qt.data, qt.scale)
    want = ref.int8_matmul_ref(x, qt.data, qt.scale)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=5e-2, rtol=5e-2)
