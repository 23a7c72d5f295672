"""Flash-decoding for TPU: single-token attention against a long KV cache,
split over KV blocks with online softmax, emitting (o, m, l) partials so a
sequence-sharded cache (model-axis, see DESIGN §4) can LSE-merge across
shards with one tiny collective.

Two kernel families live here:

  * :func:`flash_decode_partial` — dense cache, q (B, H, D) against
    k, v (B, K, S, D) with `lengths` (B,) valid prefixes.
  * :func:`paged_flash_decode_partial` — **paged** cache: K/V stay in the
    stacked (L, n_blocks, block, K*hd) HBM pool, one lane-dense row of all
    kv heads per token, and are read *through the block table* at a named
    layer with a scalar-prefetch BlockSpec index_map, so neither the dense
    (B, max_blocks*block, K, hd) gather nor a per-layer slice of the pool
    ever materializes. Int8 KV (LightLLM 'Int8KV' analogue) dequantizes
    block-wise in VMEM via the per-(layer, block, position, head) scales.
  * :func:`paged_flash_prefix_partial` — the **multi-query** generalization:
    T query rows per sequence against the same paged prefix. The serving
    engine runs fused decode (T=1), chunked prefill and speculative verify
    through this one family; the Pallas kernel moves one whole page (all
    kv heads) per grid step and packs all T rows of a kv group into one
    row tile, so a page is fetched into VMEM exactly once per sequence
    and dotted against every query row.

The paged variants also ship an XLA fallback (`impl="xla"`) with identical
partial semantics — a column loop over the block table that gathers one
block per sequence per step — used on backends where Pallas would run in
interpret mode (see kernels/ops.default_interpret). The fallback bounds
the loop at ``ceil(max(lengths)/block)`` live columns instead of scanning
every table column; the skipped tail is provably a bitwise no-op (masked
scores contribute exp-weight 0 and a max/correction of exactly 1.0), and
``bound_scan=False`` keeps the unbounded scan around as the regression
oracle for that contract.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._interpret import resolve_interpret as _default_interpret

NEG_INF = -1e30


# ==========================================================================
# Dense-cache flash decode
# ==========================================================================


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, mm_ref, ll_ref, *, bk, scale, n_blocks, g):
    jb = pl.program_id(2)

    @pl.when(jb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mm_ref[...] = jnp.full_like(mm_ref, NEG_INF)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    length = len_ref[0]
    run = jb * bk < length

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bk)
        kpos = jb * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = mm_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        ll_ref[...] = ll_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        mm_ref[...] = m_new

    @pl.when(jb == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)   # unnormalized
        m_ref[0, 0] = mm_ref[...]
        l_ref[0, 0] = ll_ref[...]


def flash_decode_partial(q, k, v, lengths, *, bk: int = 256,
                         interpret: Optional[bool] = None,
                         sm_scale: float = None):
    """Returns unnormalized (o (B,H,D) f32, m (B,H,1), l (B,H,1)); caller
    merges across shards then normalizes: out = o_merged / l_merged."""
    interpret = _default_interpret(interpret)
    b, h, d = q.shape
    n_kv, s = k.shape[1], k.shape[2]
    g = h // n_kv
    bk = min(bk, s)
    assert s % bk == 0
    qg = q.reshape(b, n_kv, g, d)
    kernel = functools.partial(_decode_kernel, bk=bk,
                               scale=(sm_scale or 1.0 / np.sqrt(d)),
                               n_blocks=s // bk, g=g)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(b, n_kv, s // bk),
        in_specs=[
            pl.BlockSpec((1,), lambda b_, k_, j: (b_,)),
            pl.BlockSpec((1, 1, g, d), lambda b_, k_, j: (b_, k_, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, k_, j: (b_, k_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, k_, j: (b_, k_, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, k_, j: (b_, k_, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b_, k_, j: (b_, k_, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b_, k_, j: (b_, k_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_kv, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, g, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(lengths, qg, k, v)
    return (o.reshape(b, h, d), m.reshape(b, h, 1), l.reshape(b, h, 1))


def flash_decode(q, k, v, lengths, *, bk: int = 256,
                 interpret: Optional[bool] = None, sm_scale: float = None):
    o, m, l = flash_decode_partial(q, k, v, lengths, bk=bk,
                                   interpret=interpret, sm_scale=sm_scale)
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def merge_partials(parts):
    """LSE-merge a list of (o, m, l) partials (e.g. gathered across the
    model axis for a sequence-sharded cache, or cache + fresh-token)."""
    os_, ms, ls = zip(*parts)
    m_glob = functools.reduce(jnp.maximum, ms)
    o = sum(o_ * jnp.exp(m_ - m_glob) for o_, m_ in zip(os_, ms))
    l = sum(l_ * jnp.exp(m_ - m_glob) for l_, m_ in zip(ls, ms))
    return o / jnp.maximum(l, 1e-30)


# ==========================================================================
# Paged reads: block-table-indexed pages of a layer-addressed pool, no
# dense materialization. ONE read family serves fused decode (T=1),
# chunked prefill (T=chunk) and speculative verify (T=window) — each page
# tile is gathered once and dotted against every query row, so the HBM
# traffic per token shrinks by the window width (the whole point of
# speculation and chunking on a bandwidth-bound read path). Pallas packs
# the rows into one VMEM tile (:func:`_paged_mq_pallas`); the XLA
# fallback loops over live table columns with identical partial
# semantics.
#
# The pool is every attention layer's storage stacked,
# (L, n_blocks, block, K*hd): the kv heads of a token sit side by side on
# the lane axis, and a read names its layer. Both reads address the pool
# in place — no per-layer slice, no re-layout.
# ==========================================================================


def _paged_kernel(tbl_ref, len_ref, lay_ref, q_ref, k_ref, v_ref, *rest,
                  bs, d, scale, n_tblk, n_kv, quant):
    del lay_ref     # consumed by the index maps
    if quant:
        (ks_ref, vs_ref, o_ref, m_ref, l_ref,
         acc_ref, mm_ref, ll_ref) = rest
    else:
        o_ref, m_ref, l_ref, acc_ref, mm_ref, ll_ref = rest
    ib = pl.program_id(0)
    jb = pl.program_id(1)

    @pl.when(jb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mm_ref[...] = jnp.full_like(mm_ref, NEG_INF)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    length = len_ref[ib]

    @pl.when(jb * bs < length)
    def _block():
        # one page tile holds every kv head side by side on the lane axis;
        # walk them with static lane slices
        for hk in range(n_kv):
            lanes = slice(hk * d, (hk + 1) * d)
            q = q_ref[0, hk].astype(jnp.float32) * scale     # (rows, D)
            k = k_ref[0, 0, :, lanes].astype(jnp.float32)    # (bs, D)
            v = v_ref[0, 0, :, lanes].astype(jnp.float32)
            if quant:  # int8 pages: dequantize block-wise in VMEM
                k = k * ks_ref[0, 0, :, hk:hk + 1]
                v = v * vs_ref[0, 0, :, hk:hk + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            kpos = jb * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < length, s, NEG_INF)
            m_prev = mm_ref[hk]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            ll_ref[hk] = ll_ref[hk] * corr + jnp.sum(p, -1, keepdims=True)
            acc_ref[hk] = acc_ref[hk] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            mm_ref[hk] = m_new

    @pl.when(jb == n_tblk - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)      # unnormalized
        m_ref[0] = mm_ref[...]
        l_ref[0] = ll_ref[...]


def _paged_mq_pallas(q, k_pool, v_pool, layer, table, lengths, k_scale,
                     v_scale, *, sm_scale, interpret, name):
    """Pallas multi-query paged partials: q (B, T, H, D) against the paged
    prefix of layer ``layer``. The grid is (B, table columns) and each
    step moves ONE whole page of that layer, all kv heads at once: block
    (1, 1, bs, K*hd) at (layer, table[b, j]) of the (L, n_blocks, bs, K*hd)
    pool, the layer index being the third scalar-prefetch operand. The
    block's last two dims equal the pool's, which is what Mosaic's tiling
    rule asks of a block, and the int8 scale block (1, 1, bs, K) passes the
    same way. All T rows (times their G heads per kv group) of a head are
    packed into one row tile, so a page is fetched once per sequence and
    dotted against the whole query window. ``name`` names the kernel on
    the device: ``paged_prefix`` for a T-row window (chunked prefill,
    verify), ``paged_decode`` for fused decode's one row."""
    b, tq, h, d = q.shape
    bs, width = k_pool.shape[2:]
    n_kv = width // d
    g = h // n_kv
    rows = tq * g
    mb = table.shape[1]
    quant = k_scale is not None
    # group query rows by kv head: (B, K, T*G, D), T-major within a group
    qg = q.reshape(b, tq, n_kv, g, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, n_kv, rows, d)
    kernel = functools.partial(_paged_kernel, bs=bs, d=d, n_tblk=mb,
                               n_kv=n_kv, quant=quant,
                               scale=(sm_scale or 1.0 / np.sqrt(d)))

    # scalar-prefetch index maps: page blocks are addressed *through the
    # block table* within the named layer, so only the live pages of each
    # sequence ever move.
    def page_idx(b_, j, tbl, lens, lay):
        return (lay[0], tbl[b_, j], 0, 0)

    def row_idx(b_, j, tbl, lens, lay):
        return (b_, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, n_kv, rows, d), row_idx),
        pl.BlockSpec((1, 1, bs, width), page_idx),
        pl.BlockSpec((1, 1, bs, width), page_idx),
    ]
    inputs = [qg, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, bs, n_kv), page_idx),
                     pl.BlockSpec((1, 1, bs, n_kv), page_idx)]
        inputs += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, n_kv, rows, d), row_idx),
            pl.BlockSpec((1, n_kv, rows, 1), row_idx),
            pl.BlockSpec((1, n_kv, rows, 1), row_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_kv, rows, d), jnp.float32),
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_kv, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, rows, 1), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(table, lengths, jnp.asarray(layer, jnp.int32).reshape(1), *inputs)

    def unpack(a):
        last = a.shape[-1]
        a = a.reshape(b, n_kv, tq, g, last).transpose(0, 2, 1, 3, 4)
        return a.reshape(b, tq, h, last)

    return unpack(o), unpack(m), unpack(l)


def _live_cols(lengths, bs: int, mb: int):
    """Leading table columns any row can still touch: ceil(max(len)/block).
    Every later column is fully masked for every row, which makes it a
    bitwise no-op in the online-softmax recurrence (p == 0 exactly,
    correction == exp(0) == 1.0 exactly), so the loop can stop there."""
    mx = jnp.max(lengths.astype(jnp.int32))
    return jnp.minimum(jnp.asarray(mb, jnp.int32), (mx + bs - 1) // bs)


def _paged_prefix_xla(q, k_pool, v_pool, layer, table, lengths, k_scale,
                      v_scale, *, sm_scale, bound_scan: bool = True):
    """XLA fallback: an online-softmax loop over table columns, T query
    rows wide. Each step gathers one (B, block, K*hd) page tile of layer
    ``layer`` — memory stays O(B * block) — views it per head and reuses
    it for all T rows. The loop covers only the live columns (see
    :func:`_live_cols`) unless ``bound_scan=False`` forces the full-width
    regression oracle."""
    b, tq, h, d = q.shape
    bs, width = k_pool.shape[2:]
    n_kv = width // d
    g = h // n_kv
    mb = table.shape[1]
    scale = sm_scale or 1.0 / np.sqrt(d)
    qg = q.reshape(b, tq, n_kv, g, d).astype(jnp.float32) * scale

    def col(j, carry):
        m, l, acc = carry
        blk = table[:, j]                                   # (B,)
        k = k_pool[layer, blk].reshape(b, bs, n_kv, d).astype(jnp.float32)
        v = v_pool[layer, blk].reshape(b, bs, n_kv, d).astype(jnp.float32)
        if k_scale is not None:
            k = k * k_scale[layer, blk][..., None]
            v = v * v_scale[layer, blk][..., None]
        s = jnp.einsum("btkgd,bskd->btkgs", qg, k)          # (B,T,K,G,bs)
        kpos = j * bs + jnp.arange(bs)
        valid = (kpos[None, :] < lengths[:, None])[:, None, None, None, :]
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        # mask p explicitly: a row with no valid prefix position yet would
        # otherwise give exp(NEG_INF - NEG_INF) = 1 weight to garbage (the
        # Pallas kernel emits 0s)
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, -1)
        acc = acc * corr[..., None] + jnp.einsum("btkgs,bskd->btkgd", p, v)
        return (m_new, l, acc)

    init = (jnp.full((b, tq, n_kv, g), NEG_INF, jnp.float32),
            jnp.zeros((b, tq, n_kv, g), jnp.float32),
            jnp.zeros((b, tq, n_kv, g, d), jnp.float32))
    upper = _live_cols(lengths, bs, mb) if bound_scan else mb
    m, l, acc = jax.lax.fori_loop(0, upper, col, init)
    return (acc.reshape(b, tq, h, d), m.reshape(b, tq, h, 1),
            l.reshape(b, tq, h, 1))


def _paged_read(q, k_pool, v_pool, layer, table, lengths, k_scale, v_scale,
                *, impl, interpret, sm_scale, bound_scan, name):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        return _paged_mq_pallas(q, k_pool, v_pool, layer, table, lengths,
                                k_scale, v_scale, sm_scale=sm_scale,
                                interpret=_default_interpret(interpret),
                                name=name)
    if impl == "xla":
        return _paged_prefix_xla(q, k_pool, v_pool, layer, table, lengths,
                                 k_scale, v_scale, sm_scale=sm_scale,
                                 bound_scan=bound_scan)
    raise ValueError(f"unknown paged read impl {impl!r}")


def paged_flash_prefix_partial(q, k_pool, v_pool, layer, table, lengths, *,
                               k_scale=None, v_scale=None,
                               impl: str = "auto",
                               interpret: Optional[bool] = None,
                               sm_scale: float = None,
                               bound_scan: bool = True):
    """Attention partials of a T-token window against one layer's pages.

    q: (B, T, H, D); k_pool/v_pool: the stacked (L, n_blocks, block, K*hd)
    storage, heads folded into the last axis; layer: which of the L
    layers to read (an int or a traced int32 scalar); k_scale/v_scale:
    the int8 pool's (L, n_blocks, block, K) scales; table: (B, max_blocks)
    int32; lengths: (B,) valid prefix lengths — every row of the window
    attends the same [0, lengths[b]) prefix (the window's own tokens are
    NOT in the pages; merge their causal self-attention via
    :func:`causal_self_partial` + :func:`merge_partials`).
    Returns unnormalized (o (B,T,H,D) f32, m (B,T,H,1), l (B,T,H,1)).

    impl: "pallas" (the multi-query row-packed kernel), "xla" (bounded
    column loop), or "auto" — pallas on TPU, xla elsewhere.
    ``bound_scan=False`` (xla only) forces the unbounded full-table scan,
    the regression oracle for the bounded-loop bitwise contract.
    """
    return _paged_read(q, k_pool, v_pool, layer, table, lengths, k_scale,
                       v_scale, impl=impl, interpret=interpret,
                       sm_scale=sm_scale, bound_scan=bound_scan,
                       name="paged_prefix")


def paged_flash_decode_partial(q, k_pool, v_pool, layer, table, lengths, *,
                               k_scale=None, v_scale=None, impl: str = "auto",
                               interpret: Optional[bool] = None,
                               sm_scale: float = None,
                               bound_scan: bool = True):
    """Single-token attention against one layer's pages: the T=1 member
    of :func:`paged_flash_prefix_partial` (same pool, layer, table and
    scale arguments), with q (B, H, D) and partials (B, H, D) / (B, H, 1).
    The fresh token is NOT in the pages — merge it with
    :func:`merge_partials`. On the device the kernel is ``paged_decode``.
    """
    o, m, l = _paged_read(q[:, None], k_pool, v_pool, layer, table, lengths,
                          k_scale, v_scale, impl=impl, interpret=interpret,
                          sm_scale=sm_scale, bound_scan=bound_scan,
                          name="paged_decode")
    return o[:, 0], m[:, 0], l[:, 0]


def paged_flash_decode(q, k_pool, v_pool, layer, table, lengths, *,
                       k_scale=None, v_scale=None, impl: str = "auto",
                       interpret: Optional[bool] = None,
                       sm_scale: float = None):
    """Normalized paged decode output (B, H, D) in q.dtype."""
    o, m, l = paged_flash_decode_partial(
        q, k_pool, v_pool, layer, table, lengths, k_scale=k_scale,
        v_scale=v_scale, impl=impl, interpret=interpret, sm_scale=sm_scale)
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def causal_self_partial(q, k, v, *, sm_scale: float = None):
    """Unnormalized causal self-attention partials of a fresh T-token chunk.

    Row i attends columns j <= i (rows and columns share positions — the
    chunk sits after the paged prefix, so the cross terms live in
    :func:`paged_flash_prefix_partial`). q (B,T,H,D), k/v (B,T,K,hd)
    already storage-roundtripped; returns (o f32, m, l) shaped like
    :func:`paged_flash_prefix_partial` for one :func:`merge_partials` call.
    For T=1 this degenerates to the fused decode step's analytic fresh-token
    partial: m = q·k·scale, l = 1, o = v.
    """
    b, t, h, d = q.shape
    n_kv = k.shape[2]
    g = h // n_kv
    scale = sm_scale or 1.0 / np.sqrt(d)
    qg = q.reshape(b, t, n_kv, g, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bikgd,bjkd->bikgj", qg, kf) * scale     # (B,T,K,G,T)
    mask = (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])
    mask = mask[None, :, None, None, :]                     # (1,T,1,1,T)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, -1, keepdims=True)                       # diag always live
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum("bikgj,bjkd->bikgd", p, vf)
    return (o.reshape(b, t, h, d), m.reshape(b, t, h, 1),
            l.reshape(b, t, h, 1))
