"""Transformer / SSM / MoE blocks: ParamSpec builders + pure apply fns.

Every block takes a ``ctx`` (parallel.sharding.ShardCtx or None) used only to
(a) place sharding constraints on activations and (b) drive the expert-
parallel all-to-all path in MoE. With ``ctx=None`` everything runs locally
(CPU smoke tests).

Cache conventions (decode):
  attn: {"k": (B,S,K,hd), "v": (B,S,K,hd)}           + global `lengths` (B,)
  ssm:  {"conv": (B, w-1, Cch), "state": (B,H,P,N)}
  cross (enc-dec): {"k","v"} precomputed from encoder output.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ArchConfig
from repro.models import layers as L
from repro.models.params import spec
from repro.models.ssd import ssd_chunked, ssd_decode_scan, ssd_decode_step


def _constrain(ctx, x, kind):
    return ctx.constrain(x, kind) if ctx is not None else x


# ==========================================================================
# Attention block
# ==========================================================================


def attn_specs(cfg: ArchConfig, n_stack: int, cross: bool = False) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = (n_stack,)
    ly = ("layers",)
    p = {
        "ln": spec(s + (d,), ly + ("embed",), "ones"),
        "wq": spec(s + (d, h, hd), ly + ("embed", "q_heads", "head_dim")),
        "wk": spec(s + (d, kv, hd), ly + ("embed", "kv_heads", "head_dim")),
        "wv": spec(s + (d, kv, hd), ly + ("embed", "kv_heads", "head_dim")),
        "wo": spec(s + (h, hd, d), ly + ("q_heads", "head_dim", "embed"),
                   fan_in_axes=(0, 1)),
    }
    if cfg.qkv_bias:
        p["bq"] = spec(s + (h, hd), ly + ("q_heads", "head_dim"), "zeros")
        p["bk"] = spec(s + (kv, hd), ly + ("kv_heads", "head_dim"), "zeros")
        p["bv"] = spec(s + (kv, hd), ly + ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        p["q_norm"] = spec(s + (hd,), ly + ("head_dim",), "ones")
        p["k_norm"] = spec(s + (hd,), ly + ("head_dim",), "ones")
    return p


def _qkv(x, p, cfg: ArchConfig, ctx, positions, rope: bool = True):
    # the projection -> norm -> rope chain runs as REAL f32 tensors with
    # ONE rounding at the end (qk_headnorm and apply_rope are dtype-
    # preserving, so f32 stays f32 throughout). Intermediate narrowing
    # here is the excess-precision trap described in layers.swiglu: which
    # rounds survive would depend on fusion shape, and q/k/v feed int8 KV
    # quantization in the serving engine, where a one-ulp input flip moves
    # a whole vector's scale.
    q = L.dense(x, p["wq"], bias=p.get("bq"), out_dtype=jnp.float32)
    k = L.dense(x, p["wk"], bias=p.get("bk"), out_dtype=jnp.float32)
    v = L.dense(x, p["wv"], bias=p.get("bv"), out_dtype=jnp.float32)
    if cfg.qk_norm:
        q = L.qk_headnorm(q, p["q_norm"], cfg.norm_eps)
        k = L.qk_headnorm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = L.apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
    q = _constrain(ctx, q.astype(x.dtype), "act_q")
    k = _constrain(ctx, k.astype(x.dtype), "act_kv")
    v = _constrain(ctx, v.astype(x.dtype), "act_kv")
    return q, k, v


def attn_apply(x, p, cfg: ArchConfig, ctx, *, attn_impl: str, positions,
               causal: bool = True, cache: Optional[Dict] = None,
               lengths: Optional[jax.Array] = None,
               return_kv: bool = False) -> Tuple[jax.Array, Any]:
    """Self-attention residual block.

    train/prefill: cache is None; optionally returns the fresh (k, v).
    decode: cache holds (B,S,K,hd); new token written at `lengths`.
    """
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    q, k, v = _qkv(h, p, cfg, ctx, positions)
    new_cache = None
    if cache is not None:  # decode: update paged cache then attend over it
        kc, vc = cache["k"], cache["v"]
        s = kc.shape[1]
        slot = jnp.clip(lengths, 0, s - 1)                       # (B,)
        write = (jnp.arange(s)[None, :] == slot[:, None])        # (B,S)
        m = write[:, :, None, None]
        kc = jnp.where(m, k.astype(kc.dtype), kc)
        vc = jnp.where(m, v.astype(vc.dtype), vc)
        kc = _constrain(ctx, kc, "kv_cache")
        vc = _constrain(ctx, vc, "kv_cache")
        new_cache = {"k": kc, "v": vc}
        out = L.attention(q, kc.astype(q.dtype), vc.astype(q.dtype),
                          mode="naive" if attn_impl != "pallas" else "pallas_decode",
                          causal=False, kv_len=lengths + 1)
    else:
        out = L.attention(q, k, v, mode=attn_impl, causal=causal)
        if return_kv:
            new_cache = {"k": k, "v": v}
    out = _constrain(ctx, out, "act_q")
    y = L.dense(out, p["wo"], n_in=2)
    y = _constrain(ctx, y, "hidden")
    return x + y, new_cache


def cross_attn_specs(cfg: ArchConfig, n_stack: int) -> Dict:
    p = attn_specs(cfg, n_stack)
    p.pop("q_norm", None), p.pop("k_norm", None)
    return p


def cross_attn_apply(x, enc_kv, p, cfg: ArchConfig, ctx) -> jax.Array:
    """Cross-attention: Q from decoder stream, KV precomputed from encoder."""
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    q = L.dense(h, p["wq"], bias=p.get("bq"))
    q = _constrain(ctx, q, "act_q")
    out = L.attention(q, enc_kv["k"], enc_kv["v"], mode="naive", causal=False)
    y = L.dense(out, p["wo"], n_in=2)
    return x + _constrain(ctx, y, "hidden")


def cross_kv(enc_out, p, cfg: ArchConfig, ctx) -> Dict:
    k = L.dense(enc_out, p["wk"], bias=p.get("bk"))
    v = L.dense(enc_out, p["wv"], bias=p.get("bv"))
    return {"k": _constrain(ctx, k, "act_kv"), "v": _constrain(ctx, v, "act_kv")}


# ==========================================================================
# Dense FFN (SwiGLU)
# ==========================================================================


def ffn_specs(cfg: ArchConfig, n_stack: int) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    s, ly = (n_stack,), ("layers",)
    return {
        "ln": spec(s + (d,), ly + ("embed",), "ones"),
        "w_gate": spec(s + (d, f), ly + ("embed", "mlp")),
        "w_up": spec(s + (d, f), ly + ("embed", "mlp")),
        "w_down": spec(s + (f, d), ly + ("mlp", "embed")),
    }


def ffn_apply(x, p, cfg: ArchConfig, ctx) -> jax.Array:
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    y = L.swiglu(h, p["w_gate"], p["w_up"], p["w_down"],
                 act_constraint=lambda t: _constrain(ctx, t, "act_ffn"))
    return x + _constrain(ctx, y, "hidden")


# ==========================================================================
# MoE FFN: top-k routing; dense path (smoke) or EP all-to-all (shard_map)
# ==========================================================================


def moe_specs(cfg: ArchConfig, n_stack: int) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    s, ly = (n_stack,), ("layers",)
    return {
        "ln": spec(s + (d,), ly + ("embed",), "ones"),
        "router": spec(s + (d, e), ly + ("embed", "null")),
        "w_gate": spec(s + (e, d, f), ly + ("experts", "embed", "mlp"),
                       fan_in_axes=(1,)),
        "w_up": spec(s + (e, d, f), ly + ("experts", "embed", "mlp"),
                     fan_in_axes=(1,)),
        "w_down": spec(s + (e, f, d), ly + ("experts", "mlp", "embed"),
                       fan_in_axes=(1,)),
    }


def _route(h, router_w, cfg: ArchConfig):
    logits = L.dense(h.astype(jnp.float32), router_w).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                    # (..., E)
    top_w, top_e = jax.lax.top_k(gates, cfg.top_k)             # (..., k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch-style), returned for training
    me = jnp.mean(gates, axis=tuple(range(gates.ndim - 1)))
    ce = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], cfg.n_experts, dtype=jnp.float32),
        axis=tuple(range(top_e.ndim - 1)))
    aux = cfg.n_experts * jnp.sum(me * ce)
    return top_w, top_e, aux


def _expert_ffn(xs, wg, wu, wd):
    """xs: (E, C, D); weights (E, D, F)/(E, F, D). Batched SwiGLU.

    Accumulates in f32 and keeps the gate activation in f32 into the down
    projection, rounding once at the end — same rationale as
    layers.swiglu: the EP shard_map boundary (and a TP-sharded mlp axis)
    changes fusion shapes, and any bf16 materialization point that XLA's
    excess-precision pass elides in one executable but not the other
    breaks the EP-vs-local (and TP-vs-single-device) bitwise match."""
    g = jnp.einsum("ecd,edf->ecf", xs, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", xs, wu,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("ecf,efd->ecd", L.silu(g) * u, wd,
                      preferred_element_type=jnp.float32).astype(xs.dtype)


def _moe_local(h, p, cfg: ArchConfig, capacity_mult: float) -> Tuple[jax.Array, jax.Array]:
    """Single-device token-choice dispatch with capacity (sort-based,
    no (T,E,C) one-hot). Used for smoke tests and inside each shard."""
    wg = p["w_gate"].dequantize(h.dtype) if hasattr(p["w_gate"], "dequantize") else p["w_gate"]
    wu = p["w_up"].dequantize(h.dtype) if hasattr(p["w_up"], "dequantize") else p["w_up"]
    wd = p["w_down"].dequantize(h.dtype) if hasattr(p["w_down"], "dequantize") else p["w_down"]
    orig_shape = h.shape
    d, e, k = cfg.d_model, cfg.n_experts, cfg.top_k
    x = h.reshape(-1, d)
    n = x.shape[0]
    top_w, top_e, aux = _route(x, p["router"], cfg)
    cap = int(np.ceil(k * n / e * cfg.capacity_factor * capacity_mult))
    cap = min(max(cap, 4), k * n)
    flat_e = top_e.reshape(-1)                                  # (n*k,)
    flat_w = top_w.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(n), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(sorted_e, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(n * k) - starts[sorted_e]
    pos = jnp.zeros((n * k,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)         # drop -> pad row
    buf = jnp.zeros((e * cap + 1, d), h.dtype).at[slot].set(x[flat_t])
    ys = _expert_ffn(buf[:-1].reshape(e, cap, d), wg, wu, wd)   # (E,C,D)
    ys = jnp.concatenate([ys.reshape(e * cap, d),
                          jnp.zeros((1, d), h.dtype)])
    # gate-weighted combine in f32, rounded once — must stay structurally
    # identical to the _moe_ep tail (bitwise EP-vs-local contract)
    gathered = ys[slot].astype(jnp.float32) * flat_w[:, None]   # (n*k, D)
    out = jnp.zeros((n, d), jnp.float32).at[flat_t].add(
        jnp.where(keep[:, None], gathered, 0.0)).astype(h.dtype)
    return out.reshape(orig_shape), aux


def _moe_ep(h, p, cfg: ArchConfig, ctx, capacity_mult: float):
    """Expert-parallel dispatch: shard_map over the mesh; tokens exchanged
    with all-to-all along the model axis (experts sharded over `model`)."""
    from jax.sharding import PartitionSpec as P

    d, e, k = cfg.d_model, cfg.n_experts, cfg.top_k
    mesh = ctx.mesh
    maxis = ctx.model_axis
    msize = ctx.axis_size(maxis)
    e_loc = e // msize
    b, t = h.shape[0], h.shape[1]
    dp = ctx._dp(b)         # None when the batch can't split (e.g. B=1)
    split_t = (t % msize == 0) and t > 1
    h_spec = P(dp, maxis if split_t else None, None)

    def local(hh, router_w, wg, wu, wd):
        # dequantize the *local* expert shard only (weight-resident int8)
        wg = wg.dequantize(hh.dtype) if hasattr(wg, "dequantize") else wg
        wu = wu.dequantize(hh.dtype) if hasattr(wu, "dequantize") else wu
        wd = wd.dequantize(hh.dtype) if hasattr(wd, "dequantize") else wd
        x = hh.reshape(-1, d)
        n = x.shape[0]
        top_w, top_e, aux = _route(x, router_w, cfg)
        cap = int(np.ceil(k * n / e * cfg.capacity_factor * capacity_mult))
        cap = min(max(cap, 4), k * n)
        flat_e = top_e.reshape(-1)
        flat_w = top_w.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(n), k)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.bincount(sorted_e, length=e)
        starts = jnp.cumsum(counts) - counts
        pos_sorted = jnp.arange(n * k) - starts[sorted_e]
        pos = jnp.zeros((n * k,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32))
        keep = pos < cap
        slot = jnp.where(keep, flat_e * cap + pos, e * cap)
        send = jnp.zeros((e * cap + 1, d), hh.dtype).at[slot].set(x[flat_t])
        send = send[:-1].reshape(msize, e_loc * cap, d)
        recv = jax.lax.all_to_all(send, maxis, 0, 0, tiled=False)
        # recv: (msize, e_loc*cap, d) -> (e_loc, msize*cap, d)
        xs = recv.reshape(msize, e_loc, cap, d).transpose(1, 0, 2, 3) \
                 .reshape(e_loc, msize * cap, d)
        ys = _expert_ffn(xs, wg, wu, wd)
        ys = ys.reshape(e_loc, msize, cap, d).transpose(1, 0, 2, 3) \
               .reshape(msize, e_loc * cap, d)
        back = jax.lax.all_to_all(ys, maxis, 0, 0, tiled=False)
        back = jnp.concatenate([back.reshape(e * cap, d),
                                jnp.zeros((1, d), hh.dtype)])
        # f32 combine, rounded once — mirrors the _moe_local tail exactly
        gathered = back[slot].astype(jnp.float32) * flat_w[:, None]
        out = jnp.zeros((n, d), jnp.float32).at[flat_t].add(
            jnp.where(keep[:, None], gathered, 0.0)).astype(hh.dtype)
        # aux loss: average over every mesh axis the input is split on
        aux = jax.lax.pmean(aux, maxis)
        for ax in (dp if isinstance(dp, tuple) else (dp,)):
            if ax is not None:
                aux = jax.lax.pmean(aux, ax)
        return out.reshape(hh.shape), aux

    wq_specs = (P(None, None), P(maxis, None, None), P(maxis, None, None),
                P(maxis, None, None))
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(h_spec,) + wq_specs,
                       out_specs=(h_spec, P()),
                       check_vma=False)
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    return fn(h, p["router"], wg, wu, wd)


def moe_apply(x, p, cfg: ArchConfig, ctx, capacity_mult: float = 1.0
              ) -> Tuple[jax.Array, jax.Array]:
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    use_ep = (ctx is not None and ctx.model_axis is not None
              and cfg.n_experts % ctx.axis_size(ctx.model_axis) == 0
              and not ctx.technique_disables_ep)
    if use_ep:
        y, aux = _moe_ep(h, p, cfg, ctx, capacity_mult)
    else:
        y, aux = _moe_local(h, p, cfg, capacity_mult)
    return x + _constrain(ctx, y, "hidden"), aux


# ==========================================================================
# Mamba2 (SSD) block
# ==========================================================================


def ssm_specs(cfg: ArchConfig, n_stack: int) -> Dict:
    d, di = cfg.d_model, cfg.d_inner
    g, n_ssm, ns = cfg.ssm_ngroups, cfg.n_ssm_heads, cfg.ssm_state
    conv_ch = di + 2 * g * ns
    proj_out = 2 * di + 2 * g * ns + n_ssm
    s, ly = (n_stack,), ("layers",)
    return {
        "ln": spec(s + (d,), ly + ("embed",), "ones"),
        "in_proj": spec(s + (d, proj_out), ly + ("embed", "ssm_inner")),
        "conv_w": spec(s + (cfg.ssm_conv, conv_ch), ly + ("conv", "ssm_inner"),
                       fan_in_axes=(0,)),
        "conv_b": spec(s + (conv_ch,), ly + ("ssm_inner",), "zeros"),
        "a_log": spec(s + (n_ssm,), ly + ("ssm_heads",), "ssm_a", jnp.float32),
        "d_skip": spec(s + (n_ssm,), ly + ("ssm_heads",), "ones", jnp.float32),
        "dt_bias": spec(s + (n_ssm,), ly + ("ssm_heads",), "dt_bias", jnp.float32),
        "norm": spec(s + (di,), ly + ("ssm_inner",), "ones"),
        "out_proj": spec(s + (di, d), ly + ("ssm_inner", "embed")),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv, width W. x: (B,T,C), w: (W,C)."""
    wdt = w.shape[0]
    pads = [jnp.pad(x, ((0, 0), (wdt - 1 - i, 0), (0, 0)))[:, : x.shape[1]]
            for i in range(wdt)]
    y = sum(p * w[i][None, None, :] for i, p in enumerate(pads))
    return y + b[None, None, :]


def _ssm_pre(h, p, cfg: ArchConfig, conv_state=None, capture_tail=False,
             ctx=None, n_valid=None):
    """in_proj + causal conv + splits. Returns z, x, B, C, dt, new_conv_state
    (decode) or the conv-input tail (prefill with capture_tail).

    ``n_valid`` (scalar, chunked prefill only) marks the valid prefix of a
    right-padded chunk: dt is zeroed past it (a state-neutral no-op for the
    SSD recurrence) and the carried conv tail is taken from the last valid
    inputs instead of the padding.

    The whole pre-pipeline (in_proj output, conv, silu, splits) runs as
    REAL f32 tensors — no narrowing convert between ops — and the conv
    history cache stores f32 (see :func:`ssm_init_cache`), so the values
    crossing the ssm_x/ssm_bc/ssm_dt sharding-constraint boundaries are
    bit-identical in every compilation (eager legacy, jit fused, TP-
    sharded); narrowing here is a fusion-dependent excess-precision trap,
    see layers.swiglu."""
    di, g, ns, nh = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state, cfg.n_ssm_heads
    zxbcdt = L.dense(h, p["in_proj"], out_dtype=jnp.float32)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di: di + di + 2 * g * ns]
    dt = zxbcdt[..., di + di + 2 * g * ns:]
    new_conv_state = None
    if conv_state is not None and xbc.shape[1] == 1:  # decode: T==1
        buf = jnp.concatenate([conv_state, xbc], axis=1)        # (B, W, C)
        w = p["conv_w"]
        y = jnp.einsum("bwc,wc->bc", buf, w)[:, None, :] + p["conv_b"][None, None]
        new_conv_state = buf[:, 1:]
        xbc = y
    elif conv_state is not None:  # chunked prefill continue: T>1 with history
        w1 = conv_state.shape[1]                                # ssm_conv - 1
        buf = jnp.concatenate([conv_state, xbc], axis=1)        # (B, W-1+T, C)
        if n_valid is None:
            new_conv_state = buf[:, -w1:]
        else:   # last W-1 *valid* inputs: rows [n_valid, n_valid + w1)
            new_conv_state = jax.lax.dynamic_slice_in_dim(buf, n_valid, w1, 1)
        xbc = _causal_conv(buf, p["conv_w"], p["conv_b"])[:, w1:]
    else:
        if capture_tail:  # conv state to resume decoding after prefill
            w1 = cfg.ssm_conv - 1
            tail = xbc[:, -w1:]
            pad = w1 - tail.shape[1]
            if pad > 0:
                tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
            new_conv_state = tail
        xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    xbc = L.silu(xbc)
    xs = xbc[..., :di]
    Bs = xbc[..., di: di + g * ns]
    Cs = xbc[..., di + g * ns:]
    b, t = h.shape[0], h.shape[1]
    xs = xs.reshape(b, t, nh, cfg.ssm_headdim)
    Bs = Bs.reshape(b, t, g, ns)
    Cs = Cs.reshape(b, t, g, ns)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    if n_valid is not None:
        # padded positions: dt=0 ⇒ decay exp(0)=1 and update x·dt=0, so the
        # SSD state is untouched past the valid prefix
        dt = jnp.where((jnp.arange(t) < n_valid)[None, :, None], dt, 0.0)
    xs = _constrain(ctx, xs, "ssm_x")
    Bs = _constrain(ctx, Bs, "ssm_bc")
    Cs = _constrain(ctx, Cs, "ssm_bc")
    dt = _constrain(ctx, dt, "ssm_dt")
    return z, xs, Bs, Cs, dt, new_conv_state


def ssm_apply(x, p, cfg: ArchConfig, ctx, *, cache: Optional[Dict] = None,
              ssd_impl: str = "ref", return_state: bool = False,
              n_valid=None) -> Tuple[jax.Array, Any]:
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                # (H,)
    if cache is not None and x.shape[1] == 1:
        z, xs, Bs, Cs, dt, conv_state = _ssm_pre(h, p, cfg, cache["conv"],
                                                 ctx=ctx)
        y, new_state = ssd_decode_step(
            xs[:, 0], Bs[:, 0], Cs[:, 0], dt[:, 0], a, p["d_skip"],
            cache["state"])
        y = y[:, None]
        new_cache = {"conv": conv_state, "state": new_state}
    elif cache is not None:
        # chunked prefill continue: T>1 starting from a carried (conv, ssd)
        # state — conv consumes the W-1 token history, SSD seeds the
        # inter-chunk recurrence with the carried state
        z, xs, Bs, Cs, dt, conv_state = _ssm_pre(h, p, cfg, cache["conv"],
                                                 ctx=ctx, n_valid=n_valid)
        y, final_state = ssd_chunked(xs, Bs, Cs, dt, a, p["d_skip"],
                                     chunk=cfg.ssm_chunk, impl=ssd_impl,
                                     init_state=cache["state"])
        new_cache = {"conv": conv_state, "state": final_state}
    else:
        z, xs, Bs, Cs, dt, conv_tail = _ssm_pre(
            h, p, cfg, capture_tail=return_state, ctx=ctx)
        y, final_state = ssd_chunked(xs, Bs, Cs, dt, a, p["d_skip"],
                                     chunk=cfg.ssm_chunk, impl=ssd_impl)
        new_cache = ({"conv": conv_tail, "state": final_state}
                     if return_state else None)
    b, t = h.shape[0], h.shape[1]
    y = y.reshape(b, t, cfg.d_inner)
    y = L.rmsnorm(y * L.silu(z), p["norm"], cfg.norm_eps)
    y = _constrain(ctx, y, "act_ssm")
    # f32 all the way through out_proj (row-parallel psum under TP), ONE
    # rounding into the residual dtype
    out = L.dense(y, p["out_proj"]).astype(x.dtype)
    return x + _constrain(ctx, out, "hidden"), new_cache


def ssm_apply_spec(x, p, cfg: ArchConfig, ctx, *, cache: Dict,
                   valid) -> Tuple[jax.Array, Dict]:
    """Speculative-verify SSM block: T tokens through the *decode-path*
    math, with every intermediate (conv, state) snapshot emitted.

    Semantically this is T sequential ``ssm_apply`` decode calls (per-token
    conv window einsum + :func:`ssd_decode_scan` recurrence — NOT the
    grouping-sensitive ``ssd_chunked`` form), which is what makes spec-on
    greedy decode token-exact versus spec-off: the verify forward scores a
    proposed window with bit-identical state updates to the fused decode
    step that would otherwise consume it one token at a time. Position-
    independent projections (in_proj, conv einsum inputs, gating, out_proj)
    still run once for the whole window, so weights are read once per layer.

    ``valid`` (B, T) bool masks per-row right-padding (and rows that are
    not speculating at all): invalid positions keep the prior (conv,
    state) and their outputs are garbage. Returns
    ``(x_out, {"conv": (T,B,W-1,C), "state": (T,B,H,P,N)})`` — the cache
    after each token; the caller rolls back to the accepted prefix by
    indexing the leading axis.
    """
    di, g, ns, nh = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state, cfg.n_ssm_heads
    b, t = x.shape[0], x.shape[1]
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    # f32 pre-pipeline, mirroring _ssm_pre bit-for-bit (the verify scan
    # must match sequential decode steps exactly)
    zxbcdt = L.dense(h, p["in_proj"], out_dtype=jnp.float32)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di: di + di + 2 * g * ns]
    dt = zxbcdt[..., di + di + 2 * g * ns:]

    # per-token causal conv through the carried window (decode semantics)
    def conv_step(cs, inp):
        xt, vt = inp                                        # (B, C), (B,)
        buf = jnp.concatenate([cs, xt[:, None]], axis=1)    # (B, W, C)
        y = jnp.einsum("bwc,wc->bc", buf, p["conv_w"]) + p["conv_b"][None]
        ncs = jnp.where(vt[:, None, None], buf[:, 1:], cs)
        return ncs, (y, ncs)

    _, (ys, conv_states) = jax.lax.scan(
        conv_step, cache["conv"], (xbc.transpose(1, 0, 2), valid.T))
    xbc = L.silu(ys.transpose(1, 0, 2))                     # (B, T, C)
    xs = xbc[..., :di].reshape(b, t, nh, cfg.ssm_headdim)
    Bs = xbc[..., di: di + g * ns].reshape(b, t, g, ns)
    Cs = xbc[..., di + g * ns:].reshape(b, t, g, ns)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    y, ssd_states = ssd_decode_scan(xs, Bs, Cs, dt, a, p["d_skip"],
                                    cache["state"], valid=valid)
    y = y.reshape(b, t, di)
    y = L.rmsnorm(y * L.silu(z), p["norm"], cfg.norm_eps)
    out = L.dense(y, p["out_proj"]).astype(x.dtype)
    return x + out, {"conv": conv_states, "state": ssd_states}


def ssm_init_cache(cfg: ArchConfig, batch: int) -> Dict:
    # both leaves are f32: the SSD state always was, and the conv history
    # now stores the f32 pre-pipeline values unrounded — a bf16 conv cache
    # would make a chunk-continued conv differ from the whole-prompt one
    # at chunk boundaries (stored-rounded vs in-flight history) and break
    # the bitwise chunk-carry contract. It is (B, W-1, C): tiny.
    conv_ch = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), jnp.float32),
        "state": jnp.zeros((batch, cfg.n_ssm_heads, cfg.ssm_headdim,
                            cfg.ssm_state), jnp.float32),
    }
