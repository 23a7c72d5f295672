"""What every family's plain reference shares (bench/families/): float32
arithmetic written from the published architectures, importing nothing of
the program.

Every matmul is float32 at ``Precision.HIGHEST``; attention and the head
run in blocks of :data:`ROWS` rows, so a long sequence fits beside the
weights. Weights sharded over several chips stay where they are: the
forward runs on those chips. The control computes one precision below the
configurations' bf16: every matmul operand rounded to float8 e4m3
(:func:`fp8`, activations per row, weights per tensor).

:func:`readings` turns a family's forward (to the final normed hidden
states) into the jitted function of (weights, tokens) that the comparison
(bench/correct.py) and the control (bench/control.py) read.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
ROWS = 512          # query rows per attention block and head block
E4M3_MAX = 448.0


def fp8(x, axis=None):
    """Round to float8 e4m3 with a scale that maps the largest magnitude
    (per tensor, or per slice along ``axis``) onto e4m3's largest value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def ident(x, axis=None):
    return x


def mm(eq, a, w, lowp):
    """A matmul; under the control both operands are rounded to e4m3
    (activations per row, weights per tensor)."""
    return jnp.einsum(eq, lowp(a, axis=-1), lowp(w), precision=HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotary embedding (rotate-half, all of head_dim) of x (S, H, hd)."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal grouped-query attention of q (S, H, hd) over k, v (S, K, hd)
    at 1/sqrt(hd), in blocks of query rows."""
    s, h, hd = q.shape
    kh = k.shape[1]
    qg = q.reshape(s // ROWS, ROWS, kh, h // kh, hd)
    pos = jnp.arange(s)

    def block(args):
        i, qb = args
        sc = jnp.einsum("rkgd,tkd->kgrt", qb, k, precision=HI) / math.sqrt(hd)
        rows = i * ROWS + jnp.arange(ROWS)
        sc = jnp.where(pos[None, None, None, :] <= rows[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgrt,tkd->rkgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(s // ROWS), qg))
    return out.reshape(s, h, hd)


def readings(conf, hidden, control: bool = False):
    """Jitted ``f(weights, tokens)`` -> dict of per-position float32
    vectors: ``max`` and ``sd`` of the reference logits, ``next`` the
    reference logit of ``tokens[i + 1]``; with ``control`` also ``pick``,
    the reference logit of the token the e4m3 forward ranks first.

    ``hidden(w, tokens, lowp)`` is the family's forward to the final normed
    hidden states (S, d), float32, every matmul through :func:`mm` with
    ``lowp``. The head is the untied output projection where the weights
    have one (``head``, (d, vocab)), else the tied embedding, over the real
    vocabulary. ``tokens`` is (S,) with S a multiple of :data:`ROWS`."""
    vocab = int(conf["vocab_size"])

    def f(w, tokens):
        if "head" in w:
            out_w, eq = w["head"][:, :vocab].astype(jnp.float32), "rd,dv->rv"
        else:
            out_w, eq = w["embed"][:vocab].astype(jnp.float32), "rd,vd->rv"
        h = hidden(w, tokens, ident)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        hl = hidden(w, tokens, fp8) if control else h
        s = tokens.shape[0]

        def block(args):
            hb, hlb, nb = args
            lg = jnp.einsum(eq, hb, out_w, precision=HI)
            out = {"max": lg.max(-1), "sd": lg.std(-1),
                   "next": jnp.take_along_axis(lg, nb[:, None], 1)[:, 0]}
            if control:
                lo = mm(eq, hlb, out_w, fp8)
                pick = jnp.argmax(lo, -1)
                out["pick"] = jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
            return out

        def blocks(a):
            return a.reshape((s // ROWS, ROWS) + a.shape[1:])

        out = jax.lax.map(block, (blocks(h), blocks(hl), blocks(nxt)))
        return {k: v.reshape(s) for k, v in out.items()}

    return jax.jit(f)
