"""A family module that only the tests supply (bench/tests/test_families.py
puts it beside bench/families/dense.py under the name ``tiny_hybrid``):
a hybrid of Mamba-2 (SSD) and grouped-query attention layers, each with a
SwiGLU MLP, in the keys of a Granite 4.0-H ``config.json``
(``layer_types``, ``mamba_*``, ``position_embedding_type``). It shows
that a family is one new file: nothing else of the harness names it.

The reference is a float32 forward that imports nothing of the program.
A Mamba-2 layer: RMSNorm; ``in_proj`` to the gate z, the conv inputs (x,
B, C) and dt; a causal depthwise conv of width ``mamba_d_conv`` with bias,
then SiLU; dt = softplus(dt + dt_bias), A = -exp(a_log); the recurrence
state <- state * exp(dt A) + dt x B^T, y = state C + D x, one step a
token; RMSNorm of y * SiLU(z); ``out_proj``. An attention layer is the
dense family's, with no rotary embedding where the positions are
``nope``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

from bench import flops
from bench.families import dense


def _sizes(conf):
    d = conf["hidden_size"]
    di = conf["mamba_expand"] * d
    nh, p = conf["mamba_n_heads"], conf["mamba_d_head"]
    g, n, w = (conf["mamba_n_groups"], conf["mamba_d_state"],
               conf["mamba_d_conv"])
    if nh * p != di:
        raise ValueError(f"{conf['name']}: {nh} heads of {p} are not {di}")
    return d, di, nh, p, g, n, w, di + 2 * g * n


def _pattern(conf):
    """(period, offset) of the attention layers in ``layer_types``."""
    kinds = conf["layer_types"]
    at = [i for i, k in enumerate(kinds) if k == "attention"]
    period = at[1] - at[0] if len(at) > 1 else len(kinds)
    if not at or any((k == "attention") != (i % period == at[0] % period)
                     for i, k in enumerate(kinds)):
        raise ValueError(f"{conf['name']}: attention layers are not periodic")
    return period, at[0] % period


def arch_config(conf):
    from repro.core.config import ArchConfig

    d, di, nh, p, g, n, w, _ = _sizes(conf)
    period, offset = _pattern(conf)
    return ArchConfig(
        name=conf["name"], family="hybrid",
        n_layers=conf["num_hidden_layers"], d_model=d,
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=dense.head_dim(conf),
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=dense.qkv_bias(conf),
        rope_fraction=0.0 if conf["position_embedding_type"] == "nope"
        else 1.0,
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        ssm_state=n, ssm_expand=conf["mamba_expand"], ssm_headdim=p,
        ssm_conv=w, ssm_chunk=conf["mamba_chunk_size"], ssm_ngroups=g,
        attn_period=period, attn_offset=offset)


def _a_log(key, shape):       # A in [1, 16], as Mamba-2 draws it
    import jax
    import jax.numpy as jnp
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape):     # softplus(dt_bias) in [1e-3, 1e-1]
    import jax
    import jax.numpy as jnp
    u = jax.random.uniform(key, shape, jnp.float32, 1e-3, 1e-1)
    return u + jnp.log(-jnp.expm1(-u))


def layout(conf) -> Dict:
    """One position of the layer pattern a ``pos<i>`` block, stacked over
    the pattern's repeats, as the program's model lays it out."""
    period, offset = _pattern(conf)
    reps = conf["num_hidden_layers"] // period
    d, di, nh, _, g, n, w, ch = _sizes(conf)
    out = dense.layout(dict(conf, num_hidden_layers=reps))
    attn, ffn = out["blocks"]["pos0"]["mix"], out["blocks"]["pos0"]["ffn"]
    ssm = {"ln": ((reps, d), "ones"),
           "in_proj": ((reps, d, 2 * di + 2 * g * n + nh), d),
           "conv_w": ((reps, w, ch), w), "conv_b": ((reps, ch), "bias"),
           "a_log": ((reps, nh), _a_log, "float32"),
           "d_skip": ((reps, nh), "ones", "float32"),
           "dt_bias": ((reps, nh), _dt_bias, "float32"),
           "norm": ((reps, di), "ones"), "out_proj": ((reps, di, d), di)}
    out["blocks"] = {f"pos{i}": {"mix": attn if i == offset else ssm,
                                 "ffn": ffn} for i in range(period)}
    return out


@dataclasses.dataclass(frozen=True)
class Counts(flops.Counts):
    """Beside the weights and attention, each token runs every SSM
    layer's conv and recurrence: ``ssm_flops`` operations."""
    ssm_flops: float = 0.0

    def token_flops(self, context: int) -> float:
        return super().token_flops(context) + self.ssm_flops

    def chunk_flops(self, start: int, n: int, last: bool) -> float:
        return super().chunk_flops(start, n, last) + n * self.ssm_flops


def counts(conf, es) -> Counts:
    d, di, nh, p, g, n, w, ch = _sizes(conf)
    kinds = conf["layer_types"]
    n_attn = kinds.count("attention")
    n_ssm = len(kinds) - n_attn
    one = dense.counts(dict(conf, num_hidden_layers=1), es)
    attn = one.matmul_params - one.head_params \
        - 3 * d * conf["intermediate_size"]
    ssm = d * (2 * di + 2 * g * n + nh) + di * d
    ffn = 3 * d * conf["intermediate_size"]
    return Counts(
        attn_layers=n_attn, attention=one.attention,
        matmul_params=n_attn * attn + n_ssm * ssm + len(kinds) * ffn
        + one.head_params,
        head_params=one.head_params,
        # the SSD state and the conv's last w - 1 inputs, float32
        state_bytes=n_ssm * (nh * p * n + (w - 1) * ch) * 4,
        # conv; state decay, update and read-out
        ssm_flops=n_ssm * (2.0 * w * ch + 5.0 * nh * p * n))


def _ssm(x, q, conf, lowp):
    import jax
    import jax.numpy as jnp

    from bench.reference import HI, mm, rms

    d, di, nh, p, g, n, w, ch = _sizes(conf)
    eps, s = float(conf["rms_norm_eps"]), x.shape[0]
    zxbcdt = mm("sd,de->se", rms(x, q["ln"], eps), q["in_proj"], lowp)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + ch], zxbcdt[:, di + ch:]
    pad = jnp.pad(xbc, ((w - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[i:i + s] * q["conv_w"][i] for i in range(w))
                      + q["conv_b"])
    xs = xbc[:, :di].reshape(s, nh, p)
    bs = jnp.repeat(xbc[:, di:di + g * n].reshape(s, g, n), nh // g, 1)
    cs = jnp.repeat(xbc[:, di + g * n:].reshape(s, g, n), nh // g, 1)
    dt = jax.nn.softplus(dt + q["dt_bias"])
    a = -jnp.exp(q["a_log"])

    def step(state, t):
        xt, bt, ct, dtt = t
        state = state * jnp.exp(dtt * a)[:, None, None] \
            + (xt * dtt[:, None])[:, :, None] * bt[:, None, :]
        y = jnp.einsum("hpn,hn->hp", state, ct, precision=HI) \
            + xt * q["d_skip"][:, None]
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), jnp.float32),
                        (xs, bs, cs, dt))
    y = rms(y.reshape(s, di) * jax.nn.silu(z), q["norm"], eps)
    return x + mm("se,ed->sd", y, q["out_proj"], lowp)


def _attn(x, q, conf, lowp):
    from bench.reference import attention, mm, rms, rope

    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    h = rms(x, q["ln"], eps)
    qh, k, v = (mm("sd,dhk->shk", h, q[n], lowp) for n in ("wq", "wk", "wv"))
    if "bq" in q:
        qh, k, v = qh + q["bq"], k + q["bk"], v + q["bv"]
    if conf["position_embedding_type"] != "nope":
        qh, k = rope(qh, theta), rope(k, theta)
    return x + mm("shk,hkd->sd", attention(qh, k, v), q["wo"], lowp)


def _mlp(x, q, conf, lowp):
    import jax

    from bench.reference import mm, rms

    h = rms(x, q["ln"], float(conf["rms_norm_eps"]))
    g = mm("sd,df->sf", h, q["w_gate"], lowp)
    u = mm("sd,df->sf", h, q["w_up"], lowp)
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, q["w_down"], lowp)


def forward_hidden(conf, w, tokens, lowp):
    """Final normed hidden states (S, d), float32: a scan over the
    pattern's repeats, its positions in order inside."""
    import jax
    import jax.numpy as jnp

    from bench.reference import rms

    period, offset = _pattern(conf)
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    x = w["embed"][tokens].astype(jnp.float32)

    def repeat(x, blocks):
        for i in range(period):
            q = f32(blocks[f"pos{i}"])
            mix = _attn if i == offset else _ssm
            x = _mlp(mix(x, q["mix"], conf, lowp), q["ffn"], conf, lowp)
        return x, None

    x, _ = jax.lax.scan(repeat, x, w["blocks"])
    return rms(x, w["final_ln"].astype(jnp.float32),
               float(conf["rms_norm_eps"]))


def reference(conf, control: bool = False):
    from bench.reference import readings
    return readings(conf, functools.partial(forward_hidden, conf), control)
