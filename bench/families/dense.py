"""Dense decoders: every layer is pre-norm grouped-query attention and a
SwiGLU MLP (Qwen, Granite, Llama).

The reference is a float32 forward written from the published
architecture and the configuration file, importing nothing of the
program: RMSNorm, q/k/v projections (with biases where the config has
them), rotary embedding (rotate-half, all of head_dim), causal
grouped-query attention at 1/sqrt(head_dim), output projection; RMSNorm,
SwiGLU MLP; final RMSNorm and the head (bench/reference.py). The bf16
weights are widened one layer at a time.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

from bench import flops
from bench.model import padded_vocab

# keys of a source config that the serving stack does not model: the run
# may only hold them at the value that changes nothing
NEUTRAL = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
           "logits_scaling": 1.0}


def head_dim(conf) -> int:
    return int(conf.get("head_dim")
               or conf["hidden_size"] // conf["num_attention_heads"])


def qkv_bias(conf) -> bool:
    return bool(conf.get("attention_bias", conf["model_type"] == "qwen2"))


def arch_config(conf):
    """The program's ``ArchConfig`` for a dense decoder configuration."""
    from repro.core.config import ArchConfig

    for key, neutral in NEUTRAL.items():
        if float(conf.get(key, neutral)) != neutral:
            raise ValueError(f"{conf['name']}: {key}={conf[key]} is not "
                             f"modelled by the serving stack")
    hd = head_dim(conf)
    if "attention_multiplier" in conf and not math.isclose(
            conf["attention_multiplier"], hd ** -0.5):
        raise ValueError(f"{conf['name']}: attention_multiplier "
                         f"{conf['attention_multiplier']} is not 1/sqrt(hd)")
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=hd,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=qkv_bias(conf), rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]))


def layout(conf) -> Dict:
    """One stacked attention block and MLP for all layers, the embedding,
    the final norm, and ``head`` where the configuration is untied."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    h, k, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                head_dim(conf))
    f = conf["intermediate_size"]
    mix = {"ln": ((L, d), "ones"),
           "wq": ((L, d, h, hd), d), "wk": ((L, d, k, hd), d),
           "wv": ((L, d, k, hd), d), "wo": ((L, h, hd, d), h * hd)}
    if qkv_bias(conf):
        mix.update(bq=((L, h, hd), "bias"), bk=((L, k, hd), "bias"),
                   bv=((L, k, hd), "bias"))
    ffn = {"ln": ((L, d), "ones"), "w_gate": ((L, d, f), d),
           "w_up": ((L, d, f), d), "w_down": ((L, f, d), f)}
    out = {"embed": ((padded_vocab(conf["vocab_size"]), d), "embed"),
           "final_ln": ((d,), "ones"),
           "blocks": {"pos0": {"mix": mix, "ffn": ffn}}}
    if not conf["tie_word_embeddings"]:
        out["head"] = ((d, padded_vocab(conf["vocab_size"])), "head")
    return out


def counts(conf, es) -> flops.Counts:
    """Every layer attends through the pool; no recurrent state."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    h, k, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                head_dim(conf))
    attn = d * (h + 2 * k) * hd + h * hd * d
    ffn = 3 * d * conf["intermediate_size"]
    head = d * conf["vocab_size"]
    return flops.Counts(
        attn_layers=L,
        attention=flops.Attention(
            heads=h, kv_heads=k, head_dim=hd, block=es["block_size"],
            kv_bytes=1 if es["kv_dtype"] == "int8" else 2),
        matmul_params=L * (attn + ffn) + head, head_params=head)


def forward_hidden(conf, w, tokens, lowp):
    """Final normed hidden states (S, d), float32."""
    import jax
    import jax.numpy as jnp

    from bench.reference import attention, mm, rms, rope

    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        a, m = f32(lw["mix"]), f32(lw["ffn"])
        h = rms(x, a["ln"], eps)
        q = mm("sd,dhk->shk", h, a["wq"], lowp)
        k = mm("sd,dhk->shk", h, a["wk"], lowp)
        v = mm("sd,dhk->shk", h, a["wv"], lowp)
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        o = attention(rope(q, theta), rope(k, theta), v)
        x = x + mm("shk,hkd->sd", o, a["wo"], lowp)
        h = rms(x, m["ln"], eps)
        g = mm("sd,df->sf", h, m["w_gate"], lowp)
        u = mm("sd,df->sf", h, m["w_up"], lowp)
        x = x + mm("sf,fd->sd", jax.nn.silu(g) * u, m["w_down"], lowp)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["pos0"])
    return rms(x, w["final_ln"].astype(jnp.float32), eps)


def reference(conf, control: bool = False):
    from bench.reference import readings
    return readings(conf, functools.partial(forward_hidden, conf), control)
