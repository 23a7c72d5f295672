"""A cell cut down for the CPU: the qwen configuration's file at d_model
64, two layers and a 256-token vocabulary, chat lengths cut to a few dozen
tokens, a 2-second window. The CPU reports no device memory to fit a pool
to, so :func:`fixed_pool` stands in for ``bench.model.fit_pool``."""
import copy
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
WORKLOAD = "qwen05b-chat-poisson"


def cell():
    from bench.run import load_cell
    bench, wl, conf, mix = load_cell(WORKLOAD)
    conf = copy.deepcopy(conf)
    conf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
    conf["engine"].update(max_batch=4, prefill_chunk=16)
    mix = {"arrival": {"kind": "poisson", "rate_rps": 20.0},
           "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                          "min": 4, "max": 64},
           "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 2, "max": 24},
           "ramp_s": 1}
    return bench, wl, conf, mix


def cpu_only():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    return jax.devices()[:1]


def fixed_pool(monkeypatch, blocks=64):
    from bench import model
    monkeypatch.setattr(model, "fit_pool",
                        lambda *a, **k: (blocks, {"blocks": blocks}))
