"""A whole run on the CPU, past the harness's look for a chip, with the
timed path broken underneath: ``correct`` must come out false. Of the
faults a run can plant (a step that returns its state unchanged, half the
batch left out, the exchange between chips left out, a token altered where
it is produced), a one-chip serving cell can have only the last."""
import time

from bench import correct
from bench.run import run_cell
from bench.tests.small import cell, cpu_only, fixed_pool

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def serve(seed, monkeypatch):
    fixed_pool(monkeypatch)
    bench, wl, conf, mix = cell()
    res = run_cell(bench, wl, conf, mix, seed=seed, seconds=2.0, trace=False,
                    devices=cpu_only(), peaks=PEAKS,
                    limits=correct.limits(wl["name"]),
                    t_start=time.monotonic(), out=lambda *a: None)
    want = {m["name"] for m in bench["end_to_end"]
            if wl["name"] in m.get("workloads", [wl["name"]])}
    return res, want


def test_sound_run_is_correct(monkeypatch):
    res, want = serve(2**31 + 101, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == want and "setup_s" in want
    assert list(res)[-1] == "checks"


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving.engine import Engine
    finish = Engine._finish_step

    def altered(self, live, next_tokens, row_ok=None):
        bad = next_tokens.copy()
        for r in live:                  # every 4th token of every request
            if len(r.output) % 4 == 3:
                bad[r.slot] = (bad[r.slot] + 128) % 256
        return finish(self, live, bad, row_ok=row_ok)

    monkeypatch.setattr(Engine, "_finish_step", altered)
    res, _ = serve(2**31 + 101, monkeypatch)
    assert not res["correct"], res["checks"]
    c = res["checks"]["worst_gap_sd"]
    assert c["value"] > c["limit"]
