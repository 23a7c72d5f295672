"""Serving telemetry: the zero-overhead-off contract (identical greedy
tokens and trace_counts with telemetry on vs. off, and with a profiler
session recording or not), the engine's profiler spans (nesting, order
and arguments, read back from a real profile), request-lifecycle span
coverage on a mixed preemption/speculation/prefix-cache trace,
Chrome-trace export validity, the bounded step timeline, chaos-action
mirroring, and the snapshot schema-stability guarantee that CI pins."""
import glob
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import shared_prefix_requests
from repro.models.lm import LM
from repro.serving.engine import Engine, Rejected, Request
from repro.serving.telemetry import (PHASES, SCHEMA_VERSION,
                                     MetricsRegistry, Telemetry)


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen1.5-0.5b", reduced=True)


@pytest.fixture(scope="module")
def params(cfg):
    return LM(cfg).init(jax.random.PRNGKey(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=t).tolist() for t in lens]


def _drain(eng, prompts, max_new=6, max_steps=1500):
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, tokens=list(p), max_new_tokens=max_new))
    done = eng.run(max_steps=max_steps)
    assert len(done) == len(prompts)
    return {r.rid: r.output for r in done}


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_metrics_registry_snapshot():
    reg = MetricsRegistry(hist_cap=8)
    reg.count("a")
    reg.count("a", 4)
    reg.gauge("g", 2.5)
    for v in range(20):
        reg.observe("h", float(v))
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["g"] == 2.5
    h = snap["histograms"]["h"]
    # count/sum track every observation; the percentile reservoir is
    # bounded at hist_cap (newest-kept), so a long run can't grow it
    assert h["count"] == 20 and h["sum"] == sum(range(20))
    assert h["mean"] == pytest.approx(9.5)
    assert 12.0 <= h["p50"] <= 19.0     # reservoir holds the last 8
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


# ---------------------------------------------------------------------------
# The hard contract: telemetry is invisible to the device
# ---------------------------------------------------------------------------


def test_telemetry_on_off_token_and_trace_parity(cfg, params):
    """Enabling telemetry must not change a single traced program or a
    single sampled token: identical greedy outputs, identical retrace
    Counter (same keys AND counts), zero new dispatches."""
    prompts = _prompts(cfg, (12, 7, 9, 16))
    outs, traces = {}, {}
    for on in (False, True):
        eng = Engine(cfg, params, max_batch=2, n_blocks=64, block_size=8,
                     prefill_chunk=5, speculate="ngram", spec_depth=3,
                     telemetry=on)
        outs[on] = _drain(eng, prompts)
        traces[on] = dict(eng.trace_counts)
    assert outs[True] == outs[False]
    assert traces[True] == traces[False]


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; returns its result and the
    ``engine.*`` host spans of the profile as (start_ns, end_ns, name,
    args), read back with ``ProfileData``."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("engine.")]
    return out, spans


def _span_tree(spans):
    """Nest spans by containment: [(name, args, children)] at the top."""
    root = ("", {}, [])
    stack = [(float("inf"), root)]
    for a, b, name, args in sorted(spans, key=lambda s: (s[0], -s[1])):
        while a >= stack[-1][0]:
            stack.pop()
        node = (name, args, [])
        stack[-1][1][2].append(node)
        stack.append((b, node))
    return root[2]


STAGES = ["engine.inputs", "engine.launch", "engine.wait", "engine.finish"]


@pytest.mark.parametrize("on", [False, True])
def test_engine_step_spans_nest_in_order(cfg, params, tmp_path, on):
    """Each engine step is one ``engine.step`` span holding its phases in
    execution order; the chunk and the decode forward sit in the two
    dispatches, each with its four stages in order, and carry their
    arguments. Enabled or not, the spans are the same."""
    prompts = _prompts(cfg, (12, 7))
    eng = Engine(cfg, params, max_batch=2, n_blocks=32, block_size=8,
                 prefill_chunk=5, telemetry=on)
    _, spans = _profiled(tmp_path, lambda: _drain(eng, prompts, max_new=4))
    steps = _span_tree(spans)
    assert [s[0] for s in steps] == ["engine.step"] * len(steps)
    assert len(steps) >= 6
    chunks, both = [], 0
    for _, args, kids in steps:
        assert "step" in args
        names = [k[0] for k in kids]
        assert names == ["engine.sweep", "engine.schedule",
                         "engine.dispatch", "engine.schedule",
                         "engine.dispatch"]
        forwards = [f for k in kids if k[0] == "engine.dispatch"
                    for f in k[2]]
        for name, fargs, stages in forwards:
            assert name in ("engine.chunk", "engine.decode")
            assert [s[0] for s in stages] == STAGES
            assert all(s[2] == [] for s in stages)
            if name == "engine.chunk":
                chunks.append((fargs["rid"], fargs["start"], fargs["n"]))
            else:
                assert 1 <= fargs["rows"] <= 2 and fargs["kv_tokens"] > 0
        kinds = [f[0] for f in forwards]
        assert kinds in ([], ["engine.chunk"], ["engine.decode"],
                         ["engine.chunk", "engine.decode"])
        both += kinds == ["engine.chunk", "engine.decode"]
    assert both > 0
    # the chunks of each request tile its prompt from 0, in order
    for rid, p in enumerate(prompts):
        mine = [(s, n) for r, s, n in chunks if r == rid]
        assert [s for s, _ in mine] == list(range(0, len(p), 5))
        assert sum(n for _, n in mine) == len(p)
    if on:
        totals = eng.telemetry.timeline_summary()["phase_totals_s"]
        for name in ("step", "chunk", "decode", "inputs", "launch", "wait",
                     "finish"):
            assert totals[name] > 0.0


def test_profiler_session_token_and_trace_parity(cfg, params, tmp_path):
    """A recording profiler session changes no traced program and no
    token: the spans add no dispatch and no traced argument."""
    prompts = _prompts(cfg, (12, 7, 9))
    outs, traces = {}, {}
    for traced in (False, True):
        eng = Engine(cfg, params, max_batch=2, n_blocks=64, block_size=8,
                     prefill_chunk=5)
        if traced:
            outs[traced], _ = _profiled(tmp_path, lambda: _drain(eng, prompts))
        else:
            outs[traced] = _drain(eng, prompts)
        traces[traced] = dict(eng.trace_counts)
    assert outs[True] == outs[False]
    assert traces[True] == traces[False]


def test_disabled_phase_is_shared_null_context(cfg, params):
    """Disabled telemetry keeps nothing in memory: a phase is its bare
    profiler span (inert unless a profiler session records), with no
    clock read, no step record and no running total."""
    tel = Telemetry(enabled=False)
    for name in ("schedule", "dispatch"):
        assert type(tel.phase(name)) is jax.profiler.TraceAnnotation
    eng = Engine(cfg, params, max_batch=2, n_blocks=16, block_size=8)
    _drain(eng, _prompts(cfg, (8,)), max_new=3)
    # disabled telemetry collected nothing at all
    tel = eng.telemetry
    assert not tel.enabled
    assert tel.events == [] and len(tel.timeline) == 0
    assert tel._phase_totals == {}
    assert tel.timeline_summary()["phase_totals_s"] == \
        dict.fromkeys(PHASES, 0.0)
    assert tel.registry.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# Mixed-trace lifecycle coverage + Chrome export
# ---------------------------------------------------------------------------


def test_mixed_trace_covers_every_request_lifecycle(cfg, params, tmp_path):
    """The acceptance trace: an undersized pool (preemption), ngram
    speculation (verify rounds) and a shared prefix (cache hits) on one
    engine. Every request's track runs submit -> terminal, preemption
    episodes appear as spans, and the export is valid Chrome-trace JSON."""
    prompts = shared_prefix_requests(6, cfg.vocab_size, prefix_len=24,
                                     suffix_len=8, seed=7)
    eng = Engine(cfg, params, max_batch=4, n_blocks=14, block_size=8,
                 prefill_chunk=8, speculate="ngram", spec_depth=3,
                 prefix_cache=True, telemetry=True)
    _drain(eng, prompts, max_new=8)
    tel = eng.telemetry
    counters = tel.registry.snapshot()["counters"]
    assert counters["requests_submitted"] == 6
    assert counters["terminal_finished"] == 6
    assert counters.get("preemptions", 0) > 0       # pool pressure fired
    assert counters.get("prefix_hits", 0) > 0       # radix trie shared
    assert counters.get("spec_proposed", 0) > 0     # verify rounds ran

    # request tracks are asserted on the exported trace — per-chunk and
    # per-step events are synthesized at export time, not stored as dicts
    out = tmp_path / "trace.json"
    trace = tel.export_chrome(str(out), metadata={"chaos_seed": None})
    loaded = json.loads(out.read_text())

    by_rid = {}
    for ev in loaded["traceEvents"]:
        if ev.get("pid") == 1 and ev["ph"] != "M":
            by_rid.setdefault(ev["tid"], []).append(ev)
    for rid in range(6):
        names = [e["name"] for e in by_rid[rid]]
        assert "submit" in names and "terminal" in names
        assert "queued" in names and "prefill" in names
        term = [e for e in by_rid[rid] if e["name"] == "terminal"][0]
        assert term["args"]["state"] == "finished"
        assert term["args"]["path"] == "finished"
    # a preemption victim owns a 'preempted' span and a re-admission
    preempted = [rid for rid, evs in by_rid.items()
                 if any(e["name"] == "preempted" for e in evs)]
    assert preempted
    assert any(e["name"] == "prefix_hit"
               for evs in by_rid.values() for e in evs)
    assert any(e["name"] == "prefill_chunk"
               for evs in by_rid.values() for e in evs)

    # the engine track recorded every step with its phase split
    summary = tel.timeline_summary()
    assert summary["recorded"] == eng.steps
    assert summary["dropped"] == 0
    assert set(summary["step_kinds"]) <= {"decode", "chunk", "verify",
                                          "prefill"}
    assert summary["phase_totals_s"]["schedule"] > 0.0
    assert summary["phase_totals_s"]["dispatch"] > 0.0

    assert loaded == json.loads(json.dumps(trace))   # tuples -> lists
    assert loaded["displayTimeUnit"] == "ms"
    assert loaded["otherData"]["schema_version"] == SCHEMA_VERSION
    assert loaded["otherData"]["events_dropped"] == 0
    phases = {e["ph"] for e in loaded["traceEvents"]}
    assert {"X", "i", "C", "M"} <= phases
    # every event is structurally a Chrome trace event
    for ev in loaded["traceEvents"]:
        assert "ph" in ev and "pid" in ev and "name" in ev
        if ev["ph"] in ("X", "i", "C"):
            assert ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0


def test_rejection_traced_as_terminal_instant(cfg, params):
    eng = Engine(cfg, params, max_batch=2, n_blocks=64, block_size=8,
                 queue_cap=1, telemetry=True)
    prompts = _prompts(cfg, (8, 8, 8, 8))
    shed = 0
    for rid, p in enumerate(prompts):
        try:
            eng.submit(Request(rid=rid, tokens=list(p), max_new_tokens=2))
        except Rejected:
            shed += 1
    assert shed > 0
    counters = eng.telemetry.registry.snapshot()["counters"]
    assert counters["terminal_rejected"] == shed
    trace = eng.telemetry.export_chrome()
    rejects = [e for e in trace["traceEvents"] if e["name"] == "rejected"]
    assert len(rejects) == shed
    assert all(e["args"]["reason"] == "queue_full" for e in rejects)
    eng.run(max_steps=400)


# ---------------------------------------------------------------------------
# Bounded collection
# ---------------------------------------------------------------------------


def test_step_timeline_ring_is_bounded(cfg, params):
    tel = Telemetry(timeline_cap=4)
    eng = Engine(cfg, params, max_batch=2, n_blocks=32, block_size=8,
                 telemetry=tel)
    _drain(eng, _prompts(cfg, (8, 8)), max_new=8)
    assert eng.steps > 4
    assert len(tel.timeline) == 4
    s = tel.timeline_summary()
    assert s["recorded"] == 4
    assert s["dropped"] == eng.steps - 4
    # the ring keeps the NEWEST steps
    assert [r["step"] for r in tel.timeline] == \
        list(range(eng.steps - 4, eng.steps))


def test_event_cap_drops_and_counts(monkeypatch):
    import repro.serving.telemetry as T
    monkeypatch.setattr(T, "_EVENTS_CAP", 3)
    tel = Telemetry()
    for i in range(5):
        tel._instant(0, f"e{i}")
    assert tel.events_dropped == 2
    trace = tel.export_chrome()
    assert trace["otherData"]["events_dropped"] == 2
    assert sum(1 for e in trace["traceEvents"] if e["ph"] == "i") == 3


# ---------------------------------------------------------------------------
# Chaos actions ride the same timeline
# ---------------------------------------------------------------------------


def test_chaos_actions_recorded_even_when_disabled():
    tel = Telemetry(enabled=False)
    tel.chaos_action(3, "squeeze", 2)
    tel.chaos_action(5, "cancel", 1)
    # the replay log exists regardless; trace events only when enabled
    assert tel.chaos_actions == [(3, "squeeze", 2), (5, "cancel", 1)]
    assert tel.events == []
    assert tel.registry.snapshot()["counters"] == {}


def test_chaos_run_lands_on_trace_timeline(cfg, params):
    from repro.serving.faults import FaultInjector, StepFaults
    faults = FaultInjector({1: StepFaults(squeeze_blocks=2),
                            3: StepFaults(release_squeezed=True,
                                          cancel_rids=(1,))})
    eng = Engine(cfg, params, max_batch=2, n_blocks=16, block_size=8,
                 faults=faults, telemetry=True)
    _drain(eng, _prompts(cfg, (8, 8)), max_new=8, max_steps=400)
    tel = eng.telemetry
    # injector log and telemetry mirror are the same stream
    assert tel.chaos_actions == faults.log
    chaos_evs = [e for e in tel.events if e.get("cat") == "chaos"]
    assert [e["name"] for e in chaos_evs] == [a for _, a, _ in faults.log]
    assert all(e["pid"] == 0 and e["tid"] == 1 for e in chaos_evs)
    counters = tel.registry.snapshot()["counters"]
    assert counters["chaos_squeeze"] == 1
    # the cancelled request still reached a traced terminal
    assert counters["terminal_cancelled"] == 1


# ---------------------------------------------------------------------------
# Snapshot schema stability + stats() compatibility view
# ---------------------------------------------------------------------------

# The documented schema (docs/observability.md). The stability contract
# is SUPERSET: future PRs may add keys freely, but renaming or removing
# any key below requires a SCHEMA_VERSION bump and a docs update. CI's
# fast lane runs this test by name.
DOCUMENTED_SCHEMA = {
    "engine": {"steps", "mode", "prefill_chunk", "model_parallel"},
    "requests": {"completed", "finished", "timed_out", "cancelled",
                 "failed", "rejected", "rejected_reasons"},
    "latency": {"e2e", "ttft", "tpot", "queue"},
    "throughput": {"tok_s", "decode_tok_s", "decode_tokens",
                   "prefill_tokens", "decode_time_s", "prefill_time_s"},
    "pool": {"utilization", "owned", "cached_reclaimable", "free"},
    "prefix_cache": {"hit_rate", "cached_blocks", "tokens_reused",
                     "cow_copies"},
    "scheduler": {"preemptions", "queue_depth"},
    "telemetry": {"enabled", "fenced", "events", "events_dropped",
                  "chaos_actions"},
    "timeline": {"recorded", "dropped", "phase_totals_s", "step_kinds"},
}


def test_snapshot_schema_is_superset_of_documented(cfg, params):
    eng = Engine(cfg, params, max_batch=2, n_blocks=32, block_size=8,
                 prefill_chunk=8, telemetry=True)
    _drain(eng, _prompts(cfg, (8, 12)), max_new=4)
    snap = eng.snapshot()
    assert snap["schema_version"] == SCHEMA_VERSION
    for section, keys in DOCUMENTED_SCHEMA.items():
        assert section in snap, f"missing section {section!r}"
        missing = keys - set(snap[section])
        assert not missing, f"{section}: missing keys {sorted(missing)}"
    for extra in ("counters", "gauges", "histograms", "spec"):
        assert extra in snap
    # latency leaves are stable too
    assert {"mean", "p50", "p99"} <= set(snap["latency"]["e2e"])
    assert {"mean", "p50", "p95", "p99"} <= set(snap["latency"]["ttft"])
    json.dumps(snap)                    # machine-readable end to end


def test_stats_is_thin_view_over_snapshot(cfg, params):
    """Every legacy flat stats() field is a rename of a snapshot_base
    leaf — one source of truth, two shapes."""
    eng = Engine(cfg, params, max_batch=2, n_blocks=32, block_size=8,
                 prefill_chunk=8, prefix_cache=True, telemetry=True)
    _drain(eng, shared_prefix_requests(4, cfg.vocab_size, prefix_len=16,
                                       suffix_len=8, seed=3), max_new=4)
    st, s = eng.stats(), eng.snapshot_base()
    assert st["requests"] == s["requests"]["completed"]
    assert st["finished"] == s["requests"]["finished"]
    assert st["rejected"] == s["requests"]["rejected"]
    assert st["throughput_tok_s"] == s["throughput"]["tok_s"]
    assert st["decode_tok_s"] == s["throughput"]["decode_tok_s"]
    assert st["p50_ttft_s"] == s["latency"]["ttft"]["p50"]
    assert st["p99_tpot_s"] == s["latency"]["tpot"]["p99"]
    assert st["mean_queue_s"] == s["latency"]["queue"]["mean"]
    assert st["kv_utilization"] == s["pool"]["utilization"]
    assert st["kv_blocks_free"] == s["pool"]["free"]
    assert st["prefix_cache_hit_rate"] == s["prefix_cache"]["hit_rate"]
    assert st["cached_tokens_reused"] == s["prefix_cache"]["tokens_reused"]
    assert st["preemptions"] == s["scheduler"]["preemptions"]
    assert st["model_parallel"] == s["engine"]["model_parallel"]


def test_reset_stats_clears_telemetry(cfg, params):
    eng = Engine(cfg, params, max_batch=2, n_blocks=32, block_size=8,
                 telemetry=True)
    _drain(eng, _prompts(cfg, (8,)), max_new=3)
    assert eng.telemetry.snapshot()["telemetry"]["events"] > 0
    eng.reset_stats()
    tel = eng.telemetry
    assert tel.snapshot()["telemetry"]["events"] == 0
    assert len(tel.timeline) == 0
    assert tel.registry.snapshot()["counters"] == {}
    assert tel.chaos_actions == []
    # still live after reset: a second run records again
    _drain(eng, _prompts(cfg, (8,), seed=1), max_new=3)
    assert tel.snapshot()["telemetry"]["events"] > 0
