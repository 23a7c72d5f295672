"""bench/phase_reduce.py on a small trace in the profiler's own format:
the engine's spans inside the load generator's, and two executables."""
import pytest

from bench import phase_reduce, trace_reduce
from bench.tests.test_trace_reduce import trace_file  # noqa: F401

# The engine's spans inside the generator's (times in ns from 1000, one
# device): ops busy [1000, 2000), [5000, 6000) (the kernel), [8000, 9000);
# host: bench.step [1000, 7000) > engine.step [1200, 6800) >
# engine.schedule [1200, 1500), engine.decode [2500, 6500) > inputs
# [2500, 3500), launch [3500, 4000), wait [4000, 6000), finish
# [6000, 6500); then bench.record [7000, 7500), bench.wait [7500, 8000)
# and a bench.step [8000, 9500) with no engine span in it. Executables:
# the chunk step over the first op, the decode step over the other two.
ENGINE_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
  event_metadata { key: 2 value { id: 2 name: "_paged_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "jit__chunk_step_impl(11)" } }
  event_metadata { key: 4 value { id: 4 name: "jit__fused_step_impl(22)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 200000 duration_ps: 5600000 }
    events { metadata_id: 3 offset_ps: 200000 duration_ps: 300000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 4000000 }
    events { metadata_id: 5 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 2500000 duration_ps: 500000 }
    events { metadata_id: 7 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 8 offset_ps: 5000000 duration_ps: 500000 }
    events { metadata_id: 9 offset_ps: 6000000 duration_ps: 500000 }
    events { metadata_id: 10 offset_ps: 6500000 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "engine.step" } }
  event_metadata { key: 3 value { id: 3 name: "engine.schedule" } }
  event_metadata { key: 4 value { id: 4 name: "engine.decode" } }
  event_metadata { key: 5 value { id: 5 name: "engine.inputs" } }
  event_metadata { key: 6 value { id: 6 name: "engine.launch" } }
  event_metadata { key: 7 value { id: 7 name: "engine.wait" } }
  event_metadata { key: 8 value { id: 8 name: "engine.finish" } }
  event_metadata { key: 9 value { id: 9 name: "bench.record" } }
  event_metadata { key: 10 value { id: 10 name: "bench.wait" } } }
"""


@pytest.fixture
def engine_trace(tmp_path):
    from jax.profiler import ProfileData
    d = tmp_path / "engine" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        ENGINE_TRACE))
    return str(path)


def test_idle_split_by_innermost_engine_span(engine_trace):
    r = phase_reduce.read(engine_trace)
    assert r["window_s"] == pytest.approx(8500e-9)      # 1000 .. 9500
    assert r["busy_s"] == pytest.approx(3000e-9)
    # idle [2000, 5000), [6000, 8000), [9000, 9500), cut at every span
    # boundary and charged to the innermost engine span open over it, a
    # stage named with its forward
    assert r["idle_by_phase"] == {
        "engine.step": pytest.approx(800e-9),       # 2000-2500, 6500-6800
        "engine.decode.inputs": pytest.approx(1000e-9),
        "engine.decode.launch": pytest.approx(500e-9),
        "engine.decode.wait": pytest.approx(1000e-9),   # 4000-5000
        "engine.decode.finish": pytest.approx(500e-9),
        # bench.step outside engine.step, bench.record, the last bench.step
        "harness": pytest.approx(1200e-9),
        "bench.wait": pytest.approx(500e-9)}
    assert sum(r["idle_by_phase"].values()) == \
        pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    # the executables cover [1000, 2000), [5000, 6000), [8000, 9000):
    # all busy, so no idle lies inside one
    assert r["idle_in_modules"] == {}
    assert r["engine_steps"] == 1
    assert r["module_s"] == {"jit__chunk_step_impl": pytest.approx(1000e-9),
                             "jit__fused_step_impl": pytest.approx(2000e-9)}


def test_each_gap_splits_into_pieces_that_sum_to_it():
    engine = [(0, 100, "engine.step"), (10, 60, "engine.decode"),
              (10, 30, "engine.inputs"), (40, 60, "engine.wait")]
    waits = [(150, 200, "bench.wait")]
    segments = phase_reduce.label_segments(engine, waits)
    starts = [s[0] for s in segments]
    cases = {(0, 250): {"engine.step": 50, "engine.decode": 10,
                        "engine.decode.inputs": 20,
                        "engine.decode.wait": 20,
                        "harness": 100, "bench.wait": 50},
             (-20, 5): {"harness": 20, "engine.step": 5},
             (45, 55): {"engine.decode.wait": 10},
             (120, 140): {"harness": 20},
             (300, 310): {"harness": 10}}
    for gap, want in cases.items():
        got = {}
        for label, ns in phase_reduce.split_gap(gap, segments, starts):
            got[label] = got.get(label, 0) + ns
        assert got == want
        assert sum(got.values()) == gap[1] - gap[0]


def test_longest_step_holds_its_phases(engine_trace):
    step = phase_reduce.read(engine_trace)["longest_step"]
    assert step["name"] == "engine.step"
    assert step["s"] == pytest.approx(5600e-9)
    assert [c["name"] for c in step["children"]] == \
        ["engine.schedule", "engine.decode"]
    decode = step["children"][1]
    assert [(c["name"], c["s"]) for c in decode["children"]] == [
        ("engine.inputs", pytest.approx(1000e-9)),
        ("engine.launch", pytest.approx(500e-9)),
        ("engine.wait", pytest.approx(2000e-9)),
        ("engine.finish", pytest.approx(500e-9))]


def test_window_and_busy_are_the_trace_reductions(engine_trace,
                                                  trace_file):  # noqa: F811
    """The split covers bench/trace_reduce.py's idle time exactly, on a
    trace with the engine's spans and on one without them, where it is
    all the generator's; and the engine's spans leave trace_reduce's
    reading as it was: gaps named by ``bench.*`` spans alone."""
    for path in (engine_trace, trace_reduce.find(str(trace_file))):
        r = phase_reduce.read(path)
        t = trace_reduce.read(path, "paged_kernel")
        assert r["window_s"] == t["window_s"]
        assert r["busy_s"] == pytest.approx(t["busy_s"], rel=1e-12)
        assert sum(r["idle_by_phase"].values()) == pytest.approx(
            t["window_s"] - t["busy_s"], rel=1e-9)
    bare = phase_reduce.read(trace_reduce.find(str(trace_file)))
    # idle [4000, 6000) under bench.record, [7000, 7500) under bench.step,
    # [7500, 9000) under bench.wait
    assert bare["idle_by_phase"] == {"harness": pytest.approx(2500e-9),
                                     "bench.wait": pytest.approx(1500e-9)}
    assert bare["engine_steps"] == 0 and bare["longest_step"] is None
    t = trace_reduce.read(engine_trace, "paged_kernel")
    # each gap overlaps a bench.step the most, engine spans or not
    assert t["idle_by_span"] == {"bench.step": pytest.approx(5500e-9)}


def test_idle_inside_an_executable_is_split_out():
    """Idle between two operations of one program is the program's own,
    whatever the host did meanwhile; idle between programs is not."""
    device = {"/device:TPU:0": [("a", 0, 10, {}), ("b", 30, 40, {}),
                                ("c", 60, 70, {})]}
    engine = [(0, 70, "engine.step"), (5, 50, "engine.decode"),
              (5, 50, "engine.wait")]
    modules = {"/device:TPU:0": [("jit__fused_step_impl(1)", 0, 40)]}
    busy = {dev: [(a, b) for _, a, b, _ in ops] for dev, ops in device.items()}
    r = phase_reduce.reduce_phases(busy, [], (0, 70), engine, modules)
    assert r["idle_by_phase"] == {
        "engine.decode.wait": pytest.approx(30e-9),
        "engine.step": pytest.approx(10e-9)}
    assert r["idle_in_modules"] == {
        "engine.decode.wait": pytest.approx(20e-9)}
    assert r["module_s"] == {"jit__fused_step_impl": pytest.approx(40e-9)}


def test_report_names_each_phase_per_step(engine_trace, capsys):
    assert phase_reduce.main([str(engine_trace).rsplit("/plugins", 1)[0]]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("idle 0.005") and "over 1 engine steps" in out[0]
    assert "  engine.decode.inputs 0.001 (inside an executable 0.000)" in out
    assert "  jit__fused_step_impl 0.002" in out
    assert out[-1].endswith("step 0.006 (schedule 0.000, decode 0.004 ("
                            "inputs 0.001, launch 0.001, wait 0.002, "
                            "finish 0.001))")
