"""bench/trace_reduce.py on a small trace in the profiler's own format:
two TPU ops that overlap, the paged kernel, and host spans."""
import pytest

from bench import trace_reduce

# times in ns from 1000: device busy [1000, 4000) and [6000, 7000),
# idle [4000, 6000) under bench.record and [7000, 9000) under bench.wait
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
  event_metadata { key: 2 value { id: 2 name: "_paged_kernel" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3500000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6500000 duration_ps: 1500000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "bench.record" } }
  event_metadata { key: 3 value { id: 3 name: "bench.step" } }
  event_metadata { key: 4 value { id: 4 name: "bench.wait" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction" } } }
"""


@pytest.fixture
def trace_file(tmp_path):
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    return tmp_path


def test_busy_union_kernel_time_and_named_gaps(trace_file):
    r = trace_reduce.read(trace_reduce.find(str(trace_file)), "paged_kernel")
    assert r["window_s"] == pytest.approx(8000e-9)      # 1000 .. 9000
    assert r["busy_s"] == pytest.approx(4000e-9)        # overlap counted once
    assert r["kernel_s"] == pytest.approx(3000e-9)
    assert r["kernel_calls"] == 2
    assert r["ops"]["fusion.7"] == pytest.approx(2000e-9)
    assert r["gaps"] == [("bench.record", pytest.approx(2000e-9)),
                         ("bench.wait", pytest.approx(2000e-9))] or \
        r["gaps"] == [("bench.wait", pytest.approx(2000e-9)),
                      ("bench.record", pytest.approx(2000e-9))]
    assert r["idle_by_span"] == {"bench.record": pytest.approx(2000e-9),
                                 "bench.wait": pytest.approx(2000e-9)}


def test_union_and_gaps():
    merged = trace_reduce.union([(5, 7), (0, 2), (1, 3), (6, 6.5)])
    assert merged == [(0, 3), (5, 7)]
    assert trace_reduce.gaps_between(merged, 0, 10) == [(3, 5), (7, 10)]


def test_no_trace_file(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find(str(tmp_path))


@pytest.mark.parametrize("seed", range(4))
def test_merge_matches_union(seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    a = rng.uniform(0, 100, 300).round(1)
    b = a + rng.uniform(0, 3, 300).round(1)
    s, e = trace_reduce.merge(a, b)
    assert list(zip(s.tolist(), e.tolist())) == \
        trace_reduce.union(zip(a.tolist(), b.tolist()))


@pytest.mark.parametrize("overlapping", [False, True])
def test_name_gaps_matches_name_gap(overlapping):
    import numpy as np
    rng = np.random.default_rng(int(overlapping))
    t, spans = 5.0, []
    while t < 95:
        d = float(rng.uniform(0.5, 4))
        spans.append((t, t + d + (2.0 if overlapping else 0.0),
                      f"bench.{rng.integers(3)}"))
        t += d + float(rng.choice([0.0, 0.0, 0.7]))
    spans.sort()
    ga = np.sort(rng.uniform(0, 100, 400))
    gb = ga + rng.uniform(0.01, 6, 400)
    starts = [s[0] for s in spans]
    assert trace_reduce.name_gaps(ga, gb, spans) == \
        [trace_reduce.name_gap(g, spans, starts) for g in zip(ga, gb)]


# the same device ops named as a TPU trace names them, by their HLO text:
# the paged read's two Pallas kernels, and optionally a third kernel of
# another name (a state-space scan) in the idle stretch [7000, 7500)
HLO_OP = '%{0} = f32[8,4,64]{{2,1,0}} custom-call(f32[8,4,64]{{2,1,0}} %p), ' \
    'custom_call_target=\\"tpu_custom_call\\"'
TRACE_HLO = """
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }}
    events {{ metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }}
    events {{ metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }}
    {extra} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.7 = bf16[8,64]{{1,0}} fusion(bf16[8,64]{{1,0}} %x), kind=kLoop" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{decode}" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "{prefix}" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "{scan}" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 8000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.step" }} }} }}
"""
SCAN = "events { metadata_id: 4 offset_ps: 6000000 duration_ps: 500000 }"
# what the reader before kernels were told apart by name read on TRACE_HLO
# with no third kernel: kernel_s and kernel_calls, the paged read's time
PARENT = {"kernel_s": 3.0000000000000005e-06, "kernel_calls": 2}


def hlo_trace(tmp_path, scan: bool):
    from jax.profiler import ProfileData
    text = TRACE_HLO.format(extra=SCAN if scan else "",
                            decode=HLO_OP.format("paged_decode.5"),
                            prefix=HLO_OP.format("paged_prefix.12"),
                            scan=HLO_OP.format("ssd_scan.3"))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.read(trace_reduce.find(str(tmp_path)))


@pytest.mark.parametrize("scan", [False, True])
def test_each_kernel_by_its_own_name(tmp_path, scan):
    from bench.layer_metrics import paged_attn_roofline as paged
    r = hlo_trace(tmp_path, scan)
    k = r["kernels"]
    assert k["paged_decode"] == {"s": pytest.approx(2000e-9), "calls": 1}
    assert k["paged_prefix"] == {"s": pytest.approx(1000e-9), "calls": 1}
    # the paged read's seconds and calls are what the reader before
    # kernel names read, whatever else runs
    assert paged.kernel_seconds(r) == PARENT["kernel_s"]
    assert sum(k[n]["calls"] for n in paged.KERNELS) == PARENT["kernel_calls"]
    if scan:    # the other kernel has its own time, and counts in the total
        assert k["ssd_scan"] == {"s": pytest.approx(500e-9), "calls": 1}
        assert r["kernel_s"] == pytest.approx(3500e-9)
        assert r["kernel_calls"] == 3
    else:
        assert set(k) == set(paged.KERNELS)
        assert r["kernel_s"] == PARENT["kernel_s"]
        assert r["kernel_calls"] == PARENT["kernel_calls"]


def test_kernel_name_of_an_op():
    assert trace_reduce.kernel_name(HLO_OP.format("paged_decode.5")) == \
        "paged_decode"
    assert trace_reduce.kernel_name("%flash_decode = f32[1] custom-call()") \
        == "flash_decode"
    assert trace_reduce.kernel_name("_paged_kernel") == "_paged_kernel"
