"""The TP collectives' share of busy device time, and per-chip op times,
on a small two-device trace in the profiler's own format."""
import pytest

from bench import trace_reduce
from bench.layer_metrics import load

# times in ns from 1000. TPU:0: fusion.1 [1000, 3000), all-reduce.5
# [3000, 4000), fusion.1 [4000, 5000); TPU:1: fusion.1 [1000, 2000),
# all-gather-start.2 [2000, 2500) and all-gather-done.2 [2500, 3000)
# beside fusion.1 [2000, 5000). A window of [1000, 5000) on each.
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.5" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "all-gather-start.2" } }
  event_metadata { key: 4 value { id: 4 name: "all-gather-done.2" } } }
"""

NO_COLLECTIVE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "all-reduce-like-fusion.3" } } }
"""


def write(tmp_path, text):
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.find(str(tmp_path))


class Ctx:
    def __init__(self, trace):
        self.trace = trace


def test_collective_share_is_the_mean_over_chips(tmp_path):
    r = trace_reduce.read(write(tmp_path, TRACE), "")
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(4000e-9)        # both always busy
    # TPU:0 1000 ns of all-reduce, TPU:1 1000 ns of all-gather halves
    assert r["collective_s"] == pytest.approx(1000e-9)
    # op seconds are a chip's: the sum over devices over their count
    assert r["ops"]["fusion.1"] == pytest.approx((3000e-9 + 4000e-9) / 2)
    assert r["ops"]["all-reduce.5"] == pytest.approx(500e-9)
    assert r["ops"]["all-gather-start.2"] == pytest.approx(250e-9)
    share = load("collective_share").read(Ctx(r))
    assert share == pytest.approx(25.0)


def test_no_collective_reads_zero(tmp_path):
    r = trace_reduce.read(write(tmp_path, NO_COLLECTIVE), "")
    assert r["collective_s"] == 0.0
    assert load("collective_share").read(Ctx(r)) == 0.0
    assert load("collective_share").read(Ctx(None)) is None


def test_collective_names():
    match = trace_reduce.COLLECTIVE.match
    for name in ("all-reduce.5", "all-reduce", "all-reduce-start.1",
                 "all-reduce-done", "all-gather.3", "reduce-scatter.2",
                 "collective-permute-start.4", "all-to-all.1",
                 # as a TPU trace names them: the instruction's HLO text
                 "%all-reduce.5 = bf16[32,5120]{1,0:T(8,128)(2,1)} "
                 "all-reduce(%fusion.181), channel_id=1",
                 "%all-gather-start.2 = (s32[1,32]) all-gather-start(%b)"):
        assert match(name), name
    for name in ("fusion.7", "all-reduce-like-fusion.3", "reduce.4",
                 "copy-start.2",
                 # an op that reads a collective's result is not one
                 "%fusion.182 = (f32[32]{0}, bf16[32,1,5120]) "
                 "fusion(%all-reduce.5, %get-tuple-element.1191)"):
        assert not match(name), name
