"""The window arithmetic of bench/window.py, through the real driver on a
fake engine and clock."""
import json
from pathlib import Path

import numpy as np

from bench import driver, traffic, window
from bench.tests.fakes import Clock, FakeEngine

MIX = {"arrival": {"kind": "poisson", "rate_rps": 120.0},
       "prompt_len": {"dist": "fixed", "value": 8, "min": 1, "max": 8},
       "output_len": {"dist": "fixed", "value": 6, "min": 1, "max": 6},
       "ramp_s": 1.0}


def serve(stall):
    clock = Clock()
    eng = FakeEngine(clock, max_batch=8, dt=0.01, stall=stall)
    plan = traffic.plan(MIX, seed=3, seconds=4.0, vocab=100)
    run = driver.drive(eng, plan, 0.0, 1.0, 4.0, clock=clock,
                       sleep=clock.sleep, spans=False)
    return run, window.end_to_end(run.tracked, run.t0, run.t1)


def test_stall_mid_window_moves_every_metric():
    base_run, base = serve(lambda i: 0.0)
    # near capacity; from 2.5 s to 3.5 s every step takes 30 ms longer
    stalled_run, stalled = serve(lambda t: 0.03 if 2.5 <= t < 3.5 else 0.0)
    assert stalled["ttft_p95_ms"] > 2 * base["ttft_p95_ms"]
    assert stalled["itl_p95_ms"] > 2 * base["itl_p95_ms"]
    assert stalled["output_tok_s"] < 0.9 * base["output_tok_s"]


def test_unserved_request_counts_at_its_age():
    run, _ = serve(lambda i: 0.0)
    tr = run.tracked[-1]
    tr.emits = []
    ages = window.ttfts([tr], run.t0, run.t1)
    assert np.isclose(ages[0], run.t1 - tr.due)


def test_gaps_are_per_token_not_per_request_means():
    class T:
        due, finish = 0.0, 5.0
        emits = [(1.0, 1), (1.1, 1), (3.1, 2), (3.2, 1)]
    g = window.gaps([T()], 0.0, 4.0)
    # 0.1, the 2.0 s stall, the two tokens stamped together (2.0 and 0),
    # 0.1, and the open gap of 0.8 s at the close
    assert np.allclose(sorted(g), sorted([0.1, 2.0, 0.0, 0.1, 0.8]))
    assert window.tokens_in([T()], 0.0, 4.0) == 5


def test_every_window_holds_the_same_requests_on_every_seed():
    mix = json.loads((Path(__file__).parents[1] / "traffic" /
                      "chat_poisson.json").read_text())
    a = traffic.plan(mix, 2**31 + 5, 30, 1000)
    b = traffic.plan(mix, 9, 30, 1000)
    assert sorted(a.prompt_lens) == sorted(b.prompt_lens)
    assert sorted(a.output_lens) == sorted(b.output_lens)
    assert list(a.prompt_lens) != list(b.prompt_lens)
    for p in (a, b):
        block = p.arrivals.block
        assert block == round(mix["arrival"]["rate_rps"] * 30)
        # a window one period long, wherever it starts, holds one copy of
        # every request of the block, and each copy keeps its sizes
        for t0 in (mix["ramp_s"], 7.3):
            idx = [i for i, t in enumerate(p.arrivals.due) if t0 <= t < t0 + 30]
            assert sorted(i % block for i in idx) == list(range(block))
        assert p.output_len(3) == p.output_len(3 + block)
        assert np.allclose(p.arrivals.due[block:2 * block],
                           p.arrivals.due[:block] + 30)
    # the seeds start one cycle at different places: the same requests, each
    # with its own gap and sizes, in the same cyclic order
    n = a.arrivals.block
    ga, gb = (np.diff(p.arrivals.due[:n + 1]) for p in (a, b))
    r = next(r for r in range(n) if np.allclose(np.roll(ga, r), gb))
    assert r != 0
    assert list(np.roll(a.prompt_lens, r)) == list(b.prompt_lens)
    assert list(np.roll(a.output_lens, r)) == list(b.output_lens)
    again = traffic.plan(mix, 2**31 + 5, 30, 1000)
    assert a.prompt(3) == again.prompt(3)


def test_window_below_capacity_emits_one_period_of_tokens():
    mix = dict(MIX, arrival={"kind": "poisson", "rate_rps": 20.0},
               output_len={"dist": "lognormal", "median": 6, "sigma": 0.8,
                           "min": 2, "max": 24})
    for seed in (1, 2**31 + 77):
        clock = Clock()
        eng = FakeEngine(clock, max_batch=8, dt=0.01)
        plan = traffic.plan(mix, seed=seed, seconds=4.0, vocab=100)
        run = driver.drive(eng, plan, 0.0, 1.0, 4.0, clock=clock,
                           sleep=clock.sleep, spans=False)
        want = int(plan.output_lens.sum())
        got = window.tokens_in(run.tracked, run.t0, run.t1)
        assert abs(got - want) <= 0.02 * want, (seed, got, want)


def test_closed_loop_keeps_each_client_busy():
    mix = dict(MIX, arrival={"kind": "closed", "clients": 3, "think_s": 0.0})
    clock = Clock()
    eng = FakeEngine(clock, max_batch=8, dt=0.01)
    plan = traffic.plan(mix, seed=4, seconds=2.0, vocab=100)
    run = driver.drive(eng, plan, 0.0, 0.5, 2.0, clock=clock,
                       sleep=clock.sleep, spans=False)
    # never more than one request of a client in flight, and each next one
    # falls due when the last one finished
    for c in range(3):
        mine = [tr for tr in run.tracked if tr.client == c]
        assert len(mine) > 10
        for prev, nxt in zip(mine, mine[1:]):
            assert nxt.due == prev.finish


def test_bursty_gaps_keep_their_mean_and_spread():
    mix = dict(MIX, arrival={"kind": "gamma_bursty", "rate_rps": 10.0,
                             "cv": 3.0})
    gaps = np.diff(traffic.plan(mix, seed=5, seconds=60.0, vocab=100)
                   .arrivals.due)
    assert np.isclose(gaps.mean(), 0.1, rtol=0.05)
    assert gaps.std() / gaps.mean() > 2.0
