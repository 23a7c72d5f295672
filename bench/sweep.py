"""Find the knee of an open-loop cell: the highest arrival rate with no
backlog growing through the window. One process, one engine; each rate
runs its own ramp and window, then every request still in flight is
cancelled before the next.

    python bench/sweep.py --workload qwen05b-chat-poisson --seed 5 \
        --rates 1.0,1.2,1.4,1.6,1.8,2.0 --seconds 30

Prints one JSON line per rate: output tokens/s, TTFT p50 and p95, the
backlog (requests due but not admitted) at the window's start and end, and
the finished share of the requests due in the window. The rate a cell runs
at is fixed in its traffic file from such a sweep; the benchmark never
searches for it.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

if __package__ in (None, ""):    # run as a script: python bench/<name>.py
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import run as R  # noqa: E402  (also puts src/ on the path)
from bench import driver, traffic, window  # noqa: E402


def backlog(tracked, t: float) -> int:
    return sum(1 for tr in tracked if tr.due <= t and (
        tr.req.admitted_time is None or tr.req.admitted_time > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    bench, wl, conf, mix = R.load_cell(args.workload)
    R.log(f"compile cache: {R.compile_cache()}")
    try:
        devices = R.chips(int(wl["chips"]))
    except R.NoChip as e:
        R.log(f"bench/sweep.py: {e}")
        return 2
    prep = R.prepare(conf, mix, args.seed, devices)
    eng = prep.eng
    for rate in [float(r) for r in args.rates.split(",")]:
        m = copy.deepcopy(mix)
        m["arrival"]["rate_rps"] = rate
        plan = traffic.plan(m, args.seed, args.seconds, prep.cfg.vocab_size)
        run = driver.drive(eng, plan, time.monotonic(), float(m["ramp_s"]),
                           args.seconds)
        due = [tr for tr in run.tracked if run.t0 <= tr.due < run.t1]
        tt = window.ttfts(run.tracked, run.t0, run.t1)
        e2e = window.end_to_end(run.tracked, run.t0, run.t1)
        print(json.dumps({
            "rate_rps": rate, **e2e,
            "ttft_p50_ms": 1e3 * float(np.median(tt)) if tt else None,
            "backlog_start": backlog(run.tracked, run.t0),
            "backlog_end": backlog(run.tracked, run.t1),
            "finished_share": sum(tr.state == "finished" for tr in due)
            / max(len(due), 1),
            "steps": run.counters1["steps"] - run.counters0["steps"],
            "preemptions": run.counters1["preemptions"]
            - run.counters0["preemptions"]}), flush=True)
        for r in eng.live_requests():
            eng.cancel(r.rid)
    return 0


if __name__ == "__main__":
    sys.exit(main())
