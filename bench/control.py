"""The control of the comparison that decides ``correct``: the family's
reference computed one precision below the configuration's bf16, float8
e4m3 (every matmul operand rounded, activations per row, weights per
tensor; see bench/reference.py), put in the program's place. At each position of the
same prompts and served tokens it reads the reference gap of the token the
e4m3 forward puts first. The benchmark's own runs do not run it.

    python bench/control.py --workload qwen05b-chat-poisson \
        --seeds 11,12,13 --seconds 30

One process and one engine serve every seed at the cell's own load and
sizes (the weights are swapped for each seed's, the same shapes, so no
step compiles again, and the engine's device state is freed while the
references run and made anew after); each prints one JSON line with the program's widest
gap and the control's over the same sampled requests, and the verdict of
the cell's own comparison (bench/correct.py, with the cell's limits) on
each: the program's must be correct, the control's not.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):    # run as a script: python bench/<name>.py
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import run as R  # noqa: E402  (also puts src/ on the path)
from bench import correct, driver, families, model, traffic  # noqa: E402


def readings(prep, conf, mix, lim, seed: int, seconds: float, *,
             clock=time.monotonic, sleep=time.sleep, spans=True) -> dict:
    """Serve one seed on a prepared engine, read both gaps, and judge each
    by ``lim``, the cell's limits."""
    import jax
    eng = prep.eng
    if seed != prep.seed:
        for a in jax.tree.leaves(prep.params):
            a.delete()
        prep.params = eng.params = model.make_weights(conf, seed,
                                                      prep.shardings)
        prep.seed = seed
    plan = traffic.plan(mix, seed, seconds, prep.cfg.vocab_size)
    run = driver.drive(eng, plan, clock(), float(mix["ramp_s"]), seconds,
                       clock=clock, sleep=sleep, spans=spans)
    for r in eng.live_requests():
        eng.cancel(r.rid)
    # free the program's state before the reference runs, and renew it for
    # the next seed after
    model.free_state(eng)
    picked = correct.sample(run, seed)
    length = correct.padded(traffic.max_context(mix))
    family = families.load(conf)
    prog = correct.compare(family.reference(conf), prep.params, picked, length)
    ctl = family.reference(conf, control=True)
    worst = 0.0
    for tr in picked:
        g = correct.gaps(ctl, prep.params, tr.req.tokens, tr.req.output,
                         length, key="pick")
        worst = max(worst, float(g.max()))
    model.renew_state(eng)
    ctl_read = dict(prog, worst_gap_sd=worst)
    return {"seed": seed, "program_worst_gap_sd": prog["worst_gap_sd"],
            "control_worst_gap_sd": worst, "requests": prog["requests"],
            "served_tokens": prog["served_tokens"],
            "program_correct": correct.verdict(prog, lim)["correct"],
            "control_correct": correct.verdict(ctl_read, lim)["correct"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    bench, wl, conf, mix = R.load_cell(args.workload)
    R.log(f"compile cache: {R.compile_cache()}")
    try:
        devices = R.chips(int(wl["chips"]))
    except R.NoChip as e:
        R.log(f"bench/control.py: {e}")
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    lim = correct.limits(wl["name"])
    prep = R.prepare(conf, mix, seeds[0], devices)
    for seed in seeds:
        print(json.dumps(readings(prep, conf, mix, lim, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
