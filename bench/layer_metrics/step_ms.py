"""Engine host loop: window seconds over the engine steps taken in it."""


def read(ctx):
    run = ctx.run
    n = run.counters1["steps"] - run.counters0["steps"]
    return 1e3 * (run.t1 - run.t0) / n if n else None
