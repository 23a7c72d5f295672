"""Launch and warm-up: executables built or loaded inside the window, the
larger of the engine's retrace count (``Engine.trace_counts``) and JAX's
own compile events. Every shape is warmed, so this should read 0."""


def read(ctx):
    run = ctx.run
    return float(max(run.counters1["traces"] - run.counters0["traces"],
                     ctx.compiles))
