"""Model step: mean device milliseconds per run of the jitted paged decode
program, from the profiler trace.

The engine jits ``partial(model.decode_step_paged, cfg)``, which reaches the
trace unnamed (``jit__unknown(<id>)``), as do its prefill programs. The
decode program is the one of them that runs once per engine tick with a
busy slot: the unnamed program whose run count in the window is nearest to
the number of the window's decode events.
"""

UNNAMED = "jit__unknown"


def read(ctx):
    if ctx.trace is None:
        return None
    w = ctx.window
    steps = sum(1 for ev in ctx.engine_events
                if ev["kind"] == "decode" and w.t0 <= ev["wall_ms"] * 1e-3 <= w.t1)
    runs = [r for name, r in ctx.trace.module_s.items() if name.startswith(UNNAMED)]
    if not steps or not runs:
        return None
    best = min(runs, key=lambda r: (abs(len(r) - steps), -sum(r)))
    return sum(best) / len(best) * 1e3
