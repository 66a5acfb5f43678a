"""Engine: mean number of busy slots over the window's decode steps (the
engine tracer's ``decode`` events)."""


def read(ctx):
    w = ctx.window
    act = [ev["active"] for ev in ctx.engine_events
           if ev["kind"] == "decode" and w.t0 <= ev["wall_ms"] * 1e-3 <= w.t1]
    return sum(act) / len(act) if act else None
