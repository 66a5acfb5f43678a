"""Engine: median wait from when a request was due to its admission into a
slot, over the requests due in the window (the engine tracer's ``admit``
events, host clock)."""
from chipbench.e2e import percentile


def read(ctx):
    admitted = {}
    for ev in ctx.engine_events:
        if ev["kind"] == "admit" and ev["rid"] not in admitted:
            admitted[ev["rid"]] = ev["wall_ms"] * 1e-3
    w = ctx.window
    waits = [admitted[r.rid] - r.due for r in ctx.timeline
             if w.t0 <= r.due < w.t_close and r.rid in admitted]
    return percentile(waits, 50) * 1e3 if waits else None
