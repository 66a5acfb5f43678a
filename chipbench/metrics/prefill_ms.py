"""Model step (prefill): median milliseconds of the engine tracer's
``prefill`` spans begun in the window. A span runs from the prefill call
through the splice into the page pool to the first token on the host, so
it is what one admission costs every busy stream. A ``prefill`` span
without ``prompt_len`` times only the enqueue (a program whose span ends
before the first token's sync) and is not read."""
from chipbench.e2e import percentile


def read(ctx):
    w = ctx.window
    durs = [ev["dur_ms"] for ev in ctx.engine_events
            if ev["kind"] == "prefill" and "prompt_len" in ev
            and w.t0 <= (ev["wall_ms"] - ev["dur_ms"]) * 1e-3 < w.t_close]
    return percentile(durs, 50) if durs else None
