"""Engine: mean milliseconds per engine tick begun in the window that the
host spends on its own work, while the device waits: the tracer's ``tick``
span less its ``step`` span (decode, sampling and their sync) and less the
``prefill`` spans inside it. Nested spans are emitted as they close, so a
tick's own spans are those of its tick number emitted since the tick
before it."""


def read(ctx):
    w = ctx.window
    host, inner = [], {}
    for ev in ctx.engine_events:
        if ev["kind"] in ("step", "prefill"):
            inner[ev["tick"]] = inner.get(ev["tick"], 0.0) + ev["dur_ms"]
        elif ev["kind"] == "tick":
            if w.t0 <= (ev["wall_ms"] - ev["dur_ms"]) * 1e-3 < w.t_close:
                host.append(ev["dur_ms"] - inner.get(ev["tick"], 0.0))
            inner = {}
    return sum(host) / len(host) if host else None
