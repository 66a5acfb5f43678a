"""Engine: the longest engine tick begun in the window, host clock. A tick
admits, prefills, decodes one token for every busy slot and samples; one
that takes many times the rest is a stall every busy stream sees."""


def read(ctx):
    w = ctx.window
    ticks = [t1 - t0 for t0, t1, _ in ctx.ticks if w.t0 <= t0 < w.t_close]
    return max(ticks) * 1e3 if ticks else None
