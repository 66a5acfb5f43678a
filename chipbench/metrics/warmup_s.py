"""Launcher: host seconds of the warm-up (the cell's prefill buckets and
the decode step, once each, ending in ``block_until_ready``)."""


def read(ctx):
    return ctx.setup.get("warmup_s")
