"""Model step: the hybrid decode program's share of the HBM roofline, in
percent. Each ``decode`` event in the window that carries ``state_bytes``
(an engine with recurrent layers) gives the step's least bytes by
``decode_bytes`` of the configuration's reference module: the weights once
in the configuration's parameter dtype, the active slots' recurrent state
read and written, and the KV of the live pages it read (``kv_pages`` pages
of ``page_size`` tokens). Their mean, over the program's mean device time
(``decode_step_ms``), over the chip's HBM bandwidth."""
import jax.numpy as jnp

from chipbench.metrics import decode_step_ms


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    w = ctx.window
    steps = [ev for ev in ctx.engine_events
             if ev["kind"] == "decode" and "state_bytes" in ev
             and w.t0 <= ev["wall_ms"] * 1e-3 <= w.t1]
    ms = decode_step_ms.read(ctx)
    if not steps or not ms:
        return None
    c = ctx.cell.config
    entry = jnp.dtype(c["param_dtype"]).itemsize
    ps = c["engine"]["page_size"]
    total = sum(ctx.ref.decode_bytes(ctx.arch, weights=entry,
                                     state_bytes=ev["state_bytes"],
                                     kv_tokens=ev["kv_pages"] * ps)
                for ev in steps)
    return total / len(steps) / (ms * 1e-3) / ctx.peak["hbm_bytes_per_s"] * 100.0
