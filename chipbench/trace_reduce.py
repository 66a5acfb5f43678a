"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the traced window, device busy time, per-op and per-program
device time, and the device's idle gaps named by what the host was doing.

The window is the host span that the harness opens around its measured
loop (``WINDOW``). Busy time is the union of the intervals in which an
operation ran on a device ("XLA Ops" line), clipped to the window and
averaged over the devices used. An idle gap is a stretch of the window with
no operation on the device; it is named by the innermost host span on the
window's thread that covers its midpoint (the harness's own spans, such as
``bench.tick``, or JAX's, such as the dispatch of a jitted program): the
shortest, and of equal ones the first by name. The gaps are named in one
sweep in time order, so the cost grows with the trace's length and not
with its square.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@dataclasses.dataclass
class Reduced:
    """What one trace says about its window (seconds)."""

    window_s: float
    busy_s: float                       # mean over the devices used
    devices: int
    op_s: dict[str, float]              # op name -> device seconds (summed)
    module_s: dict[str, list[float]]    # program name -> each run's seconds
    idle_gaps: dict[str, float]         # host span name -> idle seconds

    def breakdown(self, top: int = 10) -> dict:
        """The contract's ``breakdown``: the ops that took most device time
        and the idle time by host span, each at most ``top`` entries."""
        def head(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": head(self.op_s), "idle_gaps": head(self.idle_gaps)}


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def op_name(text: str) -> str:
    """An op's name from its HLO text in the trace ("%fusion.3 = ..." ->
    "fusion.3")."""
    return text.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(profile, devices: int = 1, window: str = WINDOW) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` over the ``window`` span, for
    the first ``devices`` TPU devices."""
    host_line = None
    win = None
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == window:
                    host_line, win = line, (e.start_ns, e.start_ns + e.duration_ns)
    if win is None:
        raise ValueError(f"no {window!r} span in the trace")
    lo, hi = win
    dev_planes = sorted((p for p in profile.planes
                         if p.name.startswith(DEVICE_PREFIX)
                         and p.name[len(DEVICE_PREFIX):].isdigit()),
                        key=lambda p: int(p.name[len(DEVICE_PREFIX):]))[:devices]
    if not dev_planes:
        raise ValueError("no TPU device plane in the trace")
    op_s: dict[str, float] = {}
    module_s: dict[str, list[float]] = {}
    busy = []
    first_busy = None
    for plane in dev_planes:
        intervals = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for text, s, e in _events(line):
                    c = _clip([(s, e)], lo, hi)
                    if c:
                        intervals.append(c[0])
                        name = op_name(text)
                        op_s[name] = op_s.get(name, 0.0) + (c[0][1] - c[0][0]) * 1e-9
            elif line.name == MODULES_LINE:
                for name, s, e in _events(line):
                    if lo <= s and e <= hi:
                        module_s.setdefault(name, []).append((e - s) * 1e-9)
        merged = _union(intervals)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if first_busy is None:
            first_busy = merged
    n = len(dev_planes)
    op_s = {k: v / n for k, v in op_s.items()}
    host = sorted((s, e, name) for name, s, e in _events(host_line)
                  if name != window and e > lo and s < hi)
    idle: dict[str, float] = {}
    opened: list[tuple[int, str, int]] = []     # (duration, name, end), a heap
    i = 0
    prev = lo
    for s, e in first_busy + [[hi, hi]]:
        if s > prev:
            mid = (prev + s) / 2                # gaps, so midpoints, ascend
            while i < len(host) and host[i][0] <= mid:
                s2, e2, name = host[i]
                heapq.heappush(opened, (e2 - s2, name, e2))
                i += 1
            while opened and opened[0][2] < mid:   # ended before this gap
                heapq.heappop(opened)
            label = opened[0][1] if opened else "no host span"
            idle[label] = idle.get(label, 0.0) + (s - prev) * 1e-9
        prev = max(prev, e)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / n, devices=n,
                   op_s=op_s, module_s=module_s, idle_gaps=idle)


def start(trace_dir: str) -> None:
    """Start the profiler: device activity and host spans, without the
    Python function tracer (it would slow the host loop being measured)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def reduce_dir(trace_dir: str, devices: int = 1) -> Reduced:
    """:func:`reduce` of the trace the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(trace_dir)), devices)
