"""Knee sweep of an open-loop cell, on the chip, in one process with one
set-up:

    python3 chipbench/sweep.py --workload olmo_1b.chat --rates 1.6,2.0,2.4 --seconds 60

For each offered rate (requests per second) the cell's traffic runs its
lead-in and then a window of ``--seconds``, and the engine is drained
before the next rate. Printed per rate: requests due and completed in the
window, the requests waiting for a slot (mean over the first and the last
quarter of the window, and at its close), busy slots, tokens/s against the
tokens/s offered, the latency tails, and what the host did besides
serving (traces, compiles, collections, preemptions, the longest ticks).
A rate keeps pace where the queue
for a slot does not grow over the window: its mean over the last quarter
exceeds that over the first by less than one request. The knee is the
highest rate that keeps pace; the cell's traffic file then fixes 0.8 x
knee as a number. Not part of a benchmark run.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sweep_rate(st, rate: float, seconds: float) -> dict:
    """One rate on an engine that is already set up and idle."""
    import jax
    from chipbench import e2e, harness, loadgen

    params = json.loads(json.dumps(st.cell.traffic))
    params["arrival"]["rate"] = rate
    traffic = loadgen.Traffic(params, st.seed, st.eng.cfg.vocab, *st.traffic.caps)
    drv = harness.Driver(st.eng)
    samples = []
    tick = drv.tick

    def sampled_tick():
        done = tick()
        samples.append((drv.clock(), len(st.eng.queue), int(st.eng.active.sum())))
        return done
    drv.tick = sampled_tick
    n_before = harness.engine_counts(st.eng)
    watch = harness.Watch()
    jax.monitoring.register_event_duration_secs_listener(watch.jax_event)
    gc.callbacks.append(watch.gc_event)
    watch.on = True
    try:
        sender = harness.lead_in(drv, traffic, seconds)
        win = harness.run_open(sender, seconds)
    finally:
        watch.on = False
        gc.callbacks.remove(watch.gc_event)
    queued_at_close = next((q for t, q, _ in samples if t >= win.t_close), 0)
    while drv.busy():
        drv.tick()

    def mean_of(col, lo, hi):
        v = [s[col] for s in samples if win.t0 + lo <= s[0] < win.t0 + hi]
        return sum(v) / len(v) if v else 0.0
    q = seconds / 4
    tl = list(drv.timeline.values())
    due = [r for r in tl if win.t0 <= r.due < win.t_close]
    completed = [r for r in tl if r.stamps and len(r.stamps) >= r.max_new
                 and win.t0 < r.stamps[r.max_new - 1] <= win.t1]
    first, last = mean_of(1, 0, q), mean_of(1, seconds - q, seconds)
    return {"rate": rate, "due": len(due), "completed": len(completed),
            "queued_first_quarter": first, "queued_last_quarter": last,
            "queued_at_close": queued_at_close,
            "busy_slots": mean_of(2, 0, seconds),
            "offered_tokens_per_s": sum(r.max_new for r in due) / seconds,
            "keeps_pace": last - first < 1.0,
            "host": str(watch),
            **{k: v - n_before[k] for k, v in harness.engine_counts(st.eng).items()},
            "longest_ticks_s": sorted((t1 - t0 for t0, t1, _ in drv.ticks),
                                      reverse=True)[:3],
            **e2e.metrics(tl, win.t0, win.t1, win.t_close)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    from chipbench import harness
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    cell = harness.find_cell(harness.load_spec(ROOT), args.workload)
    t = time.perf_counter()
    # set-up without the lead-in: each rate runs its own
    st = harness.set_up(dataclasses.replace(
        cell, traffic=dict(cell.traffic, lead_in_s=0.0)), args.seed, 0.0)
    st.cell = cell
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    for rate in (float(x) for x in args.rates.split(",")):
        print(json.dumps(sweep_rate(st, rate, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
