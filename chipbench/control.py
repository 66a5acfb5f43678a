"""Readings that set a cell's ``max_logit_gap`` limit, on the chip:

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process: set the cell up, serve it for a short
window at its own load, and compare a seeded sample of what was served
with the plain reference. Two numbers per seed:

* ``program``: the widest gap by which a served token's logit lies below
  the reference's best (the number each run compares; a sound run's reading
  is a lower reading of the limit);
* ``control``: the same reference computed in int8 put in the program's
  place; at each position of the same prompts and tokens, the gap of the
  token the int8 forward puts first (the upper reading).

Not part of a benchmark run. Exits non-zero without a TPU.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, seconds: float, smoke: bool = False,
             quant: str = "int8") -> dict:
    """The program's and the control's reading for one seed."""
    from chipbench import harness

    st = harness.set_up(cell, seed, seconds, smoke)
    harness.measure(st, seconds)
    sample = harness.release(st)
    prog = harness.reference_gaps(cell, seed, sample, smoke)
    ctrl = harness.reference_gaps(cell, seed, sample, smoke, quant=quant)
    return {"seed": seed, "tokens": int(prog.size),
            "program": float(prog.max()), "control": float(ctrl.max()),
            "control_share_over_program": float((ctrl > prog.max()).mean())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--quant", default="int8")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    from chipbench import harness
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    cell = harness.find_cell(harness.load_spec(ROOT), args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        t = time.perf_counter()
        r = readings(cell, seed, args.seconds, quant=args.quant)
        r["seconds"] = time.perf_counter() - t
        print(json.dumps({"workload": args.workload, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
