"""Run one cell of BENCHMARK.json once, on the chip:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, calibration, warm-up of the cell's shapes)
runs from process start to the opening of the window; then the window runs
for ``--seconds``; then the plain reference checks a seeded sample of what
was served. The last line of standard output is the result object; the log
and the numbers compared, each beside its limit, go to standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result. It never falls back to the CPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no program (src/repro) under {ROOT}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness

    cell = harness.find_cell(harness.load_spec(ROOT), args.workload)
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chipbench: no accelerator: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return EXIT_NO_CHIP
    from repro.utils import enable_compile_cache

    harness.say(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
                f"{enable_compile_cache()}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
