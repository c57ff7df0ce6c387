"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and, as its last lines on standard error, each compared
number beside its limit; the last line of standard output is the result
object. A run that finds no TPU, or fewer chips than the cell asks for,
exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()           # set-up is timed from here

import argparse                         # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import sys                              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    from bench import harness
    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        harness.log(f"{args.workload} needs {cell.chips} TPU chip(s); JAX "
                    f"found {len(devices)} {devices[0].platform} device(s)")
        return 2
    devices = devices[:cell.chips]
    harness.log(f"device {devices[0].device_kind} x{len(devices)}; "
                f"compilation cache {harness.use_compile_cache()}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              devices=devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
