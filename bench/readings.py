"""Readings that the correctness limits of a cell are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... [--control-seeds 1,2,3] [--fault <name>]

For each seed, in one process: the cell's data and program from that
seed, one unit of its traffic (no window), and the numbers that decide
``correct``, judged against the cell's limits. The seed also sets the
unit's fit key, in place of the configuration's panel of ``fit_seeds``
(the driver's ``fit_seeds`` override, which only this script sets), so
that the limits can be read over as many keys as seeds are given, where
a run fits the panel's few. For each control seed,
the same numbers for the control: the plain reference put in the
program's place one precision down (the driver's ``control``), which
has to fail a limit. ``--fault`` plants one of ``faults.py``'s faults
under the program first, so the program's readings are the fault's.
Prints one JSON line per reading and, last, each number's least and
largest reading of each kind (the program or its fault, the control). Needs the chip, as a run
does; the benchmark's own runs never run the control or a fault.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("readings need the TPU")
        return 2
    harness.use_compile_cache()
    if args.fault:
        from bench import faults
        faults.plant(args.fault)
    driver = harness.driver_of(cell)
    seen: dict[str, dict[str, list[float]]] = {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        session = driver.setup(cell, seed, warm=False, fit_seeds=[seed])
        out = session.unit(0)
        ctl = session.control() if seed in args.control_seeds else None
        session.release()
        rows = [(args.fault or "program", session.compare([out]))]
        if ctl is not None:
            rows.append(("control", session.compare([ctl])))
        for kind, numbers in rows:
            checks = harness.judge(numbers, cell.limits)
            ok = all(c["ok"] for c in checks.values())
            print(json.dumps({"seed": seed, "kind": kind, "ok": ok,
                              "numbers": numbers,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
            for k, v in numbers.items():
                seen.setdefault(kind, {}).setdefault(k, []).append(v)
        del session, out, ctl, rows
        gc.collect()
    print(json.dumps({"summary": {
        kind: {k: [min(v), max(v)] for k, v in numbers.items()}
        for kind, numbers in seen.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
