"""Shared helpers of the benchmark's CPU tests: the repository root on
the import path, and the benchmark's cells shrunk to a size the CPU runs
in seconds (same drivers, same limits, same comparison)."""
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# per cell: the data and fit shrunk for the CPU. As at full size, as many
# centers as mixture components, whose power-law weights leave a Forgy
# start crowded in the big components and bare in the small ones; the
# components lie farther apart than at full size, so that at 64 of them
# a bare component costs as much as one of 4096 does there
TINY = {
    "fit_sift1m_k4096": dict(data=dict(n=4096, d=32, true_k=64, spread=8.0),
                             fit=dict(k=64, kn=16, max_iters=5)),
}


def tiny_cell(name: str):
    from bench import harness
    cell = harness.load_cell(name)
    cfg = json.loads(json.dumps(cell.config))
    cfg["data"].update(TINY[name]["data"])
    cfg["fit"].update(TINY[name]["fit"])
    return dataclasses.replace(cell, config=cfg)


def run_tiny(cell, seed: int = 2**31 + 17, seconds: float = 0.5):
    """A whole run of the cell on the CPU, the look for a chip skipped."""
    import time

    import jax
    from bench import harness
    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:1])
