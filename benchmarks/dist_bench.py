"""Distributed benchmark: bounded engine step vs legacy bound-free step.

The ISSUE 3 acceptance gate: on the 4-device debug mesh at
(n=65536, k=512, kn=32) the bounded engine step must beat the legacy
bound-free sharded step in counted *distance* ops over the same
trajectory (both are exact, so both converge identically; the engine
recomputes only points whose Hamerly bounds or candidate lists demand
it). Writes BENCH_dist.json: per-backend wall clock, counted iteration
ops (seeding excluded — both pay the identical sharded full-assignment
pass), iterations, final energy, plus the acceptance ratio.

Counted ops are backend-independent (engine "xla" and "pallas" charge
identically), so the engine side runs backend="xla" here — interpret-mode
Pallas wall-clock on a CPU debug mesh is not meaningful.

Spawns itself with 4 host-platform devices so it runs anywhere:

    PYTHONPATH=src python -m benchmarks.dist_bench [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_CHILD = "REPRO_DIST_BENCH_CHILD"


def child(fast: bool, out: str, shape=None):
    import jax
    import numpy as np
    from repro.core import OpCounter
    from repro.core.distributed import fit_distributed_k2means
    from repro.data import gmm_blobs
    from repro.launch.mesh import make_debug_cluster_mesh

    from benchmarks.common import emit

    mesh = make_debug_cluster_mesh()
    # enough iterations for the Hamerly bounds to start skipping: the
    # n_need decay begins once center movement slows (~iter 13 at the
    # acceptance shape), so short runs would tie the bound-free baseline
    n, d, k, kn, iters = shape or ((8192, 32, 64, 16, 20) if fast
                                   else (65536, 32, 512, 32, 60))
    key = jax.random.PRNGKey(0)
    x = gmm_blobs(key, n, d, true_k=2 * k)
    init = x[jax.random.choice(key, n, shape=(k,), replace=False)]

    rows, records = [], []
    for backend in ("legacy", "xla"):
        counter = OpCounter()
        t0 = time.perf_counter()
        r = fit_distributed_k2means(x, k, kn, mesh, key, max_iters=iters,
                                    init_centers=init, backend=backend,
                                    counter=counter)
        wall = time.perf_counter() - t0
        # both backends pay the identical sharded seeding pass (n*k
        # distances); compare the iteration loop only
        iter_distances = counter.distances - n * k
        rows.append([backend, r.iterations, round(wall, 2),
                     round(iter_distances, 0), round(counter.total, 0),
                     round(r.energy, 1)])
        records.append({"backend": backend, "iterations": r.iterations,
                        "wall_s": wall, "iter_distances": iter_distances,
                        "total_ops": counter.total, "energy": r.energy})
    emit(rows, ["backend", "iters", "wall_s", "iter_distances",
                "total_ops", "energy"])

    by = {r["backend"]: r for r in records}
    ratio = by["xla"]["iter_distances"] / by["legacy"]["iter_distances"]
    summary = {
        "mesh_devices": len(jax.devices()), "n": n, "d": d, "k": k,
        "kn": kn, "iters": iters,
        "engine_vs_legacy_distance_ratio": round(float(ratio), 4),
        "engine_beats_legacy": bool(ratio < 1.0),
        "energy_rel_diff": float(abs(by["xla"]["energy"]
                                     - by["legacy"]["energy"])
                                 / by["legacy"]["energy"]),
    }
    print(f"# dist summary: bounded engine step used {ratio:.3f}x the "
          f"legacy step's candidate distances over {iters} iterations at "
          f"n={n}, k={k}, kn={kn} (acceptance: < 1.0)")
    with open(out, "w") as f:
        json.dump({"fast": fast, "runs": records, "summary": summary}, f,
                  indent=2)
    print(f"# wrote {out}")
    print("RESULT " + json.dumps(summary))


def run(fast: bool = False, out: str | None = None, shape=None):
    """Parent entry point (also used by benchmarks.run): spawns the child
    with a 4-device host platform, streams its CSV, returns the summary.
    ``shape`` optionally overrides (n, d, k, kn, iters) — the smoke mode
    uses it to keep the schema check tiny."""
    if out is None:     # keep CI-mode runs from clobbering the acceptance
        out = "BENCH_dist.fast.json" if fast else "BENCH_dist.json"
    env = dict(os.environ)
    # a CPU rehearsal mesh: the child must never claim an accelerator
    # that this parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env[_CHILD] = json.dumps({"fast": fast, "out": out, "shape": shape})
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run([sys.executable, "-m", "benchmarks.dist_bench"],
                          env=env, capture_output=True, text=True)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise RuntimeError("dist_bench child failed")
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")]
    return json.loads(line[0][len("RESULT "):]) if line else None


if __name__ == "__main__":
    spec = os.environ.get(_CHILD)
    if spec:
        cfg = json.loads(spec)
        child(cfg["fast"], cfg["out"],
              tuple(cfg["shape"]) if cfg.get("shape") else None)
    else:
        ap = argparse.ArgumentParser()
        ap.add_argument("--fast", action="store_true")
        args = ap.parse_args()
        run(fast=args.fast)
