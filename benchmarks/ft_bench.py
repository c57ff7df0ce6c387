"""Fault-tolerance benchmark: chaos run vs fault-free run (DESIGN.md §11).

The ISSUE 6 acceptance gate, on the 4-device debug mesh at
(n=65536, k=512, kn=32): a chaos schedule combining a poisoned NaN ingest
batch, arena free-pool exhaustion and one simulated host loss must
self-heal to a final energy within 1.01x of the fault-free run, and the
runtime invariant guards must cost <= 2% fault-free wall-clock overhead
at the monitor cadence. Writes BENCH_ft.json: per-run wall clock /
energy / iterations / repair counters, plus the acceptance summary
(energy ratio, guard overhead, recovery iterations — how many
post-fault iterations the chaos run needed to re-enter the 1.01x energy
band).

Spawns itself with 4 host-platform devices so it runs anywhere:

    PYTHONPATH=src python -m benchmarks.ft_bench [--fast | --smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_CHILD = "REPRO_FT_BENCH_CHILD"

# energy band defining "recovered" (and the acceptance gate)
ACCEPT_RATIO = 1.01


def _fit(x, k, kn, mesh, key, iters, counter, **kw):
    from repro.core.distributed import fit_distributed_k2means
    t0 = time.perf_counter()
    r = fit_distributed_k2means(x, k, kn, mesh, key, max_iters=iters,
                                backend="xla", residency="resident",
                                counter=counter, **kw)
    return r, time.perf_counter() - t0


def child(fast: bool, out: str, shape=None):
    import jax
    from repro.core import OpCounter
    from repro.data import gmm_blobs
    from repro.ft import FaultInjector
    from repro.launch.mesh import make_debug_cluster_mesh

    from benchmarks.common import emit

    mesh = make_debug_cluster_mesh()
    n, d, k, kn, iters = shape or ((8192, 32, 64, 16, 20) if fast
                                   else (65536, 32, 512, 32, 60))
    key = jax.random.PRNGKey(0)
    x = gmm_blobs(key, n, d, true_k=2 * k)
    init = x[jax.random.choice(key, n, shape=(k,), replace=False)]
    common = dict(init_centers=init)

    rows, records = [], []

    def record(name, r, wall, counter):
        prof = counter.profile()
        rec = {"run": name, "iterations": r.iterations, "wall_s": wall,
               "energy": float(r.energy), "repairs": prof["repairs"],
               "sanitized_rows": prof["sanitized_rows"],
               "resorts": prof["resorts"], "retries": prof["retries"],
               "history": [float(e) for _, e in r.history]}
        records.append(rec)
        rows.append([name, r.iterations, round(wall, 2),
                     round(float(r.energy), 1),
                     sum(prof["repairs"].values()),
                     round(prof["sanitized_rows"], 0)])
        return rec

    # warmup: compile the step and the guard once so the timed runs
    # measure steady-state iteration cost, not JIT compilation
    _fit(x, k, kn, mesh, key, 2, OpCounter(), guards=True, **common)

    # 1+2. fault-free guards-off vs guards-on: identical trajectories
    # (guards never fire on clean runs), so the guard overhead is the
    # wall ratio. Walls on a shared CPU host are noisy, so interleave
    # the two variants and take the best wall of each — any external
    # load hits both symmetrically (the iter_bench idiom).
    best = {"fault_free": float("inf"), "guarded": float("inf")}
    ref = guarded = None
    for rep in range(2):
        ctr = OpCounter()
        r0, w0 = _fit(x, k, kn, mesh, key, iters, ctr, guards=False,
                      **common)
        best["fault_free"] = min(best["fault_free"], w0)
        if ref is None:
            ref = record("fault_free", r0, w0, ctr)
        ctr = OpCounter()
        r1, w1 = _fit(x, k, kn, mesh, key, iters, ctr, guards=True,
                      **common)
        best["guarded"] = min(best["guarded"], w1)
        if guarded is None:
            guarded = record("guarded", r1, w1, ctr)

    # 3. chaos: NaN ingest batch + arena pool exhaustion + one host loss,
    # guards on (they are on by default under an active injector). The
    # fault iterations sit mid-run; +10 headroom iterations bound the
    # recovery measurement, convergence usually lands well before.
    f_nan, f_pool, f_drop = max(3, iters // 4), max(5, iters // 3), \
        max(7, iters // 2)
    ctr = OpCounter()
    with FaultInjector(seed=0, nan_rows={f_nan: max(32, n // 2048)},
                       exhaust_pool=[f_pool], drop_host={f_drop: 1}):
        r2, w2 = _fit(x, k, kn, mesh, key, iters + 10, ctr, **common)
    chaos = record("chaos", r2, w2, ctr)

    emit(rows, ["run", "iters", "wall_s", "energy", "repairs",
                "sanitized"])

    ratio = chaos["energy"] / ref["energy"]
    overhead = best["guarded"] / best["fault_free"] - 1.0
    # recovery: first post-fault iteration back inside the energy band
    band = ACCEPT_RATIO * ref["energy"]
    recovery = None
    for i, e in enumerate(chaos["history"]):
        if i + 1 > f_drop and e <= band:
            recovery = (i + 1) - f_drop
            break
    summary = {
        "mesh_devices": len(jax.devices()), "n": n, "d": d, "k": k,
        "kn": kn, "iters": iters,
        "fault_iterations": {"nan_rows": f_nan, "exhaust_pool": f_pool,
                             "drop_host": f_drop},
        "energy_ratio_vs_fault_free": round(float(ratio), 6),
        "energy_within_1p01x": bool(ratio <= ACCEPT_RATIO),
        "guard_overhead_frac": round(float(overhead), 4),
        "guard_overhead_within_2pct": bool(overhead <= 0.02),
        "wall_s_best": {k_: round(v, 3) for k_, v in best.items()},
        "recovery_iterations": recovery,
        "chaos_repairs": chaos["repairs"],
        "chaos_sanitized_rows": chaos["sanitized_rows"],
        "chaos_resorts": chaos["resorts"],
    }
    print(f"# ft summary: chaos energy {ratio:.4f}x fault-free "
          f"(acceptance: <= {ACCEPT_RATIO}), guard overhead "
          f"{overhead * 100:+.1f}% (acceptance: <= 2%), recovered "
          f"{recovery} iterations after the host loss, repairs="
          f"{chaos['repairs']} at n={n}, k={k}, kn={kn}")
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
        out = "BENCH_ft.fast.json" if fast else "BENCH_ft.json"
    env = dict(os.environ)
    # a CPU rehearsal mesh: the child must never claim an accelerator
    # that this parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env[_CHILD] = json.dumps({"fast": fast, "out": out, "shape": shape})
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run([sys.executable, "-m", "benchmarks.ft_bench"],
                          env=env, capture_output=True, text=True)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise RuntimeError("ft_bench child failed")
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
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
        ap.add_argument("--smoke", action="store_true",
                        help="tiny shape for the CI schema check")
        args = ap.parse_args()
        if args.smoke:
            run(fast=True, shape=(2048, 16, 32, 8, 10))
        else:
            run(fast=args.fast)
