"""Assignment hot-path microbenchmark: XLA gather path vs the Pallas
candidate-assignment kernels (per-row legacy vs bkn-tiled).

``PYTHONPATH=src python -m benchmarks.assign_bench [--fast] [--out PATH]``

For each (n, k, k_n, d) configuration the three paths compute the same
k_n-restricted assignment from a realistic cluster-grouped layout
(group_by_cluster_device on an actual nearest-center assignment):

- ``xla``:     the lax.map + per-point ``c[cand]`` gather used by the
               ``backend="xla"`` reference in core/k2means.py;
- ``rowwise``: the legacy Pallas kernel, grid (nb, kn) — one candidate-row
               DMA and one (bn,d)x(d,1) dot per grid step;
- ``tiled``:   the tiled Pallas kernel, grid (nb, ceil(kn/bkn)) — one
               bkn-wide candidate-slab DMA and one MXU-shaped
               (bn,d)x(d,bkn) matmul per grid step.

Assignments are cross-checked for exact equality, grid-step counts are
reported per kernel generation, and wall-clock (median of --repeats, after
a warm-up compile) is written to BENCH_assign.json so the perf trajectory
is tracked from PR 1 onward. Off-TPU the kernels run in interpret mode, so
absolute wall-clock there measures the interpreter, not the hardware — the
grid-step ratio is the machine-independent metric (the JSON records which
mode produced the numbers).
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distance import gather_candidate_sqdist, sqnorm
from repro.data import gmm_blobs
from repro.kernels.candidate_assign import (candidate_assign_tiled,
                                            candidate_tables, pad_candidates)
from repro.kernels.center_knn import center_knn
from repro.kernels.ops import (assign_nearest_pallas, candidate_assign_rowwise,
                               group_by_cluster_device, k2_assign_grouped,
                               resolve_interpret, rowwise_grid_steps,
                               scatter_from_grouped, tiled_grid_steps)

CONFIGS = [
    # (n, k, kn, d, bn, bkn)
    (2048, 64, 16, 32, 64, 8),
    (2048, 64, 32, 32, 64, 8),      # the kn=32 tile-ratio headline config
    (2048, 64, 32, 32, 64, 16),
    (4096, 256, 16, 32, 16, 8),
    (4096, 128, 32, 64, 32, 8),
]
FAST_CONFIGS = CONFIGS[:2]


@functools.partial(jax.jit, static_argnames=("chunk",))
def xla_candidate_assign(x, c, cand, chunk: int = 2048):
    """The backend="xla" hot path: chunked per-point candidate gather."""
    n, d = x.shape
    kn = cand.shape[1]
    c_sq = sqnorm(c)
    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    candp = jnp.pad(cand, ((0, pad), (0, 0)))

    def body(args):
        xb, candb = args
        sq = gather_candidate_sqdist(xb, c, candb)
        loc = jnp.argmin(sq, axis=1)
        return jnp.take_along_axis(candb, loc[:, None], 1)[:, 0], \
            jnp.min(sq, axis=1)

    a, dmin = jax.lax.map(body, (xp.reshape(-1, chunk, d),
                                 candp.reshape(-1, chunk, kn)))
    return a.reshape(-1)[:n].astype(jnp.int32), dmin.reshape(-1)[:n]


def _median_wall(fn, repeats: int):
    fn()                                   # warm-up (compile)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_config(n, k, kn, d, bn, bkn, repeats, interpret):
    key = jax.random.fold_in(jax.random.PRNGKey(17), n * k + kn + d)
    x = gmm_blobs(key, n, d, true_k=max(k // 4, 2))
    c = x[jax.random.choice(key, n, (k,), replace=False)]
    a0, d0 = assign_nearest_pallas(x, c, interpret=interpret)
    neighbors = center_knn(c, kn, interpret=interpret)

    perm, b2c = group_by_cluster_device(a0, k, bn)
    nb = perm.shape[0] // bn
    valid_block = jnp.any((perm >= 0).reshape(nb, bn), axis=1)
    skip = (~valid_block).astype(jnp.int32)   # only all-padding blocks skip
    safe_perm = jnp.maximum(perm, 0)
    big = jnp.full((n,), 1e30, jnp.float32)

    # --- the three paths ---------------------------------------------------
    cand_pt = neighbors[a0]                   # (n, kn) per-point lists
    a_x, _ = xla_candidate_assign(x, c, cand_pt)

    cand_blk = neighbors[b2c]                 # (nb, kn) per-block lists
    xg = x[safe_perm]
    pa, pd = a0[safe_perm], d0[safe_perm]
    a_rg, _ = candidate_assign_rowwise(xg, c, cand_blk, skip, pa, pd,
                                       bn=bn, interpret=interpret)
    a_r = scatter_from_grouped(perm, a_rg, a0)

    a_t, _, _ = k2_assign_grouped(x, c, neighbors, perm, b2c, skip,
                                  a0, d0, big, bn=bn, bkn=bkn,
                                  interpret=interpret)

    assert (np.asarray(a_x) == np.asarray(a_r)).all(), "rowwise != xla"
    assert (np.asarray(a_x) == np.asarray(a_t)).all(), "tiled != xla"

    # kernel-only timings on pre-built inputs, identical scope for both
    # kernel generations; wall_tiled_e2e_s adds the tiled path's own
    # per-iteration overhead (candidate-table build, point gather,
    # scatter-back) for an honest end-to-end number. wall_xla_s includes
    # its neighbors[a0] gather — that gather IS the xla hot path's layout
    # cost, the analogue of what the grouped layout precomputes.
    cidx = pad_candidates(neighbors.astype(jnp.int32), bkn)
    ctab, csqtab = candidate_tables(c, cidx)
    pd2 = big[safe_perm]
    wall_xla = _median_wall(
        lambda: xla_candidate_assign(x, c, neighbors[a0]), repeats)
    wall_rowwise = _median_wall(
        lambda: candidate_assign_rowwise(xg, c, cand_blk, skip, pa, pd,
                                         bn=bn, interpret=interpret),
        repeats)
    wall_tiled = _median_wall(
        lambda: candidate_assign_tiled(xg, ctab, csqtab, cidx, b2c, skip,
                                       pa, pd, pd2, bn=bn, bkn=bkn,
                                       interpret=interpret),
        repeats)
    wall_tiled_e2e = _median_wall(
        lambda: k2_assign_grouped(x, c, neighbors, perm, b2c, skip, a0, d0,
                                  big, bn=bn, bkn=bkn, interpret=interpret),
        repeats)

    steps_row = rowwise_grid_steps(int(nb * bn), kn, bn)
    steps_tiled = tiled_grid_steps(int(nb * bn), kn, bn, bkn)
    return {
        "n": n, "k": k, "kn": kn, "d": d, "bn": bn, "bkn": bkn,
        "blocks": int(nb),
        "grid_steps_rowwise": steps_row,
        "grid_steps_tiled": steps_tiled,
        "grid_step_ratio": round(steps_row / steps_tiled, 2),
        "wall_xla_s": wall_xla,
        "wall_rowwise_s": wall_rowwise,
        "wall_tiled_s": wall_tiled,
        "wall_tiled_e2e_s": wall_tiled_e2e,
        "tiled_vs_rowwise_wall": round(wall_rowwise / wall_tiled, 2),
    }


def run(fast: bool = False, repeats: int = 3, out: str = "BENCH_assign.json"):
    interpret = resolve_interpret()
    results = []
    for cfg in (FAST_CONFIGS if fast else CONFIGS):
        r = bench_config(*cfg, repeats=repeats, interpret=interpret)
        results.append(r)
        print(f"n={r['n']} k={r['k']} kn={r['kn']} d={r['d']} "
              f"bn={r['bn']} bkn={r['bkn']}: grid "
              f"{r['grid_steps_rowwise']} -> {r['grid_steps_tiled']} steps "
              f"({r['grid_step_ratio']}x fewer), wall xla/rowwise/tiled = "
              f"{r['wall_xla_s']:.4f}/{r['wall_rowwise_s']:.4f}/"
              f"{r['wall_tiled_s']:.4f}s")
    payload = {
        "backend": jax.default_backend(),
        "interpret_mode": interpret,
        "repeats": repeats,
        "results": results,
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="BENCH_assign.json")
    args = ap.parse_args()
    run(fast=args.fast, repeats=args.repeats, out=args.out)
