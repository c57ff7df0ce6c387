"""Smoke run of the k²-means fit and serve path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip mesh fit against one chip

One chip: the IVF coarse-quantizer shape (SIFT-shaped d=128, k=4096,
n=1,048,576, i.e. 256 training rows per centroid) goes through
``repro.core.fit`` on the Pallas backend from device GDI, at kn=32 and at
kn=128, and is checked against a plain ``jax.numpy`` Lloyd reference at
HIGHEST precision, from the same GDI centers for the same number of
iterations. The kn=128 fit is then served with the model's default
routing: ``KMeansModel.predict`` against a brute-force argmin,
``partial_fit`` folds under the arena invariants, and a ``ServeExecutor``
trace in which every request must be answered.

Four chips: the same fit row-sharded over a 4-chip mesh at 4x the rows,
against the same fit on one of the chips from the same k-means++ centers;
the sharded bound state and arena must sit on 4 distinct devices, and
every device must have held its row shard.

The run refuses any backend but the TPU (the kernels would silently fall
back to the Pallas interpreter). A failed check exits non-zero without
the result line; the last stdout line is the JSON result. Wall times
printed here are single smoke-run times with compilation included, not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402

# On this mixture (power-law weights, isotropic 128-d blobs) the
# kn-restricted search plateaus above the 1% level at kn=32 (1.0143 x
# Lloyd at this shape on a v5e), so the kn=32 ratio is printed, not
# checked; kn=128 sits inside the level and is checked and served
D, K, KN, ITERS = 128, 4096, 128, 20
KN_PAPER = 32                   # the paper's k_n: fit and print only
N_ONE = 1 << 20                 # one chip: 256 rows per centroid
N_MESH = 4 * N_ONE              # four chips: weak scaling at 4x the rows
N_SEED = 1 << 16                # rows the four-chip k-means++ seeding reads
N_QUERY, N_FOLD, FOLDS = 65536, 8192, 4
N_REQ, REQ_ROWS, REQ_GAP = 300, 64, 5e-3
SEED = 0
RECALL_BAR = 0.99               # BENCH_predict's recall@1 acceptance
ENERGY_RTOL = 1e-4              # reported vs recomputed fit energy
HIGHEST = jax.lax.Precision.HIGHEST


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    """Print a check's verdict; a failed check ends the run non-zero."""
    log(f"{'PASS' if ok else 'FAIL'} {what}")
    if not ok:
        raise SystemExit(1)


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


@functools.partial(jax.jit, static_argnames=("chunk",))
def nearest(x, c, chunk: int = 32768):
    """Exact nearest center per row, (n,) int32 and squared distance,
    with the cross term at HIGHEST precision, ``chunk`` rows at a time."""
    csq = jnp.sum(c * c, axis=1)

    def block(xb):
        sq = (jnp.sum(xb * xb, axis=1)[:, None]
              - 2.0 * jnp.dot(xb, c.T, precision=HIGHEST) + csq)
        return jnp.argmin(sq, axis=1).astype(jnp.int32), jnp.min(sq, axis=1)

    chunk = min(chunk, x.shape[0])
    a, sq = jax.lax.map(block, x.reshape(-1, chunk, x.shape[1]))
    return a.reshape(-1), jnp.maximum(sq.reshape(-1), 0.0)


def energy(x, c, a) -> float:
    """Clustering energy of (c, a) on x, elementwise in f32."""
    return float(jnp.sum(jnp.square(x - c[a])))


def lloyd_reference(x, c, iters: int) -> float:
    """Plain Lloyd: exact assignment, then the mean update, ``iters``
    times; the energy of the last (assignment, updated centers) pair —
    the quantity ``fit`` reports."""
    k = c.shape[0]
    ones = jnp.ones((x.shape[0],), x.dtype)
    for _ in range(iters):
        a, _ = nearest(x, c)
        sums = jax.ops.segment_sum(x, a, num_segments=k)
        cnt = jax.ops.segment_sum(ones, a, num_segments=k)
        c = jnp.where(cnt[:, None] > 0, sums / jnp.maximum(cnt, 1.0)[:, None],
                      c)
    return energy(x, c, a)


def data(n_rows: int, d: int, k: int):
    """One GMM draw (seeded), split by the caller into fit rows, fresh
    queries and streamed folds of the same mixture."""
    from repro.data import gmm_blobs
    return gmm_blobs(jax.random.PRNGKey(SEED), n_rows, d, true_k=k)


def compiled_step_check(n: int, d: int, k: int, kn: int) -> None:
    """The resident step the fit runs must be the compiled Pallas path:
    interpret resolves to False and the lowered program holds the TPU
    kernels."""
    from repro.core import K2Step
    from repro.kernels.ops import resolve_interpret
    interp = resolve_interpret()
    sb = K2Step(k=k, kn=kn, backend="pallas", residency="resident")
    spec = jax.ShapeDtypeStruct
    x, w = spec((n, d), jnp.float32), spec((n,), jnp.float32)
    state = jax.eval_shape(sb.init_resident, x, w, spec((k, d), jnp.float32),
                           spec((n,), jnp.int32))
    text = jax.jit(sb.build(n, d)).lower(x, w, state).as_text()
    kernels = text.count("tpu_custom_call")
    log(f"resident step: interpret={interp}, tpu_custom_call sites="
        f"{kernels}")
    check(interp is False and kernels > 0,
          "kernels run compiled (interpret=False, tpu_custom_call lowered)")


def fit_checked(x, k: int, kn: int, iters: int, key, e_ref: float,
                gate: bool):
    """``api.fit`` at one kn: compiled kernels, finite in-range output,
    reported energy equal to the recomputed one; the energy ratio to the
    Lloyd reference is checked when ``gate``, else only printed."""
    from repro.configs.paper import REFERENCE_LEVELS
    from repro.core import fit
    n, d = x.shape
    log(f"fit shape: n={n} d={d} k={k} kn={kn} max_iters={iters}")
    compiled_step_check(n, d, k, kn)
    res, t_fit = timed(fit, x, k, method="k2means", init="gdi",
                       backend="pallas", kn=kn, max_iters=iters, key=key)
    log(f"smoke time (not a benchmark): api.fit incl. its GDI init "
        f"{t_fit:.3f} s; {res.iterations} iterations")
    c, a = res.centers, res.assignment
    check(bool(jnp.all(jnp.isfinite(c))) and int(a.min()) >= 0
          and int(a.max()) < k, "fit centers finite, assignment in range")
    e_re = energy(x, c, a)
    rel = abs(res.energy - e_re) / e_re
    log(f"fit energy {res.energy:.6e}, recomputed {e_re:.6e}, rel {rel:.3e}")
    check(rel <= ENERGY_RTOL, f"reported energy within {ENERGY_RTOL} of the "
          "recomputed energy")
    ratio = res.energy / e_ref
    bar = 1.0 + REFERENCE_LEVELS[2]
    log(f"kn={kn}: k2-means / Lloyd = {ratio:.6f}")
    if gate:
        check(ratio <= bar, f"energy ratio <= {bar}")
    else:
        log(f"(kn={kn} ratio printed, not checked against {bar})")
    return res


def one_chip(n: int = N_ONE, d: int = D, k: int = K, kn: int = KN,
             iters: int = ITERS, n_query: int = N_QUERY,
             n_fold: int = N_FOLD, folds: int = FOLDS, n_req: int = N_REQ,
             req_rows: int = REQ_ROWS) -> None:
    from repro.core import (KMeansModel, assign_nearest, fit_k2means,
                            gdi_device_init)
    from repro.ft.invariants import resident_violations
    from repro.serve import ServeExecutor
    from repro.serve.queue import Request

    pool = data(n + n_query + folds * n_fold, d, k)
    x = pool[:n]
    queries = pool[n:n + n_query]
    stream = pool[n + n_query:]
    key = jax.random.PRNGKey(SEED + 1)
    k_init, _ = jax.random.split(key)      # the split api.fit makes

    # --- fit ------------------------------------------------------------
    (c0, _), t_init = timed(gdi_device_init, x, k, k_init)
    log(f"smoke time (not a benchmark): device GDI init {t_init:.3f} s")
    e_ref = lloyd_reference(x, c0, iters)
    log(f"HIGHEST-precision Lloyd from the same GDI centers, {iters} "
        f"iterations: energy {e_ref:.6e}")
    fit_checked(x, k, KN_PAPER, iters, key, e_ref, gate=False)
    res = fit_checked(x, k, kn, iters, key, e_ref, gate=True)
    # the state api.fit starts k2-means from: the GDI centers and their
    # exact assignment
    a0 = assign_nearest(x, c0)
    res_x = fit_k2means(x, c0, a0, kn=kn, max_iters=iters, backend="xla")
    agree = float(jnp.mean(res_x.assignment == res.assignment))
    log(f"pallas vs xla backend from the same start: assignment agreement "
        f"{agree:.6f}, energies {res.energy:.6e} / {res_x.energy:.6e}")

    # --- serve ----------------------------------------------------------
    # kn as api.fit(return_model=True) passes it; routing at its defaults
    model = KMeansModel.from_result(res, x, kn=kn, backend="pallas")
    log(f"model routing: {model.route_groups} groups, lists "
        f"{model.route_cap} wide, {model.route_probes} probes")
    pred = model.predict(queries)
    truth, _ = nearest(queries, model.centers)
    recall = float(jnp.mean(pred == truth))
    log(f"predict: {n_query} fresh queries, recall@1 vs HIGHEST brute force "
        f"{recall:.6f}")
    check(recall >= RECALL_BAR, f"predict recall@1 >= {RECALL_BAR}")
    for i in range(folds):
        model.partial_fit(stream[i * n_fold:(i + 1) * n_fold])
        vio = np.asarray(resident_violations(model.state, n=model.capacity))
        log(f"partial_fit batch {i + 1}/{folds} ({n_fold} rows): invariant "
            f"lanes {vio.tolist()}")
        check(not vio.any(), f"arena invariants clean after fold {i + 1}")

    ex = ServeExecutor(model)
    q_host = np.asarray(queries)
    reqs = [Request(rid=i, kind="predict",
                    x=q_host[(i * req_rows) % n_query:][:req_rows],
                    t_arrival=i * REQ_GAP, deadline=i * REQ_GAP + 1.0,
                    rows=req_rows) for i in range(n_req)]
    resp = ex.run_trace(reqs)
    ok = [r for r in resp if r.status == "ok"
          and np.asarray(r.result).shape == (req_rows,)]
    heals = [e for e in ex.events if e[1] == "heal"]
    log(f"ServeExecutor: {len(resp)} responses to {n_req} requests, "
        f"{len(ok)} answered ok, {len(heals)} heals")
    check(len(resp) == n_req and len(ok) == n_req and not heals,
          "every executor request answered, none dropped")
    device_memory("after the serve phase")


def device_memory(tag: str) -> list[int]:
    """Log each device's bytes in use and peak; returns the peaks."""
    peaks = []
    for dev in jax.devices():
        st = dev.memory_stats() or {}
        peaks.append(st.get("peak_bytes_in_use", 0))
        log(f"{tag} {dev}: in use {st.get('bytes_in_use', 0) / 2**30:.3f} "
            f"GiB, peak {peaks[-1] / 2**30:.3f} GiB")
    return peaks


def four_chips(n: int = N_MESH, d: int = D, k: int = K, kn: int = KN,
               iters: int = ITERS) -> None:
    from repro.core import (OpCounter, assign_nearest, fit, fit_k2means,
                            kmeanspp_init)

    check(len(jax.devices()) == 4, "four devices visible")
    mesh = jax.make_mesh((4,), ("data",))
    x = data(n, d, k)
    key = jax.random.PRNGKey(SEED + 1)
    k_init, _ = jax.random.split(key)      # the split api.fit makes
    log(f"mesh fit shape: n={n} d={d} k={k} kn={kn} max_iters={iters}, "
        f"mesh {dict(mesh.shape)}")
    device_memory("before the fit (x generated on device 0)")
    # k-means++ over the first N_SEED rows (iid draws of the same
    # mixture): its k-step host loop over all the rows would cost minutes;
    # both fits start from these centers
    c0, t_pp = timed(kmeanspp_init, x[:N_SEED], k, k_init, OpCounter())
    log(f"smoke time (not a benchmark): k-means++ over {min(n, N_SEED)} "
        f"rows {t_pp:.3f} s")
    r_mesh, t_mesh = timed(fit, x, k, mesh=mesh, method="k2means",
                           init_centers=c0, backend="pallas", kn=kn,
                           max_iters=iters, key=key)
    peaks = device_memory("after the mesh fit")
    shard = n // 4 * d * x.dtype.itemsize
    check(min(peaks) >= shard, "every device held at least its row shard "
          f"({shard / 2**30:.3f} GiB) during the mesh fit")
    log(f"smoke time (not a benchmark): mesh fit {t_mesh:.3f} s; "
        f"{r_mesh.iterations} iterations")

    # where the mesh fit's resident arena and bound state lived, as the
    # fit reports it
    spread = r_mesh.placement or {}
    for name, rows in spread.items():
        log(f"resident {name}: rows per device {rows}")
    check(sorted(spread) == ["lo_g", "pid", "ug", "xg"]
          and all(len(v) == 4 and len(set(v.values())) == 1
                  for v in spread.values()),
          "arena and bound state split evenly over 4 distinct devices")

    # the single-device api.fit path from the same centers
    r_one, t_one = timed(fit_k2means, x, c0, assign_nearest(x, c0), kn=kn,
                         max_iters=iters, backend="pallas")
    ratio = r_mesh.energy / r_one.energy
    log(f"smoke time (not a benchmark): one-chip fit {t_one:.3f} s; "
        f"{r_one.iterations} iterations")
    log(f"energy mesh {r_mesh.energy:.6e} / one chip {r_one.energy:.6e} = "
        f"{ratio:.6f}")
    check(ratio <= 1.01, "mesh vs one-chip energy ratio <= 1.01")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh fit and its one-chip "
                         "comparison")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from benchmarks.common import use_compile_cache
    log(f"device {dev.device_kind} x{len(jax.devices())}; compilation "
        f"cache {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f"smoke wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
