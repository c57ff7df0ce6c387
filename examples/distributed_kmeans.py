"""Distributed k²-means on a multi-device mesh via the engine layer.

One entry point — ``api.fit(x, k, mesh=...)`` — routes to the sharded
engine step (core.engine.K2Step under shard_map, DESIGN.md §7-8): points
and the Hamerly bound state row-sharded over 'data', centers replicated,
update via hierarchical psum, convergence from the psum'd changed count
(zero full-assignment host transfers inside the loop). ``init="gdi"``
seeds shard-aware: greedy frontier rounds per shard + a weighted
center-level merge.

Spawns itself with 8 host-platform devices so it runs anywhere:

    PYTHONPATH=src python examples/distributed_kmeans.py

On a real pod the same step function runs on the (16, 16) production mesh
(see src/repro/launch/mesh.py); points shard over 'pod' x 'data' and the
psum reduces over ICI before DCN.
"""
import os
import subprocess
import sys

_CHILD = "REPRO_DISTRIBUTED_CHILD"


def child():
    import jax
    import numpy as np
    from repro.core import OpCounter, assign_nearest, fit, fit_k2means
    from repro.data import gmm_blobs

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")
    key = jax.random.PRNGKey(0)
    x = gmm_blobs(key, 8192, 32, true_k=40)
    k, kn = 64, 8

    # one API for every placement: mesh=... puts the same engine
    # iteration on the sharded fast path
    counter = OpCounter()
    r = fit(x, k, mesh=mesh, kn=kn, max_iters=25, init="gdi",
            key=key, counter=counter, backend="pallas")
    hist = [e for _, e in r.history]
    print(f"distributed: {r.iterations} iters, energy {r.energy:.1f} "
          f"(monotone: {all(b <= a + 1e-2 for a, b in zip(hist, hist[1:]))}), "
          f"{counter.total:.0f} counted ops")

    # single-device reference from the same centers (assignment-seeded)
    a0 = assign_nearest(x, r.centers)
    ref = fit_k2means(x, r.centers, a0, kn=kn, max_iters=25,
                      backend="pallas")
    print(f"single-device refine from the distributed centers: "
          f"energy {ref.energy:.1f} "
          f"(rel diff {(r.energy - ref.energy) / ref.energy:+.2e})")
    print("per-iteration: assignment + bound state fully sharded over "
          "'data'; update = local segment-sum + hierarchical psum; center "
          "kNN graph replicated; convergence = psum'd changed count "
          "(no full-assignment host sync)")


if __name__ == "__main__":
    if os.environ.get(_CHILD):
        child()
    else:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"   # a CPU mesh; never claim the chip
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env[_CHILD] = "1"
        env.setdefault("PYTHONPATH", "src")
        raise SystemExit(subprocess.call([sys.executable, __file__],
                                         env=env))
