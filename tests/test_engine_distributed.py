"""Engine-layer distributed k²-means on the 4-device debug mesh.

The sharded engine step (core.engine.K2Step(mesh=...)) must be
assignment-identical to the single-device fit_k2means from the same
init — per iteration, not just at convergence — for both backends, with
convergence driven by the psum'd changed count (no full-assignment host
transfers inside the loop). Sharded GDI seeding must land within
tolerance of the replicated device GDI's energy. Needs >1 host-platform
devices, so each test runs in a subprocess with XLA_FLAGS set (the main
pytest process must keep 1 device)."""
import json
import os
import subprocess
import sys

import pytest

_ENGINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.core import (OpCounter, assign_nearest, fit, fit_k2means,
                        K2State, K2Step, init_state)
from repro.core.distributed import fit_distributed_k2means
from repro.core.k2means import k2means_pallas_step
from repro.data import gmm_blobs
from repro.launch.mesh import make_debug_cluster_mesh

mesh = make_debug_cluster_mesh()
key = jax.random.PRNGKey(0)
k, kn, bn, bkn = 16, 6, 8, 8
out = {"devices": len(jax.devices())}

# --- per-iteration parity: sharded pallas engine step vs the
# single-device pallas step, same init, lockstep ------------------------
x = gmm_blobs(key, 1024, 16, true_k=10)
init = x[jax.random.choice(key, 1024, shape=(k,), replace=False)]
a0 = assign_nearest(x, init).astype(jnp.int32)
step = K2Step(k=k, kn=kn, backend="pallas", mesh=mesh, bn=bn,
              bkn=bkn).build(1024)
w = jnp.ones((1024,), x.dtype)
sd = init_state(init, a0, kn)
ss = init_state(init, a0, kn)
per_iter_same = True
for it in range(6):
    sd, stats_d = step(x, w, sd)
    c, a, u, lo, nb, stats_s = k2means_pallas_step(
        x, ss.c, ss.a, ss.u, ss.lo, ss.prev_nb, ss.first, kn, bn, bkn,
        True)
    ss = K2State(c, a, u, lo, nb, jnp.array(False))
    per_iter_same &= bool((np.asarray(sd.a) == np.asarray(ss.a)).all())
    per_iter_same &= np.allclose(np.asarray(sd.c), np.asarray(ss.c),
                                 rtol=1e-5, atol=1e-5)
out["per_iter_same"] = per_iter_same
# n_need may differ across placements (block-granular recompute follows
# the shard-local grouping, DESIGN.md §3.1); changed must not
out["stats_match"] = bool(int(stats_d.changed) == int(stats_s[1]))

# --- driver parity + counted ops, all three backends -------------------
ref_p = fit_k2means(x, init, a0, kn=kn, max_iters=25, backend="pallas")
ref_x = fit_k2means(x, init, a0, kn=kn, max_iters=25)
dist = {}
for backend in ("pallas", "xla", "legacy"):
    cnt = OpCounter()
    r = fit_distributed_k2means(x, k, kn, mesh, key, max_iters=25,
                                init_centers=init, backend=backend,
                                counter=cnt)
    ref = ref_p if backend == "pallas" else ref_x
    dist[backend] = {
        "same": bool((np.asarray(r.assignment)
                      == np.asarray(ref.assignment)).all()),
        "iters": r.iterations, "ref_iters": ref.iterations,
        "distances": cnt.distances, "ops": cnt.total,
        "energy": r.energy, "ref_energy": ref.energy,
    }
out["dist"] = dist

# --- uneven shards: n=1000 over 4 devices (duplicate-row padding, w=0) -
xu = gmm_blobs(jax.random.PRNGKey(5), 1000, 16, true_k=10)
initu = xu[jax.random.choice(jax.random.PRNGKey(6), 1000, shape=(k,),
                             replace=False)]
ru = fit_distributed_k2means(xu, k, kn, mesh, key, max_iters=20,
                             init_centers=initu, backend="pallas")
refu = fit_k2means(xu, initu, assign_nearest(xu, initu), kn=kn,
                   max_iters=20, backend="pallas")
out["uneven_same"] = bool((np.asarray(ru.assignment)
                           == np.asarray(refu.assignment)).all())
out["uneven_shape"] = list(np.asarray(ru.assignment).shape)
out["uneven_energy_rel"] = abs(ru.energy - refu.energy) / refu.energy

# --- deferred monitoring: monitor_every > 1 leaves the result unchanged
ra = fit_distributed_k2means(x, k, kn, mesh, key, max_iters=25,
                             init_centers=init, backend="xla")
rb = fit_distributed_k2means(x, k, kn, mesh, key, max_iters=25,
                             init_centers=init, backend="xla",
                             monitor_every=4)
out["monitor_same"] = bool((np.asarray(ra.assignment)
                            == np.asarray(rb.assignment)).all()
                           and ra.iterations == rb.iterations)

# --- ISSUE 4: resident-layout engine on the mesh — per-iteration parity
# with the single-device rebuild engine through repairs and re-sorts
# (shard-local arenas, psum'd delta updates) ----------------------------
sb_rs = K2Step(k=k, kn=kn, backend="pallas", mesh=mesh, bn=bn, bkn=bkn,
               residency="resident", regroup_every=4, move_cap=128)
step_rs = sb_rs.build(1024, 16)
st_rs = sb_rs.init_resident(x, w, init, a0)
st_rb = init_state(init, a0, kn)
res_same = True
repair_moved = []           # moved counts of sparse (non-re-sort) iters
for it in range(8):
    st_rs, stats_rs = step_rs(x, w, st_rs)
    c2, a2, u2, lo2, nb2, stats_rb = k2means_pallas_step(
        x, st_rb.c, st_rb.a, st_rb.u, st_rb.lo, st_rb.prev_nb, st_rb.first,
        kn, bn, bkn, True)
    st_rb = K2State(c2, a2, u2, lo2, nb2, jnp.array(False))
    a_rs = sb_rs.final_assignment(st_rs, 1024)
    res_same &= bool((np.asarray(a_rs) == np.asarray(st_rb.a)).all())
    res_same &= bool(int(stats_rs.changed) == int(stats_rb[1]))
    if int(stats_rs.resorted) == 0:
        repair_moved.append(int(stats_rs.moved))
out["resident_per_iter_same"] = res_same
out["resident_repair_iters"] = len(repair_moved)
out["resident_repair_moved_max"] = max(repair_moved) if repair_moved else -1

# resident driver parity: sharded resident == single-device resident
cnt_rs = OpCounter()
r_rs = fit_distributed_k2means(x, k, kn, mesh, key, max_iters=25,
                               init_centers=init, backend="pallas",
                               residency="resident", counter=cnt_rs)
out["resident_driver_same"] = bool((np.asarray(r_rs.assignment)
                                    == np.asarray(ref_p.assignment)).all()
                                   and r_rs.iterations == ref_p.iterations)
out["resident_placement"] = r_rs.placement
# sparse repairs move fewer bytes than the rebuild engine's full regroup
cnt_rb = OpCounter()
fit_distributed_k2means(x, k, kn, mesh, key, max_iters=25,
                        init_centers=init, backend="pallas",
                        residency="rebuild", counter=cnt_rb)
out["resident_bytes_win"] = bool(0 < cnt_rs.bytes_moved
                                 < cnt_rb.bytes_moved)

# --- api.fit(mesh=...) entry point -------------------------------------
capi = OpCounter()
rapi = fit(x, k, mesh=mesh, kn=kn, max_iters=10, init="random",
           key=key, counter=capi, backend="xla")
out["api_shapes"] = [list(np.asarray(rapi.centers).shape),
                     list(np.asarray(rapi.assignment).shape)]
out["api_ops"] = capi.total
out["api_placement"] = rapi.placement
print("RESULT " + json.dumps(out))
"""

_GDI_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro.core import OpCounter, clustering_energy, gdi_device_init
from repro.core.distributed import fit_distributed_k2means
from repro.data import gmm_blobs
from repro.launch.mesh import make_debug_cluster_mesh

mesh = make_debug_cluster_mesh()
out = {}
x = gmm_blobs(jax.random.PRNGKey(1), 4096, 16, true_k=32)
key = jax.random.PRNGKey(3)

# sharded GDI seeding (max_iters=0 isolates the seed) vs replicated GDI
cnt = OpCounter()
r = fit_distributed_k2means(x, 16, 6, mesh, key, max_iters=0,
                            init="gdi", counter=cnt)
e_shard = float(clustering_energy(x, r.centers, r.assignment))
c_rep, a_rep = gdi_device_init(x, 16, key)
e_rep = float(clustering_energy(x, c_rep, a_rep))
out["ratio"] = e_shard / e_rep
out["seed_ops"] = cnt.total
out["seed_sorts"] = cnt.sort_equivalents
out["assign_range_ok"] = bool((np.asarray(r.assignment) >= 0).all()
                              and (np.asarray(r.assignment) < 16).all())

# k=12: k doesn't divide the shard count; merge still yields k clusters
r12 = fit_distributed_k2means(x, 12, 6, mesh, key, max_iters=5,
                              init="gdi")
out["k12_shape"] = list(np.asarray(r12.centers).shape)
out["k12_range_ok"] = bool((np.asarray(r12.assignment) >= 0).all()
                           and (np.asarray(r12.assignment) < 12).all())
out["k12_finite"] = bool(np.isfinite(r12.energy))

# gdi_replicated baseline path stays wired
rrep = fit_distributed_k2means(x, 16, 6, mesh, key, max_iters=3,
                               init="gdi_replicated")
out["rep_finite"] = bool(np.isfinite(rrep.energy))
print("RESULT " + json.dumps(out))
"""


def _run(script):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    return json.loads(line[0][len("RESULT "):])


def test_engine_step_matches_single_device():
    """ISSUE 3 acceptance: the 4-device sharded engine step is
    assignment-identical to single-device fit_k2means(backend="pallas")
    from the same init, per iteration and through the driver, for both
    engine backends; convergence comes from the psum'd changed count and
    the bounded engine counts fewer distances than the legacy bound-free
    step."""
    out = _run(_ENGINE_SCRIPT)
    assert out["devices"] == 4
    assert out["per_iter_same"]
    assert out["stats_match"]
    for backend in ("pallas", "xla", "legacy"):
        d = out["dist"][backend]
        assert d["same"], (backend, d)
        assert d["iters"] == d["ref_iters"], (backend, d)
    # Hamerly gating: the engine recomputes fewer candidate distances
    # than the bound-free legacy step over the same trajectory
    assert out["dist"]["pallas"]["distances"] \
        < out["dist"]["legacy"]["distances"]
    assert out["dist"]["xla"]["distances"] \
        < out["dist"]["legacy"]["distances"]
    # uneven shards: padding rows never leak into results
    assert out["uneven_same"]
    assert out["uneven_shape"] == [1000]
    assert out["uneven_energy_rel"] < 1e-6
    assert out["monitor_same"]
    assert out["api_shapes"] == [[16, 16], [1024]]
    assert out["api_ops"] > 0
    # ISSUE 4: sharded resident engine — per-iteration assignment parity
    # with the single-device rebuild step, driver parity with the
    # single-device resident fit, and the layout-traffic win
    assert out["resident_per_iter_same"]
    # sparse repairs actually happened and moved far less than the arena
    assert out["resident_repair_iters"] > 0
    assert 0 <= out["resident_repair_moved_max"] < 1024
    assert out["resident_driver_same"]
    assert out["resident_bytes_win"]
    # the mesh fit reports where its row-sharded state lived: an equal
    # share on each of the 4 devices
    for place, names in ((out["resident_placement"],
                          ("xg", "pid", "ug", "lo_g")),
                         (out["api_placement"], ("u", "lo"))):
        assert sorted(place) == sorted(names)
        for rows in place.values():
            assert len(rows) == 4 and len(set(rows.values())) == 1, rows
    assert sum(out["api_placement"]["u"].values()) == 1024


def test_sharded_gdi_seeding_energy():
    """Sharded GDI (frontier rounds per shard-group + weighted
    center-level merge + leaf inheritance) seeds within tolerance of the
    replicated device GDI, charges counted ops, and handles k that does
    not divide the shard count."""
    out = _run(_GDI_SCRIPT)
    assert out["assign_range_ok"]
    assert out["ratio"] < 1.35, out["ratio"]
    assert out["seed_ops"] > 0
    assert out["seed_sorts"] > 0
    assert out["k12_shape"] == [12, 16]
    assert out["k12_range_ok"]
    assert out["k12_finite"]
    assert out["rep_finite"]
