"""Distributed k²-means — the engine step under shard_map, at pod scale.

This module is a thin placement wrapper: the iteration itself lives in
the engine layer (``core.engine.k2_iteration`` /
``k2_resident_iteration``, DESIGN.md §8-9) and runs here per shard via
:class:`core.engine.K2Step` with ``mesh=...`` — including the Pallas
fast path (``backend="pallas"``: the bound-gated tiled candidate kernel
over each shard's cluster-grouped layout, which the default
``residency="resident"`` keeps device-resident and sparsely repaired
instead of regrouping per iteration). Layout
(DESIGN.md §7): points and the bound-carried state ``(a, u, lo)``
row-sharded over the flattened data axes ('pod' x 'data'); centers and
the replicated k_n-NN center graph on every shard (O(k²d) is tiny next
to O(n·k_n·d / P) per shard); the mean update is a per-shard segment-sum
followed by a hierarchical psum (reduce within pod over ICI, then across
pods over DCN — the reduction runs innermost axis first).

Convergence is device-resident: every iteration yields replicated scalar
stats (recompute count, psum'd changed count, post-update energy) and the
driver host-reads only those — every ``monitor_every`` iterations,
mirroring the single-device deferred-read contract (DESIGN.md §4.3). No
full assignment ever crosses to the host inside the loop.

Initialization (``fit_distributed_k2means(init="gdi")``) is shard-aware:
every shard-group runs greedy frontier rounds (``core.gdi
.gdi_fixed_rounds``) on its local rows under shard_map toward k *local*
leaves (each shard's n/P-point sample yields a full k-covering), the
driver merges the P·k leaf centers down to k with a tiny weighted
center-level Lloyd reduction (k-means||-style), and points inherit their
leaf's meta-cluster — the divisive assignment seeds the iteration for
free and the sharded full-assignment pass is skipped.
``init="gdi_replicated"`` keeps the replicated device GDI as the
seeding-quality baseline.

The legacy bound-free sharded step (``make_distributed_k2means_step``,
``backend="legacy"``) is kept as the benchmark baseline
(``benchmarks/dist_bench.py``): it recomputes every point's k_n
candidates each iteration, where the engine step recomputes only points
whose Hamerly bounds (or candidate lists) demand it.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding

from ..kernels.ops import resolve_interpret
from ..launch.mesh import auto_axes, dp_axes
from ..launch.sharding import clustering_specs
from .distance import (chunked_argmin_sqdist, chunked_candidate_argmin,
                       pairwise_sqdist, sqnorm)
from .engine import K2State, K2Step
from .lloyd import KMeansResult
from .opcount import OpCounter

_SHARDED_INITS = ("random", "kmeanspp", "gdi", "gdi_replicated")


def _axes(mesh, data_axes):
    return tuple(data_axes) if data_axes else dp_axes(mesh)


def _nshards(mesh, data_axes):
    s = 1
    for a in data_axes:
        s *= mesh.shape[a]
    return s


def make_distributed_k2means_step(mesh, kn: int, k: int, *,
                                  data_axes=None, chunk: int = 2048):
    """Legacy bound-free sharded step — the benchmark baseline.

    Builds ``step(x, w, c, a) -> (c', a', energy, changed)``: replicated
    center k_n-NN graph, per-shard k_n-restricted assignment of every
    row (no Hamerly gating), hierarchical psum update. ``w`` masks
    padding rows (uneven shards); ``energy`` is the post-update
    clustering energy (the engine stats convention, so driver histories
    compare across backends) and ``changed`` the psum'd count of
    assignment flips — the device-resident convergence signal (no host
    sync of the full assignment).
    """
    data_axes = _axes(mesh, data_axes)
    xspec, rowspec, rep = clustering_specs(mesh, data_axes)

    def step(x, w, c, a):
        # 1. replicated center kNN graph (self-inclusive)
        cc = pairwise_sqdist(c, c)
        _, neighbors = jax.lax.top_k(-cc, kn)              # (k, kn)
        # 2. local restricted assignment (bound-free: every row)
        cand = neighbors[a]                                # (n_loc, kn)
        a_new, _dmin = chunked_candidate_argmin(x, c, cand, chunk=chunk)
        a_new = a_new.astype(jnp.int32)
        # 3. hierarchical mean update: local segment sums + psum
        sums = jax.ops.segment_sum(x * w[:, None], a_new, num_segments=k)
        counts = jax.ops.segment_sum(w, a_new, num_segments=k)
        changed = jnp.sum((a_new != a) & (w > 0))
        for ax in reversed(data_axes):                     # ICI first
            sums = jax.lax.psum(sums, ax)
            counts = jax.lax.psum(counts, ax)
            changed = jax.lax.psum(changed, ax)
        c_new = jnp.where(counts[:, None] > 0,
                          sums / jnp.maximum(counts, 1.0)[:, None], c)
        energy = jnp.sum(w * sqnorm(x - c_new[a_new]))
        for ax in reversed(data_axes):
            energy = jax.lax.psum(energy, ax)
        return c_new, a_new, energy, changed

    return shard_map(step, mesh=mesh,
                     in_specs=(xspec, rowspec, rep, rowspec),
                     out_specs=(rep, rowspec, rep, rep))


def make_distributed_lloyd_step(mesh, k: int, *, data_axes=None,
                                chunk: int = 2048):
    """Sharded full-assignment Lloyd step (baseline for the benchmarks):
    ``step(x, w, c) -> (c', a', energy)``, assignment via the shared
    chunked argmin helper."""
    data_axes = _axes(mesh, data_axes)
    xspec, rowspec, rep = clustering_specs(mesh, data_axes)

    def step(x, w, c):
        a, dmin = chunked_argmin_sqdist(x, c, chunk=chunk)
        a = a.astype(jnp.int32)
        sums = jax.ops.segment_sum(x * w[:, None], a, num_segments=k)
        counts = jax.ops.segment_sum(w, a, num_segments=k)
        energy = jnp.sum(w * dmin)
        for ax in reversed(data_axes):
            sums = jax.lax.psum(sums, ax)
            counts = jax.lax.psum(counts, ax)
            energy = jax.lax.psum(energy, ax)
        c_new = jnp.where(counts[:, None] > 0,
                          sums / jnp.maximum(counts, 1.0)[:, None], c)
        return c_new, a, energy

    return shard_map(step, mesh=mesh, in_specs=(xspec, rowspec, rep),
                     out_specs=(rep, rowspec, rep))


def make_distributed_assign(mesh, k: int, *, data_axes=None,
                            chunk: int = 2048):
    """Sharded full assignment (no update) — seeds k²-means so the
    distributed trajectory matches the single-device one exactly."""
    data_axes = _axes(mesh, data_axes)
    xspec, rowspec, rep = clustering_specs(mesh, data_axes)

    def assign(x, c):
        a, _ = chunked_argmin_sqdist(x, c, chunk=chunk)
        return a.astype(jnp.int32)

    return shard_map(assign, mesh=mesh, in_specs=(xspec, rep),
                     out_specs=rowspec)


# ---------------------------------------------------------------------------
# Shard-aware GDI seeding (DESIGN.md §7)
# ---------------------------------------------------------------------------


def make_distributed_gdi_seed(mesh, k: int, *, data_axes=None,
                              split_iters: int = 2, bn: int = 8,
                              interpret: bool = False,
                              rounds: int | None = None,
                              frontier: float = 0.125):
    """Per-shard-group frontier rounds: every shard runs a fixed trip
    count of greedy frontier rounds of the device GDI round step on its
    local rows toward ``k`` *local* leaves (``core.gdi.gdi_fixed_rounds``
    — its n/P-point sample of the data yields a full k-covering per
    shard), with a per-shard fold of the key. Returns
    ``seed(x, key) -> (leaf_ids, centers, weights)`` where ``leaf_ids``
    lives in the global leaf space (shard p owns slots [p*k, (p+1)*k)) and
    ``centers``/``weights`` gather to (P*k, ...) in the same slot order
    (weights = member counts, 0 for dead slots).
    """
    from .gdi import gdi_fixed_rounds

    data_axes = _axes(mesh, data_axes)
    xspec, rowspec, rep = clustering_specs(mesh, data_axes)

    def seed(x, key):
        # flat shard index over the data axes (major-to-minor, matching
        # the out-spec concatenation order)
        idx = jnp.zeros((), jnp.int32)
        for ax in data_axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        a, centers, _energies, sizes, nleaf = gdi_fixed_rounds(
            x, k, jax.random.fold_in(key, idx), rounds=rounds,
            split_iters=split_iters, bn=bn, impl="xla",
            interpret=interpret, frontier=frontier)
        live = jnp.arange(k, dtype=jnp.int32) < nleaf
        weights = jnp.where(live, sizes, 0).astype(x.dtype)
        return a + idx * k, centers, weights

    # per-shard (k, ...) leaf tables concatenate over the data axes in
    # the same major-to-minor order as the flat shard index above
    return shard_map(seed, mesh=mesh, in_specs=(xspec, rep),
                     out_specs=(rowspec, xspec, rowspec),
                     check_vma=False)


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def _gdi_merge(centers_g, weights_g, k: int, iters: int = 8):
    """Weighted Lloyd reduction of the P·k per-shard leaf centers down to
    k meta-centers (k-means||-style recluster step): replicated and tiny
    — O(P·k²·d) per iteration over center rows only, never points. Dead
    slots carry weight 0 and cannot move a meta-center. Returns
    (meta (k, d), leaf2meta (P*k,))."""
    # init from shard 0's leaves — a diverse k-covering of the data (the
    # k heaviest leaves globally would duplicate the same dense regions
    # across shards); dead slots (weight 0, shard stalled short of k
    # leaves) substitute the heaviest live leaves so no meta-center
    # starts on a zero-vector slot
    _, heavy = jax.lax.top_k(weights_g, k)
    c = jnp.where((weights_g[:k] > 0)[:, None], centers_g[:k],
                  centers_g[heavy])
    a = jnp.zeros((centers_g.shape[0],), jnp.int32)
    for _ in range(iters):
        a = jnp.argmin(pairwise_sqdist(centers_g, c), axis=1)
        sums = jax.ops.segment_sum(centers_g * weights_g[:, None], a,
                                   num_segments=k)
        cnts = jax.ops.segment_sum(weights_g, a, num_segments=k)
        c = jnp.where(cnts[:, None] > 0,
                      sums / jnp.maximum(cnts, 1.0)[:, None], c)
    return c, a.astype(jnp.int32)


def _sharded_gdi_seed(x, k: int, mesh, key, data_axes, counter, *,
                      split_iters: int = 2, interpret: bool = False,
                      frontier: float = 0.125, merge_iters: int = 8):
    """``init="gdi"`` seeding: greedy frontier rounds per shard-group,
    then a weighted center-level merge of the P·k local leaves down to k
    meta-centers; points inherit their leaf's meta-cluster, so no
    full-assignment pass over the points is needed. Returns
    (centers (k, d), a0 (n_pad,) sharded)."""
    from ..kernels.ops import grouped_capacity
    from .gdi import _charge_round, frontier_round_bound

    n_pad, d = x.shape
    nsh = _nshards(mesh, data_axes)
    n_loc = n_pad // nsh
    bn = 8            # xla impl: minimize grouped-layout padding
    # +2 slack rounds absorb failed splits on degenerate leaves; surplus
    # rounds no-op once a shard reaches k leaves
    rounds = frontier_round_bound(k, frontier) + 2
    seed_fn = jax.jit(make_distributed_gdi_seed(
        mesh, k, data_axes=data_axes, split_iters=split_iters, bn=bn,
        interpret=interpret, rounds=rounds, frontier=frontier))
    leaf_ids, centers_g, weights_g = seed_fn(x, key)
    r_loc = grouped_capacity(n_loc, k, bn) * bn
    for _ in range(rounds * nsh):          # every shard executes each round
        _charge_round(counter, r_loc, n_loc, d, split_iters)
    meta, leaf2meta = _gdi_merge(centers_g, weights_g, k=k,
                                 iters=merge_iters)
    counter.add_distances(merge_iters * centers_g.shape[0] * k)
    return meta, leaf2meta[leaf_ids]


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def fit_distributed_k2means(x_global, k: int, kn: int, mesh, key, *,
                            max_iters: int = 50, init_centers=None,
                            init: str = "random", backend: str = "pallas",
                            counter: OpCounter | None = None,
                            monitor_every: int = 1, chunk: int = 2048,
                            bn: int | None = None, bkn: int = 8,
                            interpret: bool | None = None,
                            data_axes=None, split_iters: int = 2,
                            residency: str | None = None,
                            regroup_every: int = 16,
                            move_cap: int | None = None,
                            guards: bool | None = None,
                            ckpt_dir: str | None = None,
                            ckpt_every: int = 0, resume: bool = False,
                            straggler_policy=None) -> KMeansResult:
    """Host-loop driver around the sharded engine step.

    Points (and the per-point bound state) are placed row-sharded over
    the mesh's data axes, centers replicated; uneven row counts are
    padded with duplicate rows carrying weight 0 (never perturbing
    centers, energy, or convergence). Trajectory-equivalent to the
    single-device ``fit_k2means`` with the same ``backend`` from the
    same init (seeded by assignment only, no update).

    backend: "pallas" (per-shard fused engine step through the tiled
    candidate kernel), "xla" (per-shard bounded engine step, portable),
    or "legacy" (the bound-free restricted baseline step). residency:
    "resident" keeps each shard's cluster-grouped layout device-resident
    and sparsely repaired (shard-local repairs, psum'd incremental center
    deltas, shard-uniform re-sort schedule — DESIGN.md §9.5), "rebuild"
    regroups per iteration; ``None`` resolves to "resident" for the
    pallas backend and "rebuild" otherwise. init:
    "random" samples k points; "kmeanspp" runs the replicated host-loop
    seeding; "gdi" runs the frontier round step per shard-group (the
    divisive assignment seeds the loop for free, skipping the
    full-assignment pass); "gdi_replicated" keeps the replicated device
    GDI baseline. Ignored when ``init_centers`` is given.

    Per-iteration host traffic is three replicated scalars (recompute
    count, changed count, energy), read every ``monitor_every``
    iterations; convergence is the psum'd changed count hitting zero.
    Counted ops charge per-shard recomputed points exactly like the
    single-device backends (k² + n_need·k_n + k distances + n additions
    per iteration).

    Self-healing hooks (DESIGN.md §11), all free when unused: an active
    ``ft.chaos.FaultInjector`` corrupts inputs/state at iteration
    boundaries; runtime guards (``guards``, default on iff an injector is
    installed) check invariants at the monitor-flush cadence and run the
    repair lattice (``ft.invariants.heal_fit``); ``ckpt_dir`` +
    ``ckpt_every`` take atomic mesh-independent mid-fit checkpoints and
    ``resume=True`` restarts from the newest one; a simulated host loss
    (``drop_host``) or a ``straggler_policy`` escalation triggers
    failover — snapshot (and checkpoint, when configured), replan the
    mesh over the survivors (``ft.plan_remesh``, the escalated straggler
    is cordoned), re-place, and resume with ``first=True`` (counted as a
    ``restore`` repair). Guards/heal need the engine step, so the
    ``legacy`` baseline backend gets chaos + failover but no guard.
    """
    from .. import ft
    from ..ft import chaos as chaos_mod
    from ..ft.invariants import heal_fit, make_guard

    counter = counter or OpCounter()
    if monitor_every < 1:
        raise ValueError(f"monitor_every must be >= 1, got {monitor_every}")
    if backend not in ("legacy", "xla", "pallas"):
        raise ValueError(f"unknown backend {backend!r}; expected "
                         "'pallas', 'xla' or 'legacy'")
    x_global = jnp.asarray(x_global)
    n, d = x_global.shape
    kn = min(kn, k)
    mesh = auto_axes(mesh)
    data_axes = _axes(mesh, data_axes)
    nsh = _nshards(mesh, data_axes)
    pad = (-n) % nsh
    n_pad = n + pad
    interpret = resolve_interpret(interpret)
    if residency is None:
        residency = "resident" if backend == "pallas" else "rebuild"
    resident = backend != "legacy" and residency == "resident"

    xspec, rowspec, rep = clustering_specs(mesh, data_axes)
    xsh = NamedSharding(mesh, xspec)
    rowsh = NamedSharding(mesh, rowspec)
    repsh = NamedSharding(mesh, rep)
    # duplicate-row padding: weight 0 in the iteration; duplicates are
    # harmless to the divisive seeding (they only re-weight split scans)
    xp = jnp.concatenate([x_global, x_global[:pad]]) if pad else x_global
    x = jax.device_put(xp, xsh)
    w = jax.device_put(
        jnp.concatenate([jnp.ones((n,), x.dtype),
                         jnp.zeros((pad,), x.dtype)]) if pad
        else jnp.ones((n,), x.dtype), rowsh)

    inj = chaos_mod.active()
    if guards is None:
        guards = inj is not None
    ckpt = ft.FitCheckpointer(ckpt_dir, every=ckpt_every,
                              extra={"n": n, "k": k, "d": d, "kn": kn}) \
        if ckpt_dir else None
    it0 = 0
    a0 = None
    b_host = None            # rebuild-residency Hamerly state {u, lo, nb}
    if resume and ckpt is not None:
        got = ckpt.latest(n, k, d)
        if got is not None:
            # checkpoints are mesh-independent {c, a, it}: restoring onto
            # this mesh just re-pads + re-places the point-order arrays
            it0, c_h, a_h, b_host = got
            init_centers = c_h
            a0 = np.concatenate([a_h, a_h[:pad]]) if pad else a_h
            counter.count_repair("restore")

    # --- initialization (skipped on resume) -------------------------------
    if init_centers is None:
        if init == "random":
            idx = jax.random.choice(key, n, shape=(k,), replace=False)
            init_centers = x_global[idx]
        elif init == "kmeanspp":
            from .kmeanspp import kmeanspp_init
            init_centers = kmeanspp_init(x_global, k, key, counter)
        elif init == "gdi":
            init_centers, a0 = _sharded_gdi_seed(
                x, k, mesh, key, data_axes, counter,
                split_iters=split_iters, interpret=interpret)
        elif init == "gdi_replicated":
            from .gdi import gdi_device_init
            init_centers, a_real = gdi_device_init(x_global, k, key,
                                                   counter=counter)
            a0 = jnp.concatenate([a_real, a_real[:pad]]) if pad else a_real
        else:
            raise ValueError(f"unknown init {init!r}; expected one of "
                             f"{_SHARDED_INITS}")
    c = jax.device_put(jnp.asarray(init_centers), repsh)
    if a0 is None:
        assign0 = jax.jit(make_distributed_assign(mesh, k,
                                                  data_axes=data_axes,
                                                  chunk=chunk))
        a0 = assign0(x, c)
        counter.add_distances(n * k)
    a0 = jax.device_put(jnp.asarray(a0).astype(jnp.int32), rowsh)

    # --- iteration: engine step under shard_map (or the legacy baseline) -
    # The epoch loop: one epoch per mesh incarnation. A failover
    # (simulated host loss / straggler cordon) snapshots the
    # mesh-independent (c, a), replans the survivor mesh, re-places, and
    # starts the next epoch from the completed iteration.
    from .k2means import _MonitorLoop
    mon = _MonitorLoop(counter, n=n, d=d, k=k, kn=kn, resident=resident)
    pol = straggler_policy

    cur_mesh, cur_axes = mesh, data_axes
    first_epoch = True
    c_host = a_host = None                 # host snapshot across epochs
    epoch_it0 = it0

    while True:
        if first_epoch:
            x_e, w_e, c_e, a0_e = x, w, c, a0
            nsh_e, n_pad_e = nsh, n_pad
            rowsh_e, repsh_e = rowsh, repsh
        else:
            nsh_e = _nshards(cur_mesh, cur_axes)
            pad_e = (-n) % nsh_e
            n_pad_e = n + pad_e
            xspec_e, rowspec_e, rep_e = clustering_specs(cur_mesh,
                                                         cur_axes)
            rowsh_e = NamedSharding(cur_mesh, rowspec_e)
            repsh_e = NamedSharding(cur_mesh, rep_e)
            xg = np.asarray(x_global)
            x_e = jax.device_put(
                jnp.asarray(np.concatenate([xg, xg[:pad_e]]) if pad_e
                            else xg), NamedSharding(cur_mesh, xspec_e))
            w_e = jax.device_put(
                jnp.concatenate([jnp.ones((n,), x_e.dtype),
                                 jnp.zeros((pad_e,), x_e.dtype)]) if pad_e
                else jnp.ones((n,), x_e.dtype), rowsh_e)
            c_e = jax.device_put(jnp.asarray(c_host), repsh_e)
            a_pad = np.concatenate([a_host, a_host[:pad_e]]) if pad_e \
                else a_host
            a0_e = jax.device_put(jnp.asarray(a_pad).astype(jnp.int32),
                                  rowsh_e)

        sb = None
        state = None
        a_cur = a0_e
        if backend == "legacy":
            legacy = jax.jit(make_distributed_k2means_step(
                cur_mesh, kn, k, data_axes=cur_axes, chunk=chunk))
        else:
            sb = K2Step(k=k, kn=kn, backend=backend, mesh=cur_mesh,
                        data_axes=cur_axes, chunk=chunk, bn=bn, bkn=bkn,
                        interpret=interpret, residency=residency,
                        regroup_every=regroup_every, move_cap=move_cap)
            step = sb.build(n_pad_e, d)
            if resident:
                state = sb.init_resident(x_e, w_e, c_e, a0_e)
            elif b_host is not None and \
                    b_host["nb"].shape == (k, kn):
                # restored/carried Hamerly state: resume the gated
                # trajectory bit-for-bit (pad rows copy the head rows'
                # bounds — they carry weight 0 and cannot affect real
                # rows, only their own recompute-count stats)
                pad_e_ = n_pad_e - n

                def _padrows(v):
                    return np.concatenate([v, v[:pad_e_]]) if pad_e_ \
                        else v
                state = K2State(
                    c_e, a0_e,
                    jax.device_put(jnp.asarray(_padrows(b_host["u"])),
                                   rowsh_e),
                    jax.device_put(jnp.asarray(_padrows(b_host["lo"])),
                                   rowsh_e),
                    jax.device_put(jnp.asarray(b_host["nb"]), repsh_e),
                    jnp.array(False))
            else:
                state = K2State(
                    c_e, a0_e,
                    jax.device_put(jnp.zeros((n_pad_e,), x_e.dtype),
                                   rowsh_e),
                    jax.device_put(jnp.zeros((n_pad_e,), x_e.dtype),
                                   rowsh_e),
                    jax.device_put(jnp.full((k, kn), -1, jnp.int32),
                                   repsh_e),
                    jnp.array(True))
        guard = make_guard(sb, n_pad_e) if (guards and sb is not None) \
            else None

        def _snapshot():
            """Mesh-independent (c, a, bounds) host snapshot of the live
            state; bounds is the point-order Hamerly state on the
            rebuild engines (None otherwise — legacy is stateless and
            already exact, resident rebuilds loose)."""
            bounds = None
            if backend == "legacy":
                c_s, a_s = c_e, a_cur
            elif resident:
                c_s = state.c
                a_s = sb.final_assignment(state, n_pad_e)
            else:
                c_s, a_s = state.c, state.a
                bounds = {
                    "u": np.array(jax.device_get(state.u),
                                  np.float32)[:n],
                    "lo": np.array(jax.device_get(state.lo),
                                   np.float32)[:n],
                    "nb": np.array(jax.device_get(state.prev_nb),
                                   np.int32)}
            return (np.array(jax.device_get(c_s), np.float32),
                    np.array(jax.device_get(a_s), np.int32)[:n], bounds)

        failover_drop = None
        for it in range(epoch_it0 + 1, max_iters + 1):
            t_it = time.perf_counter()
            if inj is not None:
                inj.check_preempt(it)
                inj.maybe_stall(it)
                x_e, w_e = inj.corrupt_inputs(it, x_e, w_e)
                if state is not None:
                    if resident:
                        state = inj.mirror_into_arena(state, x_e, nsh_e)
                    state = inj.corrupt_state(it, state, resident)
                drop = inj.host_drop_at(it)
                if drop is not None and cur_mesh.devices.size > 1:
                    failover_drop = drop
                    epoch_it0 = it - 1     # it never ran: replay it
                    break
            if backend == "legacy":
                c_e, a_cur, _energy_d, changed = legacy(x_e, w_e, c_e,
                                                        a_cur)
                # bound-free: every row recomputes, no grouped layout
                mon.pending.append((n, changed, _energy_d, 0, 0))
            else:
                state, stats = step(x_e, w_e, state)
                mon.pending.append(tuple(stats))
            if it % monitor_every == 0 or it == max_iters:
                mon.flush()
                healed = False
                if guard is not None:
                    vio = np.asarray(jax.device_get(guard(state)))
                    bad_energy = bool(mon.history) and \
                        not math.isfinite(mon.history[-1][1])
                    if vio.any() or bad_energy:
                        if bad_energy and not vio.any():
                            vio = np.array([0, 1, 0, 0])  # full heal
                        x_e, w_e, state = heal_fit(x_e, w_e, state, sb,
                                                   n_pad_e, counter, key,
                                                   vio)
                        mon.converged = False
                        healed = True
                if ckpt is not None and not healed and ckpt.due(it):
                    c_s, a_s, b_s = _snapshot()
                    ckpt.save(it, c_s, a_s, **(b_s or {}))
                if mon.converged:
                    break
            if pol is not None:
                verdict = pol.observe(time.perf_counter() - t_it)
                if verdict == "escalate" and cur_mesh.devices.size > 1:
                    # cordon the straggler (last device of the mesh in
                    # this host-local simulation) and fail over
                    failover_drop = cur_mesh.devices.size - 1
                    epoch_it0 = it         # it completed: keep it
                    break
        else:
            break                          # max_iters exhausted
        if failover_drop is None:
            break                          # converged

        # --- failover: snapshot -> replan -> next epoch -------------------
        c_host, a_host, b_host = _snapshot()
        if ckpt is not None and epoch_it0 > 0:
            # coordinated-eviction checkpoint at the last completed step
            ckpt.save(epoch_it0, c_host, a_host, **(b_host or {}))
        devices = [dev for i, dev in enumerate(cur_mesh.devices.flat)
                   if i != failover_drop % cur_mesh.devices.size]
        plan = ft.plan_remesh(len(devices), model_parallel=1)
        cur_mesh = Mesh(np.array(devices[:plan["chips"]]), ("data",))
        cur_axes = ("data",)
        counter.count_repair("restore")
        first_epoch = False

    if backend == "legacy":
        c_fin, a_final = c_e, a_cur
    elif resident:
        c_fin, a_final = state.c, sb.final_assignment(state, n_pad_e)
    else:
        c_fin, a_final = state.c, state.a
    if mon.history and math.isfinite(mon.history[-1][1]):
        energy = mon.history[-1][1]
    else:
        energy = float(jnp.sum(w_e * sqnorm(x_e - c_fin[a_final])))
    assignment = jnp.asarray(jax.device_get(a_final)[:n])
    placement = None
    if state is not None:
        names = ("xg", "pid", "ug", "lo_g") if resident else ("u", "lo")
        placement = {nm: {sh.device.id: sh.data.shape[0]
                          for sh in getattr(state, nm).addressable_shards}
                     for nm in names}
    return KMeansResult(c_fin, assignment, energy, mon.it_done,
                        counter.total, mon.history, placement=placement)
