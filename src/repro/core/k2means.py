"""k²-means — the paper's core contribution (Algorithm 1).

Per iteration:
  1. build the k_n-NN graph over the *centers* (O(k^2 d), self-inclusive);
  2. each point competes only among the k_n neighbours of its current center
     (O(n k_n d)), with triangle-inequality bounds to skip points whose
     assignment provably cannot change;
  3. standard mean update.

Bound machinery (TPU adaptation of Elkan-within-neighbourhood, DESIGN.md §3):
we maintain per point an upper bound ``u`` on the distance to its assigned
center and a scalar lower bound ``l`` on the distance to the *second* closest
candidate (Hamerly-style, O(n) memory instead of O(n k_n); the Pallas kernel
additionally exploits the block-level variant). After the update step with
center movements delta: u += delta[a], l -= max_{c in N(a)} delta[c]. A point
recomputes its k_n candidate distances only when ``u >= l`` or when the
candidate list of its cluster changed — both exact conditions, so k²-means
assignments here match the bound-free reference exactly. Counted vector ops
charge only recomputed points, reproducing the paper's empirical decay of the
O(n k_n d) term towards O(n d) at convergence.

Two backends execute the iteration (``fit_k2means(..., backend=...)``):

``"xla"``
    Pure-XLA chunked candidate gathers; the portable reference.

``"pallas"``
    One jitted device step chains center_knn -> cluster-grouped tiled
    candidate assignment (kernels.candidate_assign) -> center update ->
    Hamerly bound adjustment. Fed from the device-resident divisive init
    (core.gdi.gdi_device_init, DESIGN.md §4 — the default via
    ``api.fit(init="gdi", backend="pallas")``), the whole program
    init -> kNN graph -> grouped assignment -> update runs on device.
    Energy / op-count host reads are deferred to every ``monitor_every``
    iterations. Assignments match the
    xla backend exactly (both recompute under the same exact conditions;
    the pallas path recomputes whole bn-point blocks, which can only
    tighten bounds, never change an assignment). Caveat: the backends
    build the center k_n-NN graph with different distance implementations
    (Pallas MXU kernel vs XLA einsum), so exact parity is conditional on
    both ranking near-tied k_n-th neighbours identically — measure-zero
    on real data, but not guaranteed on adversarial ties (DESIGN.md §3.1).

Orthogonally, ``residency`` selects how the cluster-grouped layout is
maintained (DESIGN.md §9): ``"rebuild"`` reconstructs it from scratch every
iteration; ``"resident"`` (the pallas default) keeps it device-resident in
:class:`core.engine.ResidentState` and repairs only the rows whose
assignment changed, with an incremental delta center update and periodic
full re-sorts — killing the steady-state O(n log n + nd) layout traffic
the Hamerly bounds already proved unnecessary.

All paths are thin wrappers over the engine layer
(``core.engine.k2_iteration`` / ``k2_resident_iteration``, DESIGN.md §8) —
the same bodies the distributed shard_map step executes per shard
(``core.distributed.fit_distributed_k2means`` / ``api.fit(mesh=...)``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .distance import sqnorm
from .engine import K2State, K2Step, init_state, k2_iteration
from .lloyd import KMeansResult
from .opcount import OpCounter, charge_iteration


@functools.partial(jax.jit, static_argnames=("kn", "chunk"))
def k2means_step(x, c, a, u, lo, prev_neighbors, first, kn: int,
                 chunk: int = 2048):
    """One k²-means iteration (portable XLA backend; engine-layer body).

    Returns (c', a', u', lo', neighbors, stats) with stats the device
    tuple (n_need, changed, energy, moved, resorted).
    """
    w = jnp.ones((x.shape[0],), x.dtype)
    state = K2State(c, a, u, lo, prev_neighbors, first)
    st, stats = k2_iteration(x, w, state, kn=kn, backend="xla",
                             chunk=chunk)
    return st.c, st.a, st.u, st.lo, st.prev_nb, tuple(stats)


@functools.partial(jax.jit,
                   static_argnames=("kn", "bn", "bkn", "interpret"))
def k2means_pallas_step(x, c, a, u, lo, prev_neighbors, first, kn: int,
                        bn: int, bkn: int, interpret: bool):
    """One fused k²-means iteration on the Pallas fast path
    (rebuild residency — the grouped layout is reconstructed this call).

    Chains the whole iteration into one device step: center k_n-NN graph
    (Pallas center_sqdist + top_k), cluster grouping, the tiled
    candidate-assignment kernel with per-block Hamerly skip flags,
    segment-sum center update, and the bound adjustment for the next
    iteration (engine-layer body, ``core.engine.k2_iteration``). Returns
    (c', a', u', lo', neighbors, stats) with stats a device tuple
    (n_need, changed, energy, moved, resorted) — nothing here forces a
    host sync; the fit loop reads stats every ``monitor_every``
    iterations.
    """
    w = jnp.ones((x.shape[0],), x.dtype)
    state = K2State(c, a, u, lo, prev_neighbors, first)
    st, stats = k2_iteration(x, w, state, kn=kn, backend="pallas",
                             bn=bn, bkn=bkn, interpret=interpret)
    return st.c, st.a, st.u, st.lo, st.prev_nb, tuple(stats)


class _MonitorLoop:
    """Deferred-host-read driver shared by the device-step fit loops:
    stats stay on device and are flushed (op/byte charged + convergence
    checked) every ``monitor_every`` iterations (DESIGN.md §4.3). Each
    flush is the span ``kmeans.iterate.flush``, whose attributes sum the
    iterations it consumed."""

    def __init__(self, counter, *, n, d, k, kn, resident, precision="f32"):
        self.counter = counter
        self.args = dict(n=n, d=d, k=k, kn=kn, resident=resident,
                         precision=precision)
        self.pending = []
        self.history = []
        self.it_done = 0
        self.rows_recomputed = 0    # sum of n_need over consumed iterations
        self.converged = False

    def flush(self):
        with jax.profiler.TraceAnnotation("kmeans.iterate.flush") as span:
            got = jax.device_get(self.pending)
            self.counter.host_reads += 1
            used = 0
            sums = np.zeros(4, np.int64)    # n_need, changed, moved, resorted
            for stats in got:
                used += 1
                self.it_done += 1
                energy = charge_iteration(self.counter, stats=stats,
                                          **self.args)
                self.history.append((self.counter.snapshot(), float(energy)))
                sums += [int(stats[i]) for i in (0, 1, 3, 4)]
                if self.it_done > 1 and int(stats[1]) == 0:
                    self.converged = True   # fixed point: later pending
                    break                   # iterations are identical, drop
            self.rows_recomputed += int(sums[0])
            if span.is_enabled():
                span.set_metadata(
                    iterations=used, n_need=int(sums[0]),
                    changed=int(sums[1]), moved=int(sums[2]),
                    resorted=int(sums[3]))
            self.pending.clear()


def _fit_k2means_engine(x, centers, assignment, *, kn, max_iters, counter,
                        monitor_every, backend, residency, chunk, bn, bkn,
                        interpret, regroup_every, move_cap, guards=None,
                        ckpt_dir=None, ckpt_every=0, resume=False,
                        key=None, precision="f32"):
    """The one engine-layer fit loop behind every (backend, residency)
    combination, with the self-healing hooks of DESIGN.md §11: an active
    ``ft.chaos.FaultInjector`` corrupts inputs/state at iteration
    boundaries, runtime invariant guards (``ft.invariants.make_guard``)
    fire at the monitor-flush cadence and trigger the repair lattice
    (``heal_fit``), and ``ckpt_dir``/``ckpt_every``/``resume`` give the
    loop atomic mid-fit checkpoints + restart (``ft.FitCheckpointer``).
    Hooks cost nothing when unused: no injector + ``guards=False`` is
    exactly the old loop."""
    from .. import ft
    from ..ft import chaos as chaos_mod
    from ..ft.invariants import heal_fit, make_guard

    n, d = x.shape
    k = centers.shape[0]
    resident = residency == "resident"
    sb = K2Step(k=k, kn=kn, backend=backend, chunk=chunk, bn=bn, bkn=bkn,
                interpret=interpret, residency=residency,
                regroup_every=regroup_every, move_cap=move_cap,
                precision=precision)
    step = sb.build(n, d)
    w = jnp.ones((n,), x.dtype)
    inj = chaos_mod.active()
    if guards is None:
        guards = inj is not None
    if guards and precision == "int8":
        # the invariant guards / repair lattice read f32 arena rows; the
        # quantized arena is a scan-path optimisation, not a fault domain
        raise ValueError("precision='int8' does not support invariant "
                         "guards or fault injection; fit with the f32 "
                         "arena when guards/chaos are active")
    key = key if key is not None else jax.random.PRNGKey(0)
    ckpt = ft.FitCheckpointer(ckpt_dir, every=ckpt_every) \
        if ckpt_dir else None
    it0 = 0
    bnds = None
    if resume and ckpt is not None:
        got = ckpt.latest(n, k, d)
        if got is not None:
            it0, c_h, a_h, bnds = got
            centers = jnp.asarray(c_h)
            assignment = jnp.asarray(a_h)
            counter.count_repair("restore")
    with jax.profiler.TraceAnnotation("kmeans.iterate") as span:
        if resident:
            with jax.profiler.TraceAnnotation("kmeans.iterate.build"):
                state = sb.init_resident(x, w, centers, assignment)
        else:
            state = init_state(centers,
                               jnp.asarray(assignment).astype(jnp.int32),
                               kn)
            if bnds is not None and \
                    bnds["nb"].shape == state.prev_nb.shape:
                # restored Hamerly state: resume the gated trajectory
                # bit-for-bit rather than forcing a full recompute
                state = K2State(state.c, state.a, jnp.asarray(bnds["u"]),
                                jnp.asarray(bnds["lo"]),
                                jnp.asarray(bnds["nb"]), jnp.array(False))
        guard = make_guard(sb, n) if guards else None
        mon = _MonitorLoop(counter, n=n, d=d, k=k, kn=kn,
                           resident=resident, precision=precision)

        for it in range(it0 + 1, max_iters + 1):
            if inj is not None:
                x, w, state = chaos_mod.apply_fit_faults(inj, it, x, w,
                                                         state, resident)
            with jax.profiler.TraceAnnotation("kmeans.iterate.step") as st:
                state, stats = step(x, w, state)
                if st.is_enabled():
                    st.set_metadata(it=it)
            mon.pending.append(tuple(stats))
            if it % monitor_every == 0 or it == max_iters:
                mon.flush()
                healed = False
                if guard is not None:
                    vio = np.asarray(jax.device_get(guard(state)))
                    counter.host_reads += 1
                    bad_energy = bool(mon.history) and \
                        not math.isfinite(mon.history[-1][1])
                    if vio.any() or bad_energy:
                        if bad_energy and not vio.any():
                            vio = np.array([0, 1, 0, 0])   # full-heal route
                        x, w, state = heal_fit(x, w, state, sb, n, counter,
                                               key, vio)
                        mon.converged = False   # healed state re-iterates
                        healed = True
                if ckpt is not None and not healed and ckpt.due(it):
                    if resident:
                        ckpt.save(it, state.c,
                                  sb.final_assignment(state, n))
                    else:
                        ckpt.save(it, state.c, state.a, u=state.u,
                                  lo=state.lo, nb=state.prev_nb)
                    counter.host_reads += 1
                if mon.converged:
                    break

        if resident:
            with jax.profiler.TraceAnnotation("kmeans.iterate.final"):
                a = sb.final_assignment(state, n)
        else:
            a = state.a
        c = state.c
        if mon.history and math.isfinite(mon.history[-1][1]):
            energy = mon.history[-1][1]
        else:   # no iterations ran, or the last flush preceded a heal
            counter.add_distances(x.shape[0])   # n residual distances
            energy = float(jnp.sum(w * sqnorm(x - c[a])))
            counter.host_reads += 1
        if span.is_enabled():
            span.set_metadata(iterations=mon.it_done,
                              converged=mon.converged,
                              rows_recomputed=mon.rows_recomputed)
    return KMeansResult(c, a, energy, mon.it_done, counter.total,
                        mon.history)


def fit_k2means(x: jax.Array, centers: jax.Array, assignment: jax.Array, *,
                kn: int = 30, max_iters: int = 100,
                counter: OpCounter | None = None,
                chunk: int = 2048, backend: str = "xla",
                monitor_every: int = 1, bn: int | None = None,
                bkn: int = 8, interpret: bool | None = None,
                residency: str | None = None, regroup_every: int = 16,
                move_cap: int | None = None, guards: bool | None = None,
                ckpt_dir: str | None = None, ckpt_every: int = 0,
                resume: bool = False, key: jax.Array | None = None,
                precision: str = "f32") -> KMeansResult:
    """Run k²-means from an initialisation (centers + assignments).

    Pass ``assign_nearest(x, centers)`` (and charge it to the counter
    yourself, as ``api.fit`` does): a point only ever moves among the k_n
    nearest centers of its current one, so a starting assignment that is
    not Voronoi, such as GDI's leaf assignment, is largely never repaired
    (on GMM data at kn=32, GDI's leaves end 3% above Lloyd from the same
    centers at k=128 and 35% at k=1024).

    backend: "xla" (portable lax.map reference) or "pallas" (fused device
    step through the tiled candidate-assignment kernel; see module
    docstring). Both produce identical assignments. residency: "rebuild"
    (per-iteration grouped-layout reconstruction) or "resident" (the
    persistent, sparsely repaired layout of DESIGN.md §9 with incremental
    center updates; ``regroup_every``/``move_cap`` tune its re-sort
    period and move buffer); ``None`` resolves to "resident" on the
    pallas backend and "rebuild" on xla. monitor_every defers the device
    steps' energy/op-count host reads (and hence their convergence
    check) to every that-many iterations; bn/bkn pick the point-block
    and candidate-tile sizes (bn=None auto-selects from n/k within the
    VMEM budget); interpret=None runs the kernels in interpret mode
    off-TPU.

    Self-healing hooks (DESIGN.md §11): ``guards=True`` evaluates the
    runtime invariant guards at every monitor flush and self-heals via
    the repair lattice (``None``: on exactly when a
    ``ft.chaos.FaultInjector`` is active); ``ckpt_dir``/``ckpt_every``
    write atomic mid-fit checkpoints of (centers, assignment, it) and
    ``resume=True`` restarts from the newest complete one — bounds are
    rebuilt loose, so the resumed trajectory's final assignment is
    bit-identical to the uninterrupted run's on the rebuild engines;
    ``key`` seeds the split-repair rung.

    precision: "f32" (default) or "int8" — the quantized resident arena
    of DESIGN.md §13: the candidate scan reads int8 point rows and
    candidate slabs and exactly re-ranks the margin-surviving candidates
    in f32, so assignments match the f32 engine's bit-for-bit while scan
    traffic drops ~4x. Requires the resident residency (``residency=None``
    resolves to "resident" under int8) and is incompatible with
    ``guards``/fault injection.
    """
    counter = counter or OpCounter()
    n, d = x.shape
    k = centers.shape[0]
    kn = min(kn, k)
    if monitor_every < 1:
        raise ValueError(f"monitor_every must be >= 1, got {monitor_every}")
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown backend {backend!r}; "
                         "expected 'xla' or 'pallas'")
    if precision not in ("f32", "int8"):
        raise ValueError(f"unknown precision {precision!r}; "
                         "expected 'f32' or 'int8'")
    if residency is None:
        residency = "resident" if (backend == "pallas"
                                   or precision == "int8") else "rebuild"
    if residency not in ("rebuild", "resident"):
        raise ValueError(f"unknown residency {residency!r}; "
                         "expected 'rebuild' or 'resident'")
    return _fit_k2means_engine(
        x, centers, assignment, kn=kn, max_iters=max_iters,
        counter=counter, monitor_every=monitor_every, backend=backend,
        residency=residency, chunk=chunk, bn=bn, bkn=bkn,
        interpret=interpret, regroup_every=regroup_every,
        move_cap=move_cap, guards=guards, ckpt_dir=ckpt_dir,
        ckpt_every=ckpt_every, resume=resume, key=key,
        precision=precision)
