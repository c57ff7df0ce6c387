"""Runtime invariant guards + self-heal for the fit engines.

DESIGN.md §11. The §9.1 slot-ownership invariants (previously asserted
only by ``tests/test_resident_layout.check_layout``) become cheap
device-side *violation counters* evaluated at the drivers' monitor-flush
cadence, plus host-side repair orchestration when one fires.

Guard cost: everything checked is O(n + k·d + k·kn) — finiteness of
centers / running sums / bound lanes, arena index ranges, watermark
consistency, and a slot-ownership occupancy scatter. The O(n·d) point
rows are deliberately NOT scanned every check: non-finite rows poison
the segment-sums within one iteration, so the ``centers``/``sums``
counters (and the free NaN-energy signal the monitor already reads)
catch them at the same flush, and the healer then pays the one O(n·d)
host sweep. That keeps steady-state guard overhead inside the ≤2%
acceptance budget at monitor cadence.

Violation vector lanes (device int32, psum'd across shards on a mesh)::

    [0] centers   non-finite center entries
    [1] sums      non-finite / negative running sums or counts
    [2] bounds    non-finite Hamerly bound entries
    [3] arena     slot-ownership / watermark / index-range violations

The repair lattice (cheapest sufficient rung wins, every rung counted on
``OpCounter.repairs``):

``bound_reset``
    bounds lane only → zero the bound lanes and set ``first`` (the
    stale-zero safe loose state: iteration 1 semantics, a full exact
    recompute — recomputation can only tighten bounds, so this never
    changes any assignment).
``regroup``
    arena / sums / rows corrupted → recover the point-order assignment
    from the surviving slots (untrusted rows re-assigned exactly),
    quarantine non-finite inputs to weight 0, and rebuild the arena +
    exact sums from scratch (``K2Step.init_resident``).
``split``
    a non-finite center cannot be averaged back — quarantine it and
    re-seat it with one GDI Lemma-1 ``projective_split`` of the
    highest-energy donor cluster (rides on top of a regroup / reset).
``restore``
    counted by the drivers when they fall back to a checkpoint
    (preemption resume, host-loss failover) — nothing here reaches it.

Healing is host-side and rare; correctness leans on the same exactness
argument as everything else in this repo: the healed state re-enters the
loop with ``first=True``, the next iteration recomputes every live row
exactly, and from there the trajectory is indistinguishable from a fit
seeded at the healed (centers, assignment).
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from ..core.distance import chunked_argmin_sqdist, sqnorm
from ..core.engine import K2State, ResidentState, init_state

VIOLATION_LANES = ("centers", "sums", "bounds", "arena")
STREAM_LANES = ("stale", "occupancy", "floor")


# ---------------------------------------------------------------------------
# Device-side violation counters
# ---------------------------------------------------------------------------


def resident_violations(state: ResidentState, *, n: int,
                        owned: jax.Array | None = None) -> jax.Array:
    """(4,) int32 violation counters of a (local) resident state; ``n``
    is the local point count the arena must cover exactly once.

    ``owned`` ((n,) bool, optional) marks the ids expected to own a slot
    — the sliding-window case (DESIGN.md §14), where evicted ids must
    own *zero* slots (their slot became a hole) while live ids still own
    exactly one. Default: every id owns exactly one (the append-only
    contract)."""
    k = state.fill.shape[0]
    s_total = state.pid.shape[0]
    nbt = state.b2c.shape[0]
    bn = s_total // nbt
    i32 = jnp.int32

    centers = jnp.sum(~jnp.isfinite(state.c)).astype(i32)
    sums = (jnp.sum(~jnp.isfinite(state.sums))
            + jnp.sum(~jnp.isfinite(state.counts))
            + jnp.sum(state.counts < 0)).astype(i32)
    bounds = (jnp.sum(~jnp.isfinite(state.ug))
              + jnp.sum(~jnp.isfinite(state.lo_g))).astype(i32)

    # arena: index ranges
    arena = jnp.sum((state.b2c < -1) | (state.b2c >= k)).astype(i32)
    arena += jnp.sum((state.fill < 0) | (state.fill > bn)).astype(i32)
    arena += jnp.sum(state.pid >= n).astype(i32)
    # slot ownership: every local point owns exactly one slot. Under a
    # sliding window (`owned` = live mask) an evicted id legally owns 0
    # (its slot is a hole) or 1 (re-parked by a re-sort) — never more;
    # live ids still own exactly one.
    occ = jnp.zeros((n,), i32).at[jnp.clip(state.pid, 0, n - 1)] \
        .add((state.pid >= 0).astype(i32))
    if owned is None:
        arena += jnp.sum(occ != 1).astype(i32)
    else:
        arena += jnp.sum(jnp.where(owned, occ != 1, occ > 1)).astype(i32)
    # free blocks own nothing
    freeb = jnp.repeat(state.b2c < 0, bn)
    arena += jnp.sum(freeb & (state.pid >= 0)).astype(i32)
    # watermarks: the open block belongs to its cluster and its tail
    # (slots >= fill) is free; clusters without an open block have fill 0
    ob = state.openb
    has_open = ob >= 0
    obc = state.b2c[jnp.clip(ob, 0, nbt - 1)]
    arena += jnp.sum(jnp.where(has_open,
                               (obc != jnp.arange(k)) | (state.fill < 1),
                               state.fill != 0)).astype(i32)
    tail_rows = jnp.clip(ob, 0, nbt - 1)[:, None] * bn \
        + jnp.arange(bn)[None, :]                       # (k, bn)
    tail_pid = state.pid[jnp.clip(tail_rows, 0, s_total - 1)]
    in_tail = has_open[:, None] & (jnp.arange(bn)[None, :]
                                   >= state.fill[:, None])
    arena += jnp.sum(in_tail & (tail_pid >= 0)).astype(i32)
    return jnp.stack([centers, sums, bounds, arena])


@functools.partial(jax.jit, static_argnames=("window",))
def streaming_violations(state: ResidentState, e_pts, w_pts, epoch_now,
                         floor, *, window: int) -> jax.Array:
    """(3,) int32 streaming-invariant counters (DESIGN.md §14), the
    eviction-side extension of :func:`resident_violations`:

    ``stale``
        live arena slots whose stream epoch fell out of the window
        (older than the ``window`` newest epochs) — eviction missed them
    ``occupancy``
        live arena slots whose mirror row is dead (weight 0), plus the
        absolute difference between the live-slot and live-mirror-row
        counts — the hole population must exactly mirror the evicted
        rows
    ``floor``
        decayed per-center counts below the freeze floor
    """
    i32 = jnp.int32
    cap = e_pts.shape[0]
    live = (state.pid >= 0) & (state.wg > 0)
    idx = jnp.clip(state.pid, 0, max(cap - 1, 0))
    if window:
        eg = jnp.where(live, e_pts[idx], epoch_now)
        stale = jnp.sum(live & (eg < epoch_now - window + 1)).astype(i32)
    else:
        stale = jnp.zeros((), i32)
    mirror_live = jnp.where(live, w_pts[idx] > 0, True)
    occ = jnp.sum(~mirror_live).astype(i32)
    occ += jnp.abs(jnp.sum(live.astype(i32))
                   - jnp.sum((w_pts > 0).astype(i32)))
    under = jnp.sum(state.counts < floor - 1e-6 * (1.0 + floor))
    return jnp.stack([stale, occ, under.astype(i32)])


def k2_violations(state: K2State, *, n: int) -> jax.Array:
    """(4,) int32 violation counters of a (local) rebuild-residency
    state (no arena, no running sums — those lanes check assignment
    range / nothing)."""
    del n
    k = state.c.shape[0]
    i32 = jnp.int32
    centers = jnp.sum(~jnp.isfinite(state.c)).astype(i32)
    sums = jnp.sum((state.a < 0) | (state.a >= k)).astype(i32)
    bounds = (jnp.sum(~jnp.isfinite(state.u))
              + jnp.sum(~jnp.isfinite(state.lo))).astype(i32)
    return jnp.stack([centers, sums, bounds, jnp.zeros((), i32)])


def make_guard(sb, n: int):
    """Jitted ``guard(state) -> (4,)`` violation counters for a
    :class:`core.engine.K2Step` builder (placement-aware: on a mesh the
    per-shard counters are psum'd)."""
    resident = sb.residency == "resident"
    n_loc = n // sb.shards()
    local = functools.partial(
        resident_violations if resident else k2_violations, n=n_loc)
    if sb.mesh is None:
        return jax.jit(local)
    from jax import shard_map
    from ..launch.sharding import clustering_specs
    axes = sb.axes()
    _, rowspec, rep = clustering_specs(sb.mesh, axes)

    def body(state):
        v = local(state)
        for ax in reversed(axes):
            v = jax.lax.psum(v, ax)
        return v

    specs = sb._resident_specs() if resident else \
        K2State(rep, rowspec, rowspec, rowspec, rep, rep)
    return jax.jit(shard_map(body, mesh=sb.mesh, in_specs=(specs,),
                             out_specs=rep, check_vma=False))


# ---------------------------------------------------------------------------
# Host-side recovery primitives
# ---------------------------------------------------------------------------


def recover_assignment_np(pid, b2c, bn: int, n: int,
                          nsh: int = 1) -> np.ndarray:
    """Best-effort point-order assignment from a (possibly corrupted)
    arena, host-side. Slot arrays arrive as the global device_get
    concatenation of ``nsh`` shard-local arenas (local pids in
    ``[0, n/nsh)``). Rows with ambiguous ownership (claimed by zero or
    several slots) or an out-of-range cluster come back as -1 —
    *untrusted*, to be re-assigned exactly by the healer."""
    pid = np.asarray(pid).astype(np.int64)
    b2c = np.asarray(b2c).astype(np.int64)
    s_loc = pid.shape[0] // nsh
    nbt_loc = b2c.shape[0] // nsh
    n_loc = n // nsh
    a = np.full((n,), -1, np.int64)
    for s in range(nsh):
        pidl = pid[s * s_loc:(s + 1) * s_loc]
        b2cl = b2c[s * nbt_loc:(s + 1) * nbt_loc]
        a_slot = np.repeat(np.clip(b2cl, 0, None), bn)
        owned = (pidl >= 0) & (pidl < n_loc)
        occ = np.zeros((n_loc,), np.int64)
        np.add.at(occ, pidl[owned], 1)
        trust = occ[pidl[owned]] == 1
        gl = pidl[owned][trust] + s * n_loc
        a[gl] = a_slot[owned][trust]
    return a


def split_repair(x, w, a, c, bad: np.ndarray, key, counter=None):
    """Quarantine the ``bad`` (non-finite) centers and re-seat each with
    one GDI Lemma-1 split of the highest-energy healthy donor cluster
    (``core.gdi.projective_split``): donor keeps side A, the repaired
    center takes side B and its members. Degenerate fallback (no donor
    with ≥2 members): re-seat on a live data row. Returns (c, a); every
    split lands on ``counter.repairs['split']``."""
    from ..core.gdi import projective_split
    k = c.shape[0]
    c = jnp.where(jnp.isfinite(c), c, 0.0)
    bad_set = set(int(b) for b in bad)
    live = np.flatnonzero(np.asarray(w) > 0)
    for i, j in enumerate(sorted(bad_set)):
        d2 = sqnorm(x - c[a])
        if counter is not None:   # donor-energy scan: n residual distances
            counter.add_distances(x.shape[0])
        e = np.array(jax.device_get(jax.ops.segment_sum(
            jnp.asarray(w) * d2, a, num_segments=k)))
        cnt = np.array(jax.device_get(jax.ops.segment_sum(
            jnp.asarray(w), a, num_segments=k)))
        e[list(bad_set)] = -np.inf
        e[cnt < 2] = -np.inf
        donor = int(np.argmax(e))
        if not np.isfinite(e[donor]):
            seat = int(live[i % max(live.size, 1)]) if live.size else 0
            c = c.at[j].set(x[seat])
        else:
            mask = (a == donor) & (jnp.asarray(w) > 0)
            _ma, mb, ca, cb, _pa, _pb = projective_split(
                x, mask, jax.random.fold_in(key, i))
            c = c.at[donor].set(ca).at[j].set(cb)
            a = jnp.where(mb, j, a)
        bad_set.discard(j)
        if counter is not None:
            counter.count_repair("split")
    return c, a


# ---------------------------------------------------------------------------
# Drift guard: EWMA bands + center repair for the streaming model (§14)
# ---------------------------------------------------------------------------


class DriftGuard(typing.NamedTuple):
    """Per-center EWMA bands the streaming drift detector tracks: the
    effective (decayed) count and the within-cluster energy folded per
    ``partial_fit`` batch. ``it`` is the batches-observed clock that
    gates the warm-up period."""
    cnt_ewma: jax.Array   # (k,)
    en_ewma: jax.Array    # (k,)
    it: jax.Array         # () int32


def init_drift_guard(k: int) -> DriftGuard:
    return DriftGuard(cnt_ewma=jnp.zeros((k,), jnp.float32),
                      en_ewma=jnp.zeros((k,), jnp.float32),
                      it=jnp.zeros((), jnp.int32))


@jax.jit
def drift_guard_step(dg: DriftGuard, counts, energy, floor,
                     beta=0.2, dying_frac=0.05, warmup=8):
    """One drift-guard observation (jitted, runs every fold).

    ``counts`` are the decayed per-center counts after the fold,
    ``energy`` the batch's within-cluster energy per center
    (``Σ w·d²(x, c_a)``). A center is flagged *starved* when its decayed
    mass sits at the freeze floor (``counts <= 2·floor``, or exactly
    empty at floor 0) and *dying* when its count has both collapsed
    under its own EWMA band (``< 0.5·cnt_ewma``) and fallen under
    ``dying_frac`` of the mean center mass. Flags are suppressed for the
    first ``warmup`` observations while the bands settle. The energy
    EWMA is not a flag source — it ranks donors for
    :func:`repair_dying_centers` (split where the error concentrates).
    Returns ``(dg', flags (k,) bool)``."""
    b = jnp.float32(beta)
    first = dg.it == 0
    cnt2 = jnp.where(first, counts, (1.0 - b) * dg.cnt_ewma + b * counts)
    en2 = jnp.where(first, energy, (1.0 - b) * dg.en_ewma + b * energy)
    starved = counts <= 2.0 * floor + 1e-30
    dying = (counts < 0.5 * dg.cnt_ewma) \
        & (counts < dying_frac * jnp.mean(counts))
    flags = (starved | dying) & (dg.it >= warmup)
    return DriftGuard(cnt2, en2, dg.it + 1), flags


def repair_dying_centers(model, dying, *, counter=None, key=None,
                         max_repairs: int = 4) -> int:
    """Re-seat the worst drift-guard-flagged centers (DESIGN.md §14).

    Each repair is one GDI Lemma-1 ``projective_split`` of the
    highest-energy donor cluster (by the guard's energy EWMA — split
    where the error concentrates): the donor keeps side A, the victim
    (the flagged center with the smallest effective count) takes side B
    and its member rows. The touched centers get their decayed counts
    recomputed exactly from the mirrors (``w·decay^age``, clamped at the
    floor) with sums re-anchored to ``c·counts`` (the freeze
    convention). Up to ``max_repairs`` victims are re-seated per call
    (each donor is used at most once — its energy EWMA is stale after a
    split; the monitor cadence retries next refresh), then the arena is
    rebuilt by one full re-sort, counted on the same ``split`` repair
    rung as the fit-time healer. Returns the number of centers re-seated
    (0 when the model has no member arena or no donor has ≥ 2 live
    members)."""
    from ..core.gdi import projective_split
    from ..core.model import _arena_resort
    if not model.has_arena:
        return 0
    dying_idx = list(np.flatnonzero(np.asarray(jax.device_get(dying))))
    if not dying_idx:
        return 0
    st = model.state
    k = model.k
    if key is None:
        key = jax.random.PRNGKey(model.batches_seen)
    counts_h = np.asarray(jax.device_get(st.counts), dtype=np.float64)
    a_h = np.asarray(jax.device_get(model.a_pts)).astype(np.int64)
    w_h = np.asarray(jax.device_get(model.w_pts)).astype(np.float64)
    live = w_h > 0
    # exact decayed member mass: a row folded at epoch e carries
    # w·decay^(epoch_now − e) (mirror epoch clock)
    decay = model.stream_decay
    age = np.maximum(model.batches_seen - 1
                     - np.asarray(jax.device_get(model.e_pts)), 0)
    w_eff = np.where(live, w_h * np.power(decay, age), 0.0)
    en = np.asarray(jax.device_get(model._dg.en_ewma), np.float64).copy() \
        if model._dg is not None else counts_h.copy()
    en[np.asarray(dying_idx, np.int64)] = -np.inf
    c2, sums2, counts2 = st.c, st.sums, st.counts
    repaired = 0
    while dying_idx and repaired < max_repairs:
        member_cnt = np.bincount(a_h[live], minlength=k)
        en_now = en.copy()
        en_now[member_cnt < 2] = -np.inf
        donor = int(np.argmax(en_now))
        if not np.isfinite(en_now[donor]):
            break
        victim = int(min(dying_idx, key=lambda j: counts_h[j]))
        dying_idx.remove(victim)
        key, sub = jax.random.split(key)
        mask = jnp.asarray(live & (a_h == donor))
        _ma, mb, ca, cb, _pa, _pb = projective_split(
            model.x_pts, mask, sub)
        mb_h = np.asarray(jax.device_get(mb))
        a_h = np.where(mb_h, victim, a_h)
        en[donor] = -np.inf          # stale after the split: use once
        for j, cj in ((donor, ca), (victim, cb)):
            cnt_j = max(float(w_eff[(a_h == j) & live].sum()),
                        model.count_floor)
            counts2 = counts2.at[j].set(jnp.float32(cnt_j))
            sums2 = sums2.at[j].set(cj * jnp.float32(cnt_j))
        c2 = c2.at[donor].set(ca).at[victim].set(cb)
        repaired += 1
        if counter is not None:
            counter.count_repair("split")
    if not repaired:
        return 0
    model.a_pts = jnp.asarray(a_h.astype(np.int32))
    xg, pid, wg, b2c, fill, openb = _arena_resort(
        model.x_pts, model.a_pts, model.w_pts, k=k, bn=model.bn,
        nbt=st.b2c.shape[0])
    model.state = st._replace(c=c2, sums=sums2, counts=counts2, xg=xg,
                              pid=pid, wg=wg, b2c=b2c, fill=fill,
                              openb=openb)
    return repaired


# ---------------------------------------------------------------------------
# Heal orchestration (driver hook)
# ---------------------------------------------------------------------------


def heal_fit(x, w, state, sb, n: int, counter, key, vio):
    """Repair a fit loop's (x, w, state) after a guard fired.

    ``sb`` is the :class:`core.engine.K2Step` the driver built the step
    from (carries residency + placement, including the shardings needed
    to re-place the healed arrays on a mesh); ``vio`` the host (4,)
    violation counters. Chooses the cheapest sufficient rung of the
    repair lattice (module docstring) and returns the healed
    (x, w, state) — the healed state always carries ``first=True``, so
    the next iteration recomputes everything exactly.
    """
    resident = sb.residency == "resident"
    vio = np.asarray(vio)
    only_bounds = bool(vio[2]) and not (vio[0] or vio[1] or vio[3])
    if only_bounds:
        # cheapest rung: the stale-zero safe loose state
        if resident:
            zeros = jnp.zeros_like(state.ug)
            state = state._replace(ug=zeros, lo_g=zeros,
                                   first=jnp.array(True))
        else:
            zeros = jnp.zeros_like(state.u)
            state = state._replace(u=zeros, lo=zeros,
                                   first=jnp.array(True))
        counter.count_repair("bound_reset")
        return x, w, state

    k = state.c.shape[0]
    nsh = sb.shards()
    x_h = np.array(jax.device_get(x), dtype=np.float32)
    w_h = np.array(jax.device_get(w), dtype=np.float32)

    # 1. quarantine non-finite rows (weight 0, zeroed features)
    bad_rows = ~np.isfinite(x_h).all(axis=1)
    n_sanitized = int((bad_rows & (w_h > 0)).sum())
    if bad_rows.any():
        x_h[bad_rows] = 0.0
        w_h[bad_rows] = 0.0
    if n_sanitized:
        counter.count_sanitized_rows(n_sanitized)

    # 2. best-effort assignment recovery from the surviving state
    if resident:
        pid_h = np.asarray(jax.device_get(state.pid))
        b2c_h = np.asarray(jax.device_get(state.b2c))
        bn = pid_h.shape[0] // b2c_h.shape[0]
        a_h = recover_assignment_np(pid_h, b2c_h, bn, n, nsh)
    else:
        a_h = np.array(jax.device_get(state.a), dtype=np.int64)
    a_h[(a_h < 0) | (a_h >= k)] = -1
    untrusted = a_h < 0
    a_h[untrusted] = 0                    # placeholder until re-assigned

    # 3. quarantine + split-repair non-finite centers
    c_h = np.array(jax.device_get(state.c), dtype=np.float32)
    bad_centers = np.flatnonzero(~np.isfinite(c_h).all(axis=1))
    c_dev = jnp.asarray(np.where(np.isfinite(c_h), c_h, 0.0))
    x_dev = jnp.asarray(x_h)
    a_dev = jnp.asarray(a_h.astype(np.int32))
    if bad_centers.size:
        # untrusted rows must not anchor a split: weight them out of the
        # donor-energy scan (they are re-assigned exactly right after)
        w_trust = jnp.asarray(np.where(untrusted, 0.0, w_h))
        c_dev, a_dev = split_repair(x_dev, w_trust, a_dev, c_dev,
                                    bad_centers, key, counter)
        a_h = np.array(jax.device_get(a_dev), dtype=np.int64)

    # 4. exact re-assignment of untrusted live rows
    unc = np.flatnonzero(untrusted & (w_h > 0))
    if unc.size:
        au, _ = chunked_argmin_sqdist(jnp.asarray(x_h[unc]), c_dev)
        counter.add_distances(int(unc.size) * int(c_dev.shape[0]))
        a_h[unc] = np.asarray(jax.device_get(au))
    a_dev = jnp.asarray(a_h.astype(np.int32))

    # 5. rebuild the loop state from the healed primals
    if sb.mesh is not None:
        from jax.sharding import NamedSharding
        from ..launch.sharding import clustering_specs
        xspec, rowspec, rep = clustering_specs(sb.mesh, sb.axes())
        x_dev = jax.device_put(jnp.asarray(x_h), NamedSharding(sb.mesh,
                                                               xspec))
        w_dev = jax.device_put(jnp.asarray(w_h), NamedSharding(sb.mesh,
                                                               rowspec))
        a_dev = jax.device_put(a_dev, NamedSharding(sb.mesh, rowspec))
        c_dev = jax.device_put(c_dev, NamedSharding(sb.mesh, rep))
    else:
        x_dev = jnp.asarray(x_h)
        w_dev = jnp.asarray(w_h)
    if resident:
        state = sb.init_resident(x_dev, w_dev, c_dev, a_dev)
        counter.count_repair("regroup")
    else:
        state = init_state(c_dev, a_dev, min(sb.kn, k))
        if sb.mesh is not None:
            state = jax.device_put(state, jax.tree.map(
                lambda s: NamedSharding(sb.mesh, s),
                K2State(rep, rowspec, rowspec, rowspec, rep, rep)))
        counter.count_repair("bound_reset")
    return x_dev, w_dev, state


__all__ = ["VIOLATION_LANES", "STREAM_LANES", "resident_violations",
           "streaming_violations", "k2_violations", "make_guard",
           "recover_assignment_np", "split_repair", "DriftGuard",
           "init_drift_guard", "drift_guard_step", "repair_dying_centers",
           "heal_fit"]
