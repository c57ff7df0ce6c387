"""Engine layer: ONE k²-means iteration, any backend, any placement.

DESIGN.md §8. The paper's bounded iteration (center k_n-NN graph →
k_n-restricted assignment with Hamerly bounds → mean update → bound
adjustment) is written once here and built into an executable step by
:class:`K2Step`, parameterized on

``backend``
    ``"xla"`` — portable chunked candidate gathers
    (:func:`core.distance.chunked_candidate_top2`);
    ``"pallas"`` — the fused TPU fast path (cluster-grouped layout +
    bound-gated tiled candidate kernel).

``residency`` (DESIGN.md §9)
    ``"rebuild"`` — :func:`k2_iteration`: the grouped layout is rebuilt
    from scratch every iteration (full argsort + full gather/scatter);
    ``"resident"`` — :func:`k2_resident_iteration`: the grouped layout
    lives in :class:`ResidentState` and is *repaired* each iteration by
    moving only the rows whose assignment changed, with an incremental
    delta center update and a periodic full re-sort
    (``regroup_every`` / free-pool exhaustion / move-buffer overflow) to
    re-tighten packing and bound f32 drift.

``placement``
    single-device (``mesh=None``) or a jax mesh: the same body runs under
    ``shard_map`` with points and per-point state row-sharded over the
    flattened data axes, centers and the k_n-NN graph replicated (O(k²d)
    is tiny next to O(n·k_n·d / P) per shard), and reductions (mean
    update / resident deltas / step statistics) by a hierarchical psum
    (innermost data axis first ⇒ ICI before DCN). Resident-layout
    repairs are shard-local — rows never migrate between shards.

The step carries a per-point weight vector ``w`` (1 = real row, 0 =
padding) so uneven shards (n not divisible by the device count) pad rows
without perturbing centers, energy, or convergence counts. Step
statistics — recompute count, changed-assignment count, post-update
energy, layout rows moved, re-sort count — are *device* scalars: drivers
read them back every ``monitor_every`` iterations and never transfer a
full assignment between iterations (the psum'd ``changed`` count is the
convergence signal, DESIGN.md §4.3 / §7).

Per-shard recomputation is block-granular on the pallas backend, which
can only tighten bounds (recomputation is exact — DESIGN.md §3.1), so
every (backend, residency, placement) combination produces identical
assignments from the same init, up to f32 reduction-order effects on
adversarially tied candidates (the resident incremental center update
adds its own bounded reduction-order drift, recomputed away at every
re-sort — DESIGN.md §9.4).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import typing

import jax
import jax.numpy as jnp
from jax import shard_map

from ..launch.mesh import dp_axes
from ..launch.sharding import clustering_specs
from .distance import chunked_candidate_top2, pairwise_sqdist, sqnorm


class K2State(typing.NamedTuple):
    """Bound-carried loop state of the rebuild iteration (DESIGN.md §3.1/§8).

    On a mesh placement ``a``/``u``/``lo`` are row-sharded with the
    points; ``c``/``prev_nb``/``first`` are replicated.
    """
    c: jax.Array        # (k, d) centers
    a: jax.Array        # (n,) assignment
    u: jax.Array        # (n,) upper bound on the assigned-center distance
    lo: jax.Array       # (n,) lower bound on the second-closest candidate
    prev_nb: jax.Array  # (k, kn) previous neighbor lists (-1 = invalid)
    first: jax.Array    # () bool: force a full recompute (iteration 1)


class ResidentState(typing.NamedTuple):
    """Loop state of the resident-layout iteration (DESIGN.md §9).

    The cluster-grouped layout is part of the state: ``xg`` is the
    grouped copy of the points, ``pid`` maps slots back to point ids
    (-1 = free slot), ``b2c`` maps blocks to their owning cluster
    (-1 = free block) and ``fill``/``openb`` are the per-cluster append
    watermarks sparse repairs allocate from. A slot's assignment is its
    block's cluster — there is no per-point ``a`` array. On a mesh the
    slot/block/watermark arrays are row-sharded (each shard owns its own
    layout arena over its local rows); ``c``/``prev_nb``/``sums``/
    ``counts``/``it``/``first`` are replicated.
    """
    c: jax.Array        # (k, d) centers
    prev_nb: jax.Array  # (k, kn) previous neighbor lists (-1 = invalid)
    sums: jax.Array     # (k, d) resident weighted member sums (global)
    counts: jax.Array   # (k,) resident weighted member counts (global)
    it: jax.Array       # () int32 completed iterations (re-sort schedule)
    first: jax.Array    # () bool: force a full recompute (iteration 1)
    xg: jax.Array       # (S, d) grouped point rows (S = nb_total * bn)
    pid: jax.Array      # (S,) point id per slot, -1 = free slot / hole
    ug: jax.Array       # (S,) upper bound per slot
    lo_g: jax.Array     # (S,) second-closest lower bound per slot
    wg: jax.Array       # (S,) weight per slot (0 = free slot / padding row)
    b2c: jax.Array      # (nb_total,) block -> cluster, -1 = free block
    fill: jax.Array     # (k,) open-block append watermark, in [0, bn]
    openb: jax.Array    # (k,) open (append) block per cluster, -1 = none
    # quantized arena (DESIGN.md §13, precision="int8"): ``xg`` holds int8
    # rows and ``xsc`` their per-slot scales; None on the f32 arena (an
    # empty pytree node, so f32 states keep their leaf count and existing
    # checkpoints/specs are untouched)
    xsc: typing.Any = None   # (S,) f32 per-slot scales | None (f32 arena)


class StepStats(typing.NamedTuple):
    """Replicated device scalars; host-read every ``monitor_every``.

    ``moved`` counts the rows that paid layout gather/scatter traffic
    this iteration (the whole layout for rebuild engines and resident
    re-sorts, only the changed rows for sparse repairs; 0 for the
    ungrouped xla backend) and ``resorted`` the number of shards that
    re-sorted — together they drive the host-side memory-traffic
    accounting (``core.opcount.charge_iteration``)."""
    n_need: jax.Array   # () points meeting the exact recompute condition
    changed: jax.Array  # () assignment changes across the iteration
    energy: jax.Array   # () clustering energy after the update step
    moved: jax.Array    # () rows moved through the layout this iteration
    resorted: jax.Array  # () shards that fully re-sorted this iteration
    # int8 engine only: f32 distances actually computed by the exact
    # re-rank (survivors + full-list fallbacks); 0 on the f32 paths —
    # opcount.charge_iteration reads it for the dtype-aware distance lane
    reranked: typing.Any = 0  # () re-ranked exact f32 distances


def init_state(centers: jax.Array, assignment: jax.Array,
               kn: int) -> K2State:
    """Stale-zero bounds (``first`` forces a full recompute on iteration
    1) and an all-invalid neighbor graph."""
    n = assignment.shape[0]
    k = centers.shape[0]
    dtype = centers.dtype
    return K2State(centers, assignment.astype(jnp.int32),
                   jnp.zeros((n,), dtype), jnp.zeros((n,), dtype),
                   jnp.full((k, kn), -1, jnp.int32), jnp.array(True))


def center_knn_graph(c: jax.Array, kn: int, backend: str = "xla",
                     interpret: bool = False) -> jax.Array:
    """Replicated k_n-NN graph over centers (self-inclusive, (k, kn)).

    Shared by the fit-time iteration bodies below and the query-time
    subsystem (:mod:`core.model`, DESIGN.md §10), so both sides route
    through identical neighborhoods."""
    if backend == "pallas":
        from ..kernels.center_knn import center_sqdist
        cc_sq = center_sqdist(c, interpret=interpret)
    else:
        cc_sq = pairwise_sqdist(c, c)
    _, neighbors = jax.lax.top_k(-cc_sq, kn)                # (k, kn)
    return neighbors.astype(jnp.int32)


_center_knn = center_knn_graph


def k2_iteration(x: jax.Array, w: jax.Array, state: K2State, *, kn: int,
                 backend: str = "xla", chunk: int = 2048, bn: int = 128,
                 bkn: int = 8, interpret: bool = False,
                 psum_axes: tuple = ()) -> tuple[K2State, StepStats]:
    """The rebuild-residency iteration body (pure; trace-time parameters
    only): the pallas backend reconstructs the cluster-grouped layout
    from scratch every call (DESIGN.md §3.3; the resident alternative is
    :func:`k2_resident_iteration`, §9).

    With ``psum_axes=()`` this is the single-device step; under
    ``shard_map`` it is the per-shard program and ``psum_axes`` names the
    data axes of the hierarchical reduction (reduced innermost-last ⇒
    ICI before DCN).
    """
    c, a, u, lo, prev_nb, first = state
    k = c.shape[0]
    wpos = w > 0

    # --- 1. k_n-NN graph over centers; replicated on every shard --------
    neighbors = _center_knn(c, kn, backend, interpret)
    list_changed = jnp.any(neighbors != prev_nb, axis=1)   # (k,)

    # --- 2. bounded assignment over candidate neighbourhoods (local rows;
    # padding rows never recompute) --------------------------------------
    need = ((u >= lo) | list_changed[a] | first) & wpos
    if backend == "pallas":
        from ..kernels.ops import k2_bounded_assign
        a_new, u_new, lo_new = k2_bounded_assign(
            x, c, neighbors, a, u, lo, need, bn=bn, bkn=bkn,
            interpret=interpret)
    else:
        cand = neighbors[a]                              # (n, kn)
        a_cmp, d1, d2 = chunked_candidate_top2(x, c, cand, chunk=chunk)
        a_new = jnp.where(need, a_cmp, a)
        u_new = jnp.where(need, d1, u)
        lo_new = jnp.where(need, d2, lo)

    # --- 3. weighted mean update: local segment sums + hierarchical psum -
    sums = jax.ops.segment_sum(x * w[:, None], a_new, num_segments=k)
    counts = jax.ops.segment_sum(w, a_new, num_segments=k)
    for ax in reversed(psum_axes):
        sums = jax.lax.psum(sums, ax)
        counts = jax.lax.psum(counts, ax)
    c_next = jnp.where(counts[:, None] > 0,
                       sums / jnp.maximum(counts, 1.0)[:, None], c)

    # --- 4. Hamerly bound adjustment for the next iteration --------------
    delta = jnp.sqrt(jnp.maximum(sqnorm(c_next - c), 0.0))   # (k,) movement
    delta_nb = jnp.max(delta[neighbors], axis=1)             # per-nbhood
    u_adj = u_new + delta[a_new]
    lo_adj = lo_new - delta_nb[a_new]

    # --- 5. device-resident step statistics ------------------------------
    n_need = jnp.sum(need)
    changed = jnp.sum((a_new != a) & wpos)
    energy = jnp.sum(w * sqnorm(x - c_next[a_new]))
    # the pallas backend re-sorts + regathers the whole local layout every
    # iteration; the ungrouped xla backend pays no layout traffic at all
    full_layout = backend == "pallas"
    moved = jnp.array(x.shape[0] if full_layout else 0, jnp.int32)
    resorted = jnp.array(1 if full_layout else 0, jnp.int32)
    for ax in reversed(psum_axes):
        n_need = jax.lax.psum(n_need, ax)
        changed = jax.lax.psum(changed, ax)
        energy = jax.lax.psum(energy, ax)
        moved = jax.lax.psum(moved, ax)
        resorted = jax.lax.psum(resorted, ax)

    next_state = K2State(c_next, a_new, u_adj, lo_adj, neighbors,
                         jnp.zeros((), bool))
    return next_state, StepStats(n_need, changed, energy, moved, resorted,
                                 jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Resident-layout iteration (DESIGN.md §9)
# ---------------------------------------------------------------------------


def init_resident_state(x: jax.Array, w: jax.Array, centers: jax.Array,
                        assignment: jax.Array, *, kn: int, bn: int,
                        nb_total: int, precision: str = "f32",
                        psum_axes: tuple = ()) -> ResidentState:
    """Build the resident layout once from an initial assignment: one full
    grouping pass + one full segment-sum (both paid per *init*, not per
    iteration). Stale-zero bounds with ``first`` forcing a full recompute
    on iteration 1, exactly like :func:`init_state`. Under
    ``precision="int8"`` the arena rows are symmetrically quantized
    (DESIGN.md §13) and carry per-slot scales in ``xsc``; ``x`` stays the
    f32 master copy the update/delta path reads."""
    k = centers.shape[0]
    a = assignment.astype(jnp.int32)
    from ..kernels.ops import resident_regroup
    perm, b2c, fill, openb = resident_regroup(a, k, bn, nb_total)
    valid = perm >= 0
    sp = jnp.maximum(perm, 0)
    xg = jnp.where(valid[:, None], x[sp], 0.0).astype(x.dtype)
    wg = jnp.where(valid, w[sp], 0.0).astype(x.dtype)
    zeros = jnp.zeros((perm.shape[0],), centers.dtype)
    sums = jax.ops.segment_sum(x * w[:, None], a, num_segments=k)
    counts = jax.ops.segment_sum(w, a, num_segments=k)
    for ax in reversed(psum_axes):
        sums = jax.lax.psum(sums, ax)
        counts = jax.lax.psum(counts, ax)
    xsc = None
    if precision == "int8":
        from ..kernels import quant
        xg, xsc = quant.quantize_rows(xg)
    return ResidentState(centers, jnp.full((k, kn), -1, jnp.int32), sums,
                         counts, jnp.zeros((), jnp.int32), jnp.array(True),
                         xg, perm, zeros, zeros, wg, b2c, fill, openb,
                         xsc=xsc)


def resident_assignment(state: ResidentState, n: int) -> jax.Array:
    """Point-order assignment from the resident layout: one scatter
    through ``pid`` (local rows under shard_map)."""
    from ..kernels.ops import scatter_from_grouped
    bn = state.pid.shape[0] // state.b2c.shape[0]
    a_slot = jnp.repeat(jnp.maximum(state.b2c, 0), bn).astype(jnp.int32)
    return scatter_from_grouped(state.pid, a_slot,
                                jnp.zeros((n,), jnp.int32))


@functools.partial(jax.jit, static_argnames=())
def resident_evict(state: ResidentState, eg: jax.Array, cutoff: jax.Array,
                   epoch_now: jax.Array, decay: jax.Array,
                   floor: jax.Array, masters: jax.Array | None = None
                   ) -> tuple[ResidentState, jax.Array, jax.Array]:
    """Sliding-window eviction on the resident arena (DESIGN.md §14).

    Retires every live slot whose stream epoch ``eg`` (S,) predates
    ``cutoff`` through :func:`kernels.ops.plan_layout_evict` (the slots
    become holes below the watermark, reclaimed only at the next full
    re-sort) and subtracts the evicted rows from the center sums/counts
    as an *incremental delta* — the streaming twin of the sparse-repair
    delta update, so the surviving statistics match a from-scratch
    fold of the window (bit-exact at ``decay == 1`` on exactly
    representable data; see the §14 decay algebra otherwise).

    Decay algebra: a row folded at epoch ``e`` has been forgotten down
    to weight ``w · decay^(epoch_now − e)`` by the per-epoch multiplier,
    so the subtraction uses that *decayed* weight — subtracting the raw
    weight would over-evict everything older than one epoch. ``floor``
    is the same numerically-safe count floor as the fold side: centers
    whose surviving mass dips under it are frozen at the floor with
    their sums re-anchored (``sums = c · floor``), never driven toward
    0/0. ``masters`` supplies the f32 master rows read by the delta —
    mandatory on an int8 arena (DESIGN.md §13: deltas never re-read
    quantized rows), optional on f32 where ``xg`` is exact. Returns
    ``(state', evict_mask, n_evicted)``.
    """
    from ..kernels.ops import plan_layout_evict
    k = state.c.shape[0]
    bn = state.pid.shape[0] // state.b2c.shape[0]
    evict, pid2, wg2, n_ev = plan_layout_evict(state.pid, state.wg, eg,
                                               cutoff)
    if masters is not None:
        rows = masters[jnp.clip(state.pid, 0, masters.shape[0] - 1)]
        rows = rows.astype(jnp.float32)
    elif state.xsc is not None:
        rows = state.xg.astype(jnp.float32) * state.xsc[:, None]
    else:
        rows = state.xg.astype(jnp.float32)
    cl = jnp.repeat(jnp.maximum(state.b2c, 0), bn)
    seg = jnp.where(evict, cl, k)
    age = jnp.maximum(epoch_now - eg, 0).astype(jnp.float32)
    w_eff = jnp.where(evict, state.wg * jnp.power(decay, age), 0.0)
    d_sums = jax.ops.segment_sum(rows * w_eff[:, None], seg,
                                 num_segments=k + 1)[:k]
    d_counts = jax.ops.segment_sum(w_eff, seg, num_segments=k + 1)[:k]
    sums2 = state.sums - d_sums
    counts2 = jnp.maximum(state.counts - d_counts, 0.0)
    frozen = counts2 < floor
    counts2 = jnp.where(frozen, jnp.maximum(floor, counts2), counts2)
    sums2 = jnp.where(frozen[:, None], state.c * counts2[:, None], sums2)
    c2 = jnp.where(counts2[:, None] > 0,
                   sums2 / jnp.maximum(counts2, 1e-12)[:, None], state.c)
    state2 = state._replace(c=c2, sums=sums2, counts=counts2, pid=pid2,
                            wg=wg2)
    return state2, evict, n_ev


def k2_resident_iteration(x: jax.Array, w: jax.Array, state: ResidentState,
                          *, kn: int, backend: str = "pallas",
                          chunk: int = 2048, bn: int = 128, bkn: int = 8,
                          interpret: bool = False, regroup_every: int = 16,
                          move_cap: int = 1024, precision: str = "f32",
                          rerank_r: int = 8,
                          psum_axes: tuple = ()
                          ) -> tuple[ResidentState, StepStats]:
    """One iteration over the resident grouped layout (DESIGN.md §9).

    Everything runs in slot space: the bounded assignment reads the
    resident ``xg`` directly (no per-iteration gather), the bound refresh
    and step statistics stay grouped (no full-array scatters back to
    point order), the center update is an incremental delta over the
    changed rows (``sums += Σ x_i·(onehot(new) − onehot(old))``), and the
    layout is repaired by moving only the changed rows (at most
    ``move_cap``) into their destination clusters' free slots. A full
    re-sort + exact recompute runs every ``regroup_every`` iterations, on
    move-buffer overflow, or when the free-block pool would be exhausted
    — bounding both packing decay and incremental-f32 drift. ``x``/``w``
    are the original point-order arrays (only read by re-sorts and the
    iteration-1 build). The repair changes where rows live, never what is
    computed, so assignments match the rebuild engine from the same init
    (§9.4 for the drift caveat).

    The point-block size is a property of the carried layout, so ``bn``
    is re-derived from the state's shapes — a caller-passed ``bn`` that
    disagrees with the arena (e.g. a step built without ``d``) cannot
    corrupt the iteration.

    ``precision="int8"`` (DESIGN.md §13) scans a quantized arena: ``xg``
    holds int8 rows with per-slot scales in ``xsc``; the bounded
    assignment runs the int8 survivor scan + exact f32 re-rank against
    the master rows (``x`` gathered by ``pid``), the delta/full center
    updates and the energy statistic read the f32 masters, and re-sorts
    re-quantize the regrouped rows — so bounds stay exact-or-conservative
    and assignments match the f32 engine. ``rerank_r`` is the static
    survivor width of the re-rank (overflowing rows fall back to an
    exact full-candidate pass).
    """
    k = state.c.shape[0]
    n = x.shape[0]
    s_total = state.pid.shape[0]
    nbt = state.b2c.shape[0]
    bn = s_total // nbt
    c = state.c
    wpos = state.wg > 0
    int8 = precision == "int8"

    # --- 1. k_n-NN graph over centers; replicated on every shard --------
    neighbors = _center_knn(c, kn, backend, interpret)
    list_changed = jnp.any(neighbors != state.prev_nb, axis=1)   # (k,)

    # --- 2. bounded assignment straight over the resident layout --------
    a_slot = jnp.repeat(jnp.maximum(state.b2c, 0), bn).astype(jnp.int32)
    need = ((state.ug >= state.lo_g) | list_changed[a_slot]
            | state.first) & wpos
    reranked = jnp.zeros((), jnp.int32)
    if int8:
        from ..kernels import quant
        from ..kernels.candidate_assign import pad_candidates
        from ..kernels.ops import quantized_scan_rerank
        sp1 = jnp.maximum(state.pid, 0)
        xf = jnp.where((state.pid >= 0)[:, None], x[sp1], 0.0)
        cq = quant.center_quant(c)
        cidx = pad_candidates(neighbors, bkn)
        skip = (~jnp.any(need.reshape(nbt, bn), axis=1)).astype(jnp.int32)
        rowsel = jnp.maximum(state.b2c, 0)
        a_g, d1_sq, d2_sq, nsv, fb = quantized_scan_rerank(
            xf, state.xg, state.xsc, c, cq, cidx, rowsel, skip, a_slot,
            state.ug * state.ug, state.lo_g * state.lo_g,
            bn=bn, bkn=bkn, r=rerank_r, backend=backend,
            interpret=interpret)
        fresh = jnp.repeat(skip == 0, bn)
        u_new = jnp.where(fresh, jnp.sqrt(d1_sq), state.ug)
        lo_new = jnp.where(fresh, jnp.sqrt(d2_sq), state.lo_g)
        a_new = jnp.where(wpos, a_g, a_slot)
        # counted f32 distances of the exact stage: min(n_surv, r) per
        # re-ranked row, the full candidate list on fallback rows
        reranked = jnp.sum(jnp.where(
            fb, cidx.shape[1],
            jnp.minimum(nsv, rerank_r))).astype(jnp.int32)
    elif backend == "pallas":
        from ..kernels.candidate_assign import (candidate_assign_tiled,
                                                candidate_tables,
                                                pad_candidates)
        skip = (~jnp.any(need.reshape(nbt, bn), axis=1)).astype(jnp.int32)
        cidx = pad_candidates(neighbors, bkn)
        ctab, csqtab = candidate_tables(c, cidx)
        rowsel = jnp.maximum(state.b2c, 0)
        a_g, d1_sq, d2_sq = candidate_assign_tiled(
            state.xg, ctab, csqtab, cidx, rowsel, skip, a_slot,
            state.ug * state.ug, state.lo_g * state.lo_g,
            bn=bn, bkn=bkn, interpret=interpret)
        fresh = jnp.repeat(skip == 0, bn)
        u_new = jnp.where(fresh, jnp.sqrt(d1_sq), state.ug)
        lo_new = jnp.where(fresh, jnp.sqrt(d2_sq), state.lo_g)
        # free slots / padding rows are frozen: their lanes compute
        # garbage when their block is recomputed, and they must never
        # enter the move buffer or flip a block's ownership
        a_new = jnp.where(wpos, a_g, a_slot)
    else:
        # portable reference: computes every arena slot (free slots and
        # holes included, ~n + k*bn rows) — the xla path has no per-block
        # skip gating, so residency buys it layout-traffic savings only,
        # not compute; the pallas backend is the fast path
        cand = neighbors[a_slot]                         # (S, kn)
        a_cmp, d1, d2 = chunked_candidate_top2(state.xg, c, cand,
                                               chunk=chunk)
        a_new = jnp.where(need, a_cmp, a_slot)
        u_new = jnp.where(need, d1, state.ug)
        lo_new = jnp.where(need, d2, state.lo_g)

    # --- 3. compact the changed rows into the move buffer ----------------
    mask_mv = wpos & (a_new != a_slot)
    n_changed = jnp.sum(mask_mv)
    overflow = n_changed > move_cap
    mv = jnp.nonzero(mask_mv, size=move_cap, fill_value=s_total)[0]
    active = mv < s_total
    mvs = jnp.minimum(mv, s_total - 1)
    src_c = a_slot[mvs]
    dst_c = a_new[mvs]

    # --- 4. incremental center-update deltas over the moved rows ---------
    seg_dst = jnp.where(active, dst_c, k)
    seg_src = jnp.where(active, src_c, k)
    w_mv = jnp.where(active, state.wg[mvs], 0.0)
    # int8 arena: deltas read the f32 masters, never dequantized rows —
    # centers carry no quantization error
    rows = (xf[mvs] if int8 else state.xg[mvs]) * w_mv[:, None]
    delta_sums = (jax.ops.segment_sum(rows, seg_dst, num_segments=k + 1)
                  - jax.ops.segment_sum(rows, seg_src,
                                        num_segments=k + 1))[:k]
    delta_counts = (jax.ops.segment_sum(w_mv, seg_dst, num_segments=k + 1)
                    - jax.ops.segment_sum(w_mv, seg_src,
                                          num_segments=k + 1))[:k]

    # --- 5. re-sort triggers ---------------------------------------------
    # time trigger and overflow are shard-uniform (it is replicated, the
    # overflow flag is psum'd) so the *sums* recompute decision agrees on
    # every shard; the free-pool check is shard-local — a shard may
    # re-sort its own arena while others repair
    time_trigger = (state.it + 1) % regroup_every == 0
    any_overflow = overflow.astype(jnp.int32)
    for ax in reversed(psum_axes):
        any_overflow = jax.lax.psum(any_overflow, ax)
    full_update = time_trigger | (any_overflow > 0) | state.first

    from ..kernels.ops import plan_layout_repair, resident_regroup
    dst_slot, b2c_rep, fill_rep, openb_rep, total_new, n_free = \
        plan_layout_repair(state.b2c, state.fill, state.openb, active,
                           dst_c, bn=bn)
    resort_local = time_trigger | overflow | (total_new > n_free)

    # --- 6. layout repair (sparse) or full re-sort (local) ---------------
    def _repair():
        pid2 = state.pid.at[mv].set(-1, mode="drop") \
            .at[dst_slot].set(state.pid[mvs], mode="drop")
        xg2 = state.xg.at[dst_slot].set(state.xg[mvs], mode="drop")
        wg2 = state.wg.at[mv].set(0.0, mode="drop") \
            .at[dst_slot].set(state.wg[mvs], mode="drop")
        ug2 = u_new.at[dst_slot].set(u_new[mvs], mode="drop")
        lo2 = lo_new.at[dst_slot].set(lo_new[mvs], mode="drop")
        out = (xg2, pid2, ug2, lo2, wg2, b2c_rep, fill_rep, openb_rep)
        if int8:     # the moved rows' scales travel with them
            out += (state.xsc.at[dst_slot].set(state.xsc[mvs],
                                               mode="drop"),)
        return out

    def _resort():
        from ..kernels.ops import scatter_from_grouped
        zero = jnp.zeros((n,), jnp.float32)
        a_pt = scatter_from_grouped(state.pid, a_new,
                                    jnp.zeros((n,), jnp.int32))
        u_pt = scatter_from_grouped(state.pid, u_new, zero)
        lo_pt = scatter_from_grouped(state.pid, lo_new, zero)
        perm2, b2c2, fill2, openb2 = resident_regroup(a_pt, k, bn, nbt)
        valid2 = perm2 >= 0
        sp = jnp.maximum(perm2, 0)
        xg2 = jnp.where(valid2[:, None], x[sp], 0.0).astype(x.dtype)
        wg2 = jnp.where(valid2, w[sp], 0.0).astype(x.dtype)
        ug2 = jnp.where(valid2, u_pt[sp], 0.0)
        lo2 = jnp.where(valid2, lo_pt[sp], 0.0)
        out = (xg2, perm2, ug2, lo2, wg2, b2c2, fill2, openb2)
        if int8:     # re-quantize from the f32 masters at the re-sort
            from ..kernels import quant
            xq2, xsc2 = quant.quantize_rows(xg2)
            out = (xq2,) + out[1:] + (xsc2,)
        return out

    packed = jax.lax.cond(resort_local, _resort, _repair)
    xg2, pid2, ug2, lo2, wg2, b2c2, fill2, openb2 = packed[:8]
    xsc2 = packed[8] if int8 else None
    a_slot2 = jnp.repeat(jnp.maximum(b2c2, 0), bn).astype(jnp.int32)
    if int8:
        # masters in post-repair slot order: the exact rows behind both
        # the full center recompute and the energy statistic
        sp2 = jnp.maximum(pid2, 0)
        xf2 = jnp.where((pid2 >= 0)[:, None], x[sp2], 0.0)

    # --- 7. center update: incremental delta, or exact recompute at
    # re-sort points (bounds the f32 drift of the running sums) -----------
    def _full_local():
        xrows = xf2 if int8 else xg2
        seg = jnp.where(wg2 > 0, a_slot2, k)
        return (jax.ops.segment_sum(xrows * wg2[:, None], seg,
                                    num_segments=k + 1)[:k],
                jax.ops.segment_sum(wg2, seg, num_segments=k + 1)[:k])

    loc_s, loc_c = jax.lax.cond(full_update, _full_local,
                                lambda: (delta_sums, delta_counts))
    for ax in reversed(psum_axes):
        loc_s = jax.lax.psum(loc_s, ax)
        loc_c = jax.lax.psum(loc_c, ax)
    sums2 = jnp.where(full_update, loc_s, state.sums + loc_s)
    counts2 = jnp.where(full_update, loc_c, state.counts + loc_c)
    c_next = jnp.where(counts2[:, None] > 0,
                       sums2 / jnp.maximum(counts2, 1.0)[:, None], c)

    # --- 8. Hamerly bound adjustment (slot space; a slot's assignment is
    # its block's cluster after the repair) -------------------------------
    delta = jnp.sqrt(jnp.maximum(sqnorm(c_next - c), 0.0))
    delta_nb = jnp.max(delta[neighbors], axis=1)
    u_adj = ug2 + delta[a_slot2]
    lo_adj = lo2 - delta_nb[a_slot2]

    # --- 9. device-resident step statistics ------------------------------
    n_need = jnp.sum(need)
    energy = jnp.sum(wg2 * sqnorm((xf2 if int8 else xg2)
                                  - c_next[a_slot2]))
    n_rows = jnp.sum(state.pid >= 0)
    moved = jnp.where(resort_local, n_rows, n_changed).astype(jnp.int32)
    resorted = resort_local.astype(jnp.int32)
    changed = n_changed
    for ax in reversed(psum_axes):
        n_need = jax.lax.psum(n_need, ax)
        changed = jax.lax.psum(changed, ax)
        energy = jax.lax.psum(energy, ax)
        moved = jax.lax.psum(moved, ax)
        resorted = jax.lax.psum(resorted, ax)
        reranked = jax.lax.psum(reranked, ax)

    next_state = ResidentState(c_next, neighbors, sums2, counts2,
                               state.it + 1, jnp.zeros((), bool),
                               xg2, pid2, u_adj, lo_adj, wg2, b2c2,
                               fill2, openb2, xsc=xsc2)
    return next_state, StepStats(n_need, changed, energy, moved, resorted,
                                 reranked)


@functools.partial(jax.jit, static_argnames=("kn", "backend", "chunk",
                                             "bn", "bkn", "interpret"))
def _single_step(x, w, state, kn, backend, chunk, bn, bkn, interpret):
    return k2_iteration(x, w, state, kn=kn, backend=backend, chunk=chunk,
                        bn=bn, bkn=bkn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("kn", "backend", "chunk", "bn",
                                             "bkn", "interpret",
                                             "regroup_every", "move_cap",
                                             "precision"))
def _resident_single_step(x, w, state, kn, backend, chunk, bn, bkn,
                          interpret, regroup_every, move_cap,
                          precision="f32"):
    return k2_resident_iteration(x, w, state, kn=kn, backend=backend,
                                 chunk=chunk, bn=bn, bkn=bkn,
                                 interpret=interpret,
                                 regroup_every=regroup_every,
                                 move_cap=move_cap, precision=precision)


@dataclasses.dataclass(frozen=True)
class K2Step:
    """Builder for the k²-means iteration step.

    ``K2Step(k=.., kn=.., backend=.., mesh=..).build(n, d)`` returns a
    jitted ``step(x, w, state) -> (state', stats)`` with the
    :class:`K2State` (``residency="rebuild"``) or :class:`ResidentState`
    (``residency="resident"``) / :class:`StepStats` contract above.
    ``n`` is the (padded) global row count — on a mesh it must divide
    evenly over the flattened data axes; drivers pad rows and mark them
    ``w=0``. Always pass ``d`` (the feature count) when ``bn`` is
    auto-selected: it caps the point block to the VMEM budget at huge d,
    and it keeps the block size consistent between :meth:`build` and
    :meth:`init_resident` (the resident step itself re-derives ``bn``
    from the state's arena shapes, so a mismatch degrades block sizing,
    never correctness).

    For the resident residency, :meth:`init_resident` builds the initial
    state (one full grouping pass) and :meth:`final_assignment` scatters
    the converged layout back to point order — both placement-aware.
    """
    k: int
    kn: int
    backend: str = "xla"          # "xla" | "pallas"
    mesh: typing.Any = None       # jax Mesh or None (single-device)
    data_axes: tuple | None = None
    chunk: int = 2048             # xla backend: assignment chunk rows
    bn: int | None = None         # pallas backend: point-block size
    bkn: int = 8                  # pallas backend: candidate-tile width
    interpret: bool | None = None  # None -> interpret off-TPU
    residency: str = "rebuild"    # "rebuild" | "resident" (DESIGN.md §9)
    regroup_every: int = 16       # resident: full re-sort period
    move_cap: int | None = None   # resident: move-buffer rows (None: auto)
    spare_blocks: int = 0         # resident: extra free blocks in the arena
    precision: str = "f32"        # "f32" | "int8" quantized arena (§13)

    def axes(self) -> tuple:
        if self.mesh is None:
            return ()
        return tuple(self.data_axes) if self.data_axes \
            else dp_axes(self.mesh)

    def shards(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.axes()) \
            if self.mesh is not None else 1

    def _validate(self):
        if self.backend not in ("xla", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}; "
                             "expected 'xla' or 'pallas'")
        if self.residency not in ("rebuild", "resident"):
            raise ValueError(f"unknown residency {self.residency!r}; "
                             "expected 'rebuild' or 'resident'")
        if self.residency == "resident" and self.regroup_every < 1:
            raise ValueError("regroup_every must be >= 1, got "
                             f"{self.regroup_every}")
        if self.precision not in ("f32", "int8"):
            raise ValueError(f"unknown precision {self.precision!r}; "
                             "expected 'f32' or 'int8'")
        if self.precision == "int8" and self.residency != "resident":
            raise ValueError("precision='int8' requires the resident "
                             "arena (residency='resident'): the rebuild "
                             "engines would re-quantize the whole layout "
                             "every iteration")

    def _n_local(self, n: int) -> int:
        nsh = self.shards()
        if n % nsh:
            raise ValueError(
                f"n={n} must divide over {nsh} shards; pad rows (w=0) "
                "before building the step")
        return n // nsh

    def _bn(self, n: int, d: int | None = None) -> int:
        from ..kernels.ops import choose_group_bn
        return self.bn or choose_group_bn(self._n_local(n), self.k, d,
                                          bkn=self.bkn)

    def _move_cap(self, n: int) -> int:
        return self.move_cap or max(64, self._n_local(n) // 32)

    def _layout_shape(self, n: int, d: int | None = None):
        from ..kernels.ops import resident_capacity
        bn = self._bn(n, d)
        return bn, resident_capacity(self._n_local(n), self.k, bn,
                                     self.spare_blocks)

    def _resident_specs(self):
        xspec, rowspec, rep = clustering_specs(self.mesh, self.axes())
        return ResidentState(
            c=rep, prev_nb=rep, sums=rep, counts=rep, it=rep, first=rep,
            xg=xspec, pid=rowspec, ug=rowspec, lo_g=rowspec, wg=rowspec,
            b2c=rowspec, fill=rowspec, openb=rowspec,
            xsc=rowspec if self.precision == "int8" else None)

    def build(self, n: int, d: int | None = None):
        self._validate()
        kn = min(self.kn, self.k)
        from ..kernels.ops import resolve_interpret
        interpret = resolve_interpret(self.interpret)
        bn = self._bn(n, d)

        if self.residency == "resident":
            if self.mesh is None:
                return functools.partial(
                    _resident_single_step, kn=kn, backend=self.backend,
                    chunk=self.chunk, bn=bn, bkn=self.bkn,
                    interpret=interpret, regroup_every=self.regroup_every,
                    move_cap=self._move_cap(n), precision=self.precision)
            body = functools.partial(
                k2_resident_iteration, kn=kn, backend=self.backend,
                chunk=self.chunk, bn=bn, bkn=self.bkn, interpret=interpret,
                regroup_every=self.regroup_every,
                move_cap=self._move_cap(n), precision=self.precision,
                psum_axes=self.axes())
            xspec, rowspec, rep = clustering_specs(self.mesh, self.axes())
            state_specs = self._resident_specs()
            sharded = shard_map(
                body, mesh=self.mesh,
                in_specs=(xspec, rowspec, state_specs),
                out_specs=(state_specs,
                           StepStats(rep, rep, rep, rep, rep, rep)),
                check_vma=False)
            return jax.jit(sharded)

        if self.mesh is None:
            return functools.partial(
                _single_step, kn=kn, backend=self.backend,
                chunk=self.chunk, bn=bn, bkn=self.bkn,
                interpret=interpret)

        axes = self.axes()
        xspec, rowspec, rep = clustering_specs(self.mesh, axes)
        state_specs = K2State(rep, rowspec, rowspec, rowspec, rep, rep)
        body = functools.partial(
            k2_iteration, kn=kn, backend=self.backend, chunk=self.chunk,
            bn=bn, bkn=self.bkn, interpret=interpret, psum_axes=axes)
        # check_vma=False: pallas_call has no replication rule; the
        # replicated outputs (centers, neighbor lists, stats) are psum'd
        # or shard-identical by construction.
        sharded = shard_map(body, mesh=self.mesh,
                            in_specs=(xspec, rowspec, state_specs),
                            out_specs=(state_specs,
                                       StepStats(rep, rep, rep, rep, rep,
                                                 rep)),
                            check_vma=False)
        return jax.jit(sharded)

    def init_resident(self, x: jax.Array, w: jax.Array, centers: jax.Array,
                      assignment: jax.Array) -> ResidentState:
        """One-time resident-layout build from an initial assignment."""
        self._validate()
        n = x.shape[0]
        kn = min(self.kn, self.k)
        bn, nb_total = self._layout_shape(n, x.shape[1])
        body = functools.partial(init_resident_state, kn=kn, bn=bn,
                                 nb_total=nb_total,
                                 precision=self.precision,
                                 psum_axes=self.axes())
        if self.mesh is None:
            return jax.jit(body)(x, w, centers,
                                 assignment.astype(jnp.int32))
        xspec, rowspec, rep = clustering_specs(self.mesh, self.axes())
        sharded = shard_map(body, mesh=self.mesh,
                           in_specs=(xspec, rowspec, rep, rowspec),
                           out_specs=self._resident_specs(),
                           check_vma=False)
        return jax.jit(sharded)(x, w, centers,
                                assignment.astype(jnp.int32))

    def final_assignment(self, state: ResidentState, n: int) -> jax.Array:
        """Point-order assignment of a resident state ((n,), device)."""
        n_loc = self._n_local(n)
        body = functools.partial(resident_assignment, n=n_loc)
        if self.mesh is None:
            return jax.jit(body)(state)
        _, rowspec, _ = clustering_specs(self.mesh, self.axes())
        sharded = shard_map(body, mesh=self.mesh,
                           in_specs=(self._resident_specs(),),
                           out_specs=rowspec, check_vma=False)
        return jax.jit(sharded)(state)


__all__ = ["K2State", "K2Step", "ResidentState", "StepStats",
           "center_knn_graph", "init_state", "init_resident_state",
           "k2_iteration", "k2_resident_iteration", "resident_assignment",
           "resident_evict"]
