"""Query-time subsystem: bounded ``predict`` + streaming ``partial_fit``.

DESIGN.md §10. After ``fit`` the clustering becomes a *served* structure:
:class:`KMeansModel` wraps the centers, the center k_n-NN graph and the
per-cluster statistics (running member sums/counts) — plus, when built
from the training points, the resident grouped arena
(:class:`core.engine.ResidentState`) holding the member rows cluster-major.

``predict`` is the paper's assignment machinery turned into a query path,
two-level:

*Routing* is a cluster-closure coarse quantizer over the *centers* (the
candidate-restriction idea of Wang et al., Fast Approximate K-Means via
Cluster Closures): the k centers are grouped into ``route_groups`` groups
by a tiny k-means, each group lists its assigned centers closure-filled
to ``route_cap`` (never narrower than the largest group, so every center
is listed) with the nearest outside centers (overlap kills the
group-boundary misses a disjoint partition suffers in high d), and a
query scans its ``route_probes`` nearest groups' lists. *Resolution*
takes the routed winner's k_n-neighborhood from the center kNN graph —
the paper's own fit-time candidate structure — through the bkn-tiled
Pallas candidate kernel (``kernels.ops.bounded_predict_assign``) or the
portable XLA gather (``core.distance.chunked_candidate_argmin``). The
routed center is self-inclusive in its own neighborhood, so the final
argmin dominates everything the router computed.

Triangle-inequality bounds make the *counted* cost far smaller than the
dense scan, exactly as in the fit-time iteration: with the g group
distances in hand and one exact anchor distance per probed list (the
member nearest the group centroid), a probed member survives only when
``max(|d(q,gc_probed) − d(c,gc_probed)|, d(q,gc_owner) − d(c,gc_owner))``
— two free lower bounds from precomputed member-to-centroid distances —
undercuts the anchor upper bound, and a resolution neighbor only when
``d(nb, routed) < 2 d(q, routed)`` (Elkan's condition). Pruned entries
provably cannot win, so the bounds change the charge, never the
assignment (the TPU execution stays dense; the counter reflects what the
serial bounded algorithm computes, the repo-wide §2 methodology).

Counted distances per query land around ``route_groups + survivors``
instead of the brute-force ``k``: at the acceptance shape (k=512, kn=32,
defaults g=45/cap=68/probes=2) ~162 measured vs 512 — a >3x op cut at
recall@1 ≥ 0.99 on blobs (benchmarks/predict_bench.py).

``partial_fit`` is the streaming side (Sculley-style per-center
learning-rate updates — the running mean ``centers = sums / counts`` with
optional exponential forgetting ``decay``): each batch is assigned by the
bounded route, the center update is the incremental delta over the batch
(2·m counted additions, never an O(n) re-reduction), and the batch rows
are appended into the resident arena by the sparse-repair machinery
(``kernels.ops.plan_layout_repair``; free-pool exhaustion falls back to a
full ``resident_regroup``, exactly like the fit-time engine). The center
kNN graph refreshes every ``refresh_every`` batches — the O(k²d) graph
build is the only super-linear maintenance cost, so it is amortized.

The arena parks not-yet-streamed capacity rows in cluster 0 at weight 0:
every append is then a *move* (parked slot → assigned cluster's
watermark), which keeps the §9.1 slot-ownership invariants intact after
every batch and lets re-sorts run at one fixed static shape.

Checkpointing: the model state is a pytree of arrays plus a small static
config — ``save``/``restore`` ride the repo checkpointer
(``checkpoint.save_checkpoint`` with the config in ``extra_meta``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

from .distance import (chunked_candidate_argmin, chunked_candidate_top2,
                       pairwise_sqdist, sqnorm)
from .engine import ResidentState, resident_evict
from .lloyd import KMeansResult
from .opcount import LAYOUT_STATE_LANES, OpCounter
from ..kernels import quant as _quant


_VALIDATE_MODES = ("raise", "sanitize", "none")
_PRECISIONS = ("f32", "int8")
# static f32 re-rank width of the quantized resolution scan (DESIGN.md
# §13): survivor sets beyond this width fall back to a full-kn exact
# re-rank for that row (the member-scan stage has no width cap)
_RESOLVE_RERANK = 16


def _validate_rows(x, mode: str, *, what: str):
    """Input validation for the serving paths (DESIGN.md §11): "raise"
    rejects non-finite rows with an error naming them, "sanitize" zeroes
    them, "none" skips the check."""
    if mode not in _VALIDATE_MODES:
        raise ValueError(f"validate must be one of {_VALIDATE_MODES}, "
                         f"got {mode!r}")
    if mode == "none":
        return x
    bad = ~jnp.isfinite(x).all(axis=1)
    n_bad = int(jnp.sum(bad))
    if n_bad == 0:
        return x
    if mode == "raise":
        idx = np.flatnonzero(np.asarray(bad))[:8]
        raise ValueError(
            f"{what}: {n_bad} non-finite rows (first at {idx.tolist()}); "
            f"pass validate='sanitize' to zero them")
    return jnp.where(bad[:, None], 0.0, x)


def _default_groups(k: int) -> int:
    """Routing-group count: ~2 sqrt(k) (g=45 at the k=512 acceptance
    shape), at least 4."""
    return min(k, max(4, int(round(2.0 * math.sqrt(k)))))


def _default_probes(g: int) -> int:
    """Groups a query scans: 2, or g/32 where that is more — the more
    groups, the smaller the share of a query's neighborhood each list
    holds (recall@1 on power-law GMM fits: 0.9976 with 2 probes at
    k=512, g=45; at k=4096, g=128: 0.9797 with 2, 0.9913 with 3, 0.9952
    with 4)."""
    return max(2, g // 32)


def _default_cap(k: int, g: int, kn: int) -> int:
    """Member-list width: ~6x the mean group size (5x closure overlap on
    top of the disjoint partition — the triangle-inequality pruning
    absorbs most of the dense cost, so wide lists buy recall nearly for
    free in counted ops), never below the kn-neighborhood.
    :func:`_build_router` widens it further to hold the largest group."""
    return min(k, max(kn, 6 * k // max(g, 1)))


class Router(typing.NamedTuple):
    """Cluster-closure routing structure, rebuilt with the kNN graph.

    ``mdist``/``modist`` are the member-to-centroid true distances the
    query-time triangle-inequality bounds read: ``mdist[j, i]`` to the
    *listing* group's centroid, ``modist[j, i]`` to the member's *owner*
    group's centroid (``mowner[j, i]``)."""
    gc: jax.Array       # (g, d) group centroids
    members: jax.Array  # (g, cap) int32 closure member lists
    mdist: jax.Array    # (g, cap) d(member, gc[listing group])
    mowner: jax.Array   # (g, cap) int32 owner group per member
    modist: jax.Array   # (g, cap) d(member, gc[owner group])


@functools.partial(jax.jit, static_argnames=("g", "iters"))
def _router_groups(c, g: int, iters: int):
    """The router's grouping: a tiny k-means over the k centers (strided
    warm start). Returns the group centroids (g, d), the (g, k)
    centroid-to-center squared distances and each group's size (g,)."""
    k = c.shape[0]
    gc = c[jnp.linspace(0, k - 1, g).round().astype(jnp.int32)]
    for _ in range(iters):
        ga = jnp.argmin(pairwise_sqdist(c, gc), axis=1)
        sums = jax.ops.segment_sum(c, ga, num_segments=g)
        cnt = jax.ops.segment_sum(jnp.ones((k,), c.dtype), ga,
                                  num_segments=g)
        gc = jnp.where(cnt[:, None] > 0,
                       sums / jnp.maximum(cnt, 1.0)[:, None], gc)
    dgc = pairwise_sqdist(gc, c)
    return gc, dgc, jnp.bincount(jnp.argmin(dgc, axis=0), length=g)


@functools.partial(jax.jit, static_argnames=("cap",))
def _router_lists(gc, dgc, cap: int) -> Router:
    """Each group lists its assigned members closure-filled to ``cap``
    with the nearest non-members. Selection ranks assigned members (by
    distance to the group centroid) strictly ahead of fills by squashing
    both scores into disjoint [0,1) / [1,2) bands. The member-to-centroid
    distances ride along for the query-time bounds."""
    g = gc.shape[0]
    ga = jnp.argmin(dgc, axis=0)                        # (k,) owner group
    norm = dgc / (jnp.max(dgc) + 1.0)                   # scores in [0, 1)
    assigned = ga[None, :] == jnp.arange(g)[:, None]    # (g, k)
    score = jnp.where(assigned, norm, 1.0 + norm)
    _, members = jax.lax.top_k(-score, cap)
    members = members.astype(jnp.int32)
    dgc_true = jnp.sqrt(dgc)
    mdist = jnp.take_along_axis(dgc_true, members, axis=1)
    mowner = ga[members].astype(jnp.int32)
    modist = dgc_true.T[members, mowner]                # d(c, gc_owner)
    return Router(gc, members, mdist, mowner, modist)


def _build_router(c, g: int, cap: int, iters: int) -> Router:
    """Cluster-closure router over the centers: :func:`_router_groups`
    groups the k centers into g groups and :func:`_router_lists` lists
    them, at least ``cap`` wide and never narrower than the largest
    group (rounded up to 8; one host read per build). Every center then
    sits in its own group's list, which a query at that center probes
    first — members past the width would sit in no list, where routing
    never reaches them (on power-law mixtures one group can own 12x the
    mean). The width therefore grows when drift piles centers into one
    group; the route programs retrace once at the new shape."""
    gc, dgc, size = _router_groups(c, g, iters)
    largest = -(-int(jnp.max(size)) // 8) * 8
    return _router_lists(gc, dgc, min(c.shape[0], max(cap, largest)))


@functools.partial(jax.jit, static_argnames=("probes",))
def _route(q, c, router: Router, probes: int):
    """Route queries through the closure router.

    Distances to the g group centroids, then a scan over the ``probes``
    nearest groups' member lists with triangle-inequality pruning: one
    exact anchor distance per probed list (its head member — the one
    nearest the group centroid), every other member charged only when
    ``max(|d(q,gc_probed) − mdist|, d(q,gc_owner) − modist)`` undercuts
    the anchor bound. The dense (m, probes*cap) scan still executes —
    pruned entries provably cannot win the argmin, so masking them
    changes nothing; ``n_scanned`` is what the serial bounded algorithm
    would compute (charged by the caller).

    Returns (routed (m,) int32, u_routed (m,) true distance to the
    routed center, n_scanned (m,) int32 per-query distance charge for
    the stage)."""
    m = q.shape[0]
    cap = router.members.shape[1]
    dg = jnp.sqrt(pairwise_sqdist(q, router.gc))        # (m, g)
    _, gi = jax.lax.top_k(-dg, probes)
    cand = router.members[gi].reshape(m, -1)            # (m, probes*cap)
    lb1 = jnp.abs(jnp.take_along_axis(dg, gi, axis=1)[:, :, None]
                  - router.mdist[gi]).reshape(m, -1)
    own = router.mowner[gi].reshape(m, -1)
    lb2 = jnp.take_along_axis(dg, own, axis=1) \
        - router.modist[gi].reshape(m, -1)
    lb = jnp.maximum(lb1, lb2)
    cc = c[cand]
    cross = jnp.einsum("md,mjd->mj", q, cc)
    sq = jnp.maximum(sqnorm(q)[:, None] - 2.0 * cross + sqnorm(cc), 0.0)
    anchor_cols = jnp.arange(probes) * cap
    u_anchor = jnp.sqrt(jnp.min(sq[:, anchor_cols], axis=1))
    passing = lb < u_anchor[:, None]
    passing = passing.at[:, anchor_cols].set(True)
    sq_m = jnp.where(passing, sq, jnp.inf)
    j = jnp.argmin(sq_m, axis=1)
    routed = jnp.take_along_axis(cand, j[:, None], axis=1)[:, 0]
    u_routed = jnp.sqrt(jnp.take_along_axis(sq_m, j[:, None], axis=1)[:, 0])
    n_scanned = router.gc.shape[0] + jnp.sum(passing, axis=1)
    return routed, u_routed, n_scanned


@functools.partial(jax.jit, static_argnames=("probes",))
def _route_groups_int8(q, xq, xsc, gc, gq, probes: int):
    """Quantized group-centroid scan (DESIGN.md §13), always returning
    the *exact* f32 top-``probes`` group set.

    Approximate true distances ŝ between the int8 queries and the int8
    group-centroid table give a provisional top-``probes`` selection.
    Per-row margins use the tables' exact residual norms (``err``, much
    tighter than the worst-case radius): with ``ub = ŝ + rad`` and
    ``lb = ŝ - rad`` bracketing every true distance, the exact top-probes
    set is provably contained in the *ambiguity band*
    ``{j : lb_j <= max over selected of ub}`` (the probes-th smallest
    true distance never exceeds that bound). When the band holds exactly
    ``probes`` groups the selection is proven; otherwise the band members
    are re-ranked with their exact f32 distances — the executed scan is
    dense, but the serial bounded algorithm computes only the band, so
    that is the f32 charge (§2 methodology). Returns
    (gi (m, probes) int32, n_exact (m,) per-row f32 distance charge)."""
    m, d = xq.shape
    xi = xq.astype(jnp.int32)
    cross = xi @ gq.q.astype(jnp.int32).T                    # (m, g)
    xhsq = (xsc * xsc) * jnp.sum(xi * xi, axis=1).astype(jnp.float32)
    dist = jnp.maximum(
        xhsq[:, None]
        - 2.0 * (xsc[:, None] * gq.scale[None, :]) * cross.astype(
            jnp.float32)
        + gq.sq[None, :], 0.0)
    shat = jnp.sqrt(dist)
    xerr = jnp.linalg.norm(q - xq.astype(jnp.float32) * xsc[:, None],
                           axis=1)
    rad = gq.err[None, :] + xerr[:, None]
    _, gi = jax.lax.top_k(-shat, probes)
    sel = jnp.zeros(shat.shape, bool).at[
        jnp.arange(m)[:, None], gi].set(True)
    ub_sel = jnp.max(jnp.where(sel, shat + rad, -jnp.inf), axis=1)
    band = (shat - rad) <= ub_sel[:, None]                   # ⊇ sel
    nband = jnp.sum(band.astype(jnp.int32), axis=1)
    ambiguous = nband > probes
    dg = jnp.sqrt(pairwise_sqdist(q, gc))
    _, gi_exact = jax.lax.top_k(-jnp.where(band, dg, jnp.inf), probes)
    gi = jnp.where(ambiguous[:, None], gi_exact, gi)
    return gi.astype(jnp.int32), jnp.where(ambiguous, nband, 0)


@jax.jit
def _route_members_int8(qb, xq, xsc, c, cq, cand):
    """Quantized member scan + exact f32 re-rank of ALL margin survivors.

    The int8 scan over the probed closure lists brackets every true
    distance with the exact residual radii (DESIGN.md §13); the margin
    cut keeps every candidate that could be the true minimum, and those
    survivors are re-ranked with exact f32 distances — no re-rank width
    cap, so the survivor set never overflows. The executed scan is dense
    either way; the serial charge is the number of *unique* surviving
    ids (the probed closure lists overlap, and a serial re-rank would
    dedup before computing distances). The row is accepted (``ok``)
    unless two *distinct* surviving ids tie exactly at the minimum —
    only then does the routed id depend on tie-break order and the
    caller re-routes through the f32 scan. Returns
    (routed, u_routed, ok, n_rerank)."""
    xi = xq.astype(jnp.int32)
    tab = cq.q[cand].astype(jnp.int32)                  # (m, P, d)
    cross = jnp.einsum("md,mpd->mp", xi, tab)
    xhsq = (xsc * xsc) * jnp.sum(xi * xi, axis=1).astype(jnp.float32)
    dist = jnp.maximum(
        xhsq[:, None]
        - 2.0 * (xsc[:, None] * cq.scale[cand]) * cross.astype(jnp.float32)
        + cq.sq[cand], 0.0)
    shat = jnp.sqrt(dist)
    xerr = jnp.linalg.norm(qb - xq.astype(jnp.float32) * xsc[:, None],
                           axis=1)
    rc = cq.err[cand]
    cut = jnp.min(shat + rc, axis=1) + 2.0 * xerr
    mask = (shat - rc) <= cut[:, None]
    ids = jnp.where(mask, cand, -1)
    sq = _quant.rerank_exact(qb, c, ids)
    routed, d1, _ = _quant.first_min_top2(sq, ids)
    tie_other = jnp.any((sq == d1[:, None]) & (ids >= 0)
                        & (ids != routed[:, None]), axis=1)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    srt = jnp.sort(jnp.where(mask, cand, big), axis=1)
    uniq = jnp.concatenate(
        [srt[:, :1] != big,
         (srt[:, 1:] != srt[:, :-1]) & (srt[:, 1:] != big)], axis=1)
    nsv = jnp.sum(uniq.astype(jnp.int32), axis=1)
    return routed, jnp.sqrt(d1), ~tie_other, nsv


@functools.partial(jax.jit, static_argnames=("kn",))
def _graph_with_dists(c, kn: int):
    """Center kNN graph plus true neighbor distances from ONE O(k²d)
    pairwise pass (the same top-k selection as
    :func:`core.engine.center_knn_graph`, so fit and query sides route
    through identical neighborhoods). The distances feed the resolution
    stage's Elkan ``2u`` pruning charge."""
    cc = pairwise_sqdist(c, c)
    _, neighbors = jax.lax.top_k(-cc, kn)
    neighbors = neighbors.astype(jnp.int32)
    nb_dist = jnp.sqrt(jnp.take_along_axis(cc, neighbors, axis=1))
    return neighbors, nb_dist


@jax.jit
def _delta_update(c, sums, counts, xb, wb, ab, decay, floor):
    """Sculley per-center running-mean update as an incremental delta:
    ``sums/counts`` absorb the batch (with exponential forgetting
    ``decay``) and every touched center lands on its new running mean —
    the batched equivalent of sequential ``eta = 1/v[c]`` steps.

    ``floor`` is the numerically-safe count floor of the time-decayed
    statistics (DESIGN.md §14): a center whose decayed mass dips under
    it is frozen at the floor with its sums re-anchored to the current
    center (``sums = c · floor``), so long-idle centers hold their
    position instead of collapsing toward 0/0. ``floor = 0`` disables
    the clamp exactly (the pre-streaming behavior: empty centers keep
    ``c`` through the ``counts > 0`` guard)."""
    k = c.shape[0]
    sums2 = sums * decay + jax.ops.segment_sum(xb * wb[:, None], ab,
                                               num_segments=k)
    counts2 = counts * decay + jax.ops.segment_sum(wb, ab, num_segments=k)
    frozen = counts2 < floor
    counts2 = jnp.where(frozen, jnp.maximum(floor, counts2), counts2)
    sums2 = jnp.where(frozen[:, None], c * counts2[:, None], sums2)
    c2 = jnp.where(counts2[:, None] > 0,
                   sums2 / jnp.maximum(counts2, 1e-12)[:, None], c)
    return c2, sums2, counts2


@functools.partial(jax.jit, static_argnames=("cap",))
def _batch_ids(wb, n_rows, cap: int = 0):
    """Insertion ids for the live batch rows: dense from ``n_rows`` in
    lane order, the sentinel -1 for w=0 padding lanes — padding neither
    consumes ids/capacity nor appears in the mirrors (consumers map the
    sentinel out of range and scatter with mode="drop"). With ``cap`` the
    ids wrap modulo the capacity — the windowed ring (DESIGN.md §14):
    ``n_rows`` is then the monotonic rows-streamed clock and a recycled
    id is only legal once sliding-window eviction has killed its previous
    occupant (the caller checks)."""
    live = wb > 0
    ids = n_rows + jnp.cumsum(live) - 1
    if cap:
        ids = ids % cap
    return jnp.where(live, ids, -1).astype(jnp.int32)


@jax.jit
def _update_mirrors(x_pts, a_pts, w_pts, e_pts, xb, wb, ab, ids, epoch):
    """Write the live batch rows into the insertion-order mirrors
    (re-sorts and ``assignment()`` read them) and stamp their stream
    epoch; padding lanes (sentinel ids) drop."""
    cap = x_pts.shape[0]
    idx = jnp.where(ids >= 0, ids, cap)
    x_pts = x_pts.at[idx].set(xb.astype(x_pts.dtype), mode="drop")
    a_pts = a_pts.at[idx].set(ab.astype(jnp.int32), mode="drop")
    w_pts = w_pts.at[idx].set(wb.astype(w_pts.dtype), mode="drop")
    e_pts = e_pts.at[idx].set(jnp.int32(epoch), mode="drop")
    return x_pts, a_pts, w_pts, e_pts


@jax.jit
def _evict_mirrors(a_pts, w_pts, pid_old, evict):
    """Park the evicted rows in the insertion-order mirrors: weight 0,
    cluster 0 — exactly the parked-capacity convention, so the next full
    re-sort reclaims their arena holes into cluster 0's parked pool."""
    cap = a_pts.shape[0]
    idx = jnp.where(evict & (pid_old >= 0), pid_old, cap)
    a_pts = a_pts.at[idx].set(0, mode="drop")
    w_pts = w_pts.at[idx].set(0.0, mode="drop")
    return a_pts, w_pts


@jax.jit
def _slot_epochs(pid, e_pts):
    """Per-slot stream epochs gathered from the insertion-order epoch
    mirror; free slots (pid < 0) read as INT32_MAX so they can never look
    older than the eviction cutoff."""
    cap = e_pts.shape[0]
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    eg = e_pts[jnp.clip(pid, 0, cap - 1)] if cap else \
        jnp.zeros_like(pid)
    return jnp.where(pid >= 0, eg, big)


@functools.partial(jax.jit, static_argnames=("bn", "cap"))
def _arena_try_append(state: ResidentState, xb, wb, ab, ids, *, bn: int,
                      cap: int):
    """Sparse-repair append of one batch into the arena.

    Every live batch row moves from its parked slot (cluster 0, weight 0)
    to a slot allocated at its destination cluster's watermark
    (``plan_layout_repair``); the parked slot becomes a hole reclaimed by
    the next full re-sort. Returns ``(xg, pid, wg, b2c, fill, openb, ok)``
    — the arrays are only valid when ``ok`` (the free pool sufficed);
    the caller falls back to :func:`_arena_resort` otherwise."""
    from ..kernels.ops import plan_layout_repair
    s_total = state.pid.shape[0]
    active = wb > 0
    dst_slot, b2c2, fill2, openb2, total_new, n_free = plan_layout_repair(
        state.b2c, state.fill, state.openb, active, ab, bn=bn)
    ok = total_new <= n_free
    # invert pid -> slot to find the batch rows' parked source slots
    slot_idx = jnp.arange(s_total, dtype=jnp.int32)
    slot_of = jnp.full((cap,), s_total, jnp.int32) \
        .at[jnp.where(state.pid >= 0, state.pid, cap)] \
        .set(slot_idx, mode="drop")
    src = slot_of[jnp.clip(ids, 0, cap - 1)]             # (m,) parked slots
    src = jnp.where(active, src, s_total)                # dead lanes drop
    pid2 = state.pid.at[src].set(-1, mode="drop") \
        .at[dst_slot].set(ids.astype(jnp.int32), mode="drop")
    xg2 = state.xg.at[dst_slot].set(xb.astype(state.xg.dtype), mode="drop")
    wg2 = state.wg.at[src].set(0.0, mode="drop") \
        .at[dst_slot].set(wb.astype(state.wg.dtype), mode="drop")
    return xg2, pid2, wg2, b2c2, fill2, openb2, ok


@functools.partial(jax.jit, static_argnames=("k", "bn", "nbt"))
def _arena_resort(x_pts, a_pts, w_pts, *, k: int, bn: int, nbt: int):
    """Full re-sort from the insertion-order mirrors (static shape: the
    mirrors cover the whole capacity, parked rows ride along in cluster 0
    at weight 0). Same packing as the fit-time engine's re-sort."""
    from ..kernels.ops import resident_regroup
    perm, b2c, fill, openb = resident_regroup(a_pts, k, bn, nbt)
    valid = perm >= 0
    sp = jnp.maximum(perm, 0)
    xg = jnp.where(valid[:, None], x_pts[sp], 0.0).astype(x_pts.dtype)
    wg = jnp.where(valid, w_pts[sp], 0.0).astype(w_pts.dtype)
    return xg, perm, wg, b2c, fill, openb


@dataclasses.dataclass
class KMeansModel:
    """A served clustering: centers + center kNN graph + per-cluster stats
    (+ optional resident member arena). Mutable — ``partial_fit`` updates
    it in place; ``predict`` only reads.

    ``state`` is a :class:`core.engine.ResidentState`: ``c`` the centers,
    ``prev_nb`` the center kNN graph, ``sums``/``counts`` the running
    per-cluster statistics, and the slot arrays the member arena (empty
    — zero slots — for predict-only models built without points). The
    ``ug``/``lo_g`` bound lanes are carried at zero: the query path
    recomputes from scratch, so there are no bounds to keep warm.
    """
    state: ResidentState
    router: Router              # closure routing structure (g groups)
    nb_dist: jax.Array          # (k, kn) center-to-neighbor true distances
    x_pts: jax.Array            # (cap, d) insertion-order mirror
    a_pts: jax.Array            # (cap,) int32 assignment mirror
    w_pts: jax.Array            # (cap,) weight mirror (0 = not streamed)
    kn: int
    bn: int
    backend: str = "xla"        # "xla" | "pallas" (predict resolution)
    bkn: int = 8
    interpret: bool | None = None
    route_probes: int = 2       # groups scanned per query
    router_iters: int = 8       # tiny-k-means iterations per router build
    refresh_every: int = 8      # partial_fit batches between graph builds
    decay: float = 1.0          # exponential forgetting of sums/counts
    precision: str = "f32"      # default predict scan precision (§13)
    n_rows: int = 0             # streamed rows (arena + mirrors prefix)
    batches_seen: int = 0
    degraded_folds: int = 0     # arena-full batches folded stats-only
    # lazily built quantized scan tables (centers + group centroids),
    # dropped whenever the centers/router drift — see _quant_tables
    _qt: typing.Any = dataclasses.field(default=None, repr=False,
                                        compare=False)
    # -- streaming / drift (DESIGN.md §14) --------------------------------
    window: int = 0             # sliding window in stream epochs (0 = off)
    half_life: float = 0.0      # decay half-life in epochs (0: raw decay)
    count_floor: float = 0.0    # freeze floor for decayed counts
    drift_guard: bool = False   # EWMA drift detection + center repair
    rows_streamed: int = 0      # monotonic live-row clock (ring ids)
    evicted_rows: int = 0       # rows retired by the sliding window
    repaired_centers: int = 0   # centers re-seated by the drift guard
    e_pts: jax.Array | None = None    # (cap,) int32 stream-epoch mirror
    c_motion: jax.Array | None = None  # (k,) cumulative center drift
    # drift-guard EWMA state (ft.invariants.DriftGuard) and per-stream
    # warm-start Hamerly bounds — runtime caches, not checkpointed
    _dg: typing.Any = dataclasses.field(default=None, repr=False,
                                        compare=False)
    _streams: dict = dataclasses.field(default_factory=dict, repr=False,
                                       compare=False)

    def __post_init__(self):
        if self.e_pts is None:
            self.e_pts = jnp.full((self.capacity,), -1, jnp.int32)
        if self.c_motion is None:
            self.c_motion = jnp.zeros((self.k,), jnp.float32)
        if self.rows_streamed < self.n_rows:
            self.rows_streamed = self.n_rows

    # -- construction ------------------------------------------------------

    @classmethod
    def from_result(cls, result: KMeansResult, x: jax.Array | None = None,
                    *, kn: int = 30, capacity: int | None = None,
                    backend: str = "xla", bkn: int = 8,
                    interpret: bool | None = None,
                    route_groups: int | None = None,
                    route_cap: int | None = None,
                    route_probes: int | None = None,
                    router_iters: int = 8,
                    refresh_every: int = 8, decay: float = 1.0,
                    bn: int | None = None,
                    precision: str = "f32",
                    window: int = 0, half_life: float = 0.0,
                    count_floor: float = 0.0,
                    drift_guard: bool = False) -> "KMeansModel":
        """Build a model from any :class:`KMeansResult`.

        Without ``x`` the model is predict-only plus stats-only
        ``partial_fit`` (per-cluster counts seeded from the fit
        assignment, sums from ``centers * counts`` — exact, since the
        centers are the member means). With ``x`` the resident arena is
        built over the training rows with headroom for
        ``capacity - len(x)`` streamed rows (default capacity: 2n).

        Routing defaults scale with k: ``route_groups`` ~2√k,
        ``route_cap`` ~6x the mean group (a floor: every router build
        widens the lists to hold its largest group) and ``route_probes``
        from the group count (:func:`_default_probes`).
        """
        from ..kernels.ops import choose_group_bn, resident_capacity
        if precision not in _PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}; "
                             f"expected one of {_PRECISIONS}")
        if window < 0 or half_life < 0 or count_floor < 0:
            raise ValueError("window, half_life and count_floor must be "
                             ">= 0")
        c = jnp.asarray(result.centers, jnp.float32)
        k, d = c.shape
        kn = min(kn, k)
        a0 = jnp.asarray(result.assignment, jnp.int32)
        neighbors, nb_dist = _graph_with_dists(c, kn)
        g = route_groups or _default_groups(k)
        rcap = route_cap or _default_cap(k, g, kn)
        router = _build_router(c, g, rcap, router_iters)
        counts = jnp.bincount(a0, length=k).astype(jnp.float32)
        sums = c * counts[:, None]
        common = dict(router=router, nb_dist=nb_dist, kn=kn,
                      backend=backend, bkn=bkn, interpret=interpret,
                      route_probes=route_probes or _default_probes(g),
                      router_iters=router_iters,
                      refresh_every=refresh_every, decay=decay,
                      precision=precision, batches_seen=0,
                      window=window, half_life=half_life,
                      count_floor=count_floor, drift_guard=drift_guard)
        if x is None:
            zerod = jnp.zeros((0, d), jnp.float32)
            zero1 = jnp.zeros((0,), jnp.float32)
            state = ResidentState(
                c=c, prev_nb=neighbors, sums=sums, counts=counts,
                it=jnp.zeros((), jnp.int32), first=jnp.array(False),
                xg=zerod, pid=jnp.zeros((0,), jnp.int32), ug=zero1,
                lo_g=zero1, wg=zero1, b2c=jnp.zeros((0,), jnp.int32),
                fill=jnp.zeros((k,), jnp.int32),
                openb=jnp.full((k,), -1, jnp.int32))
            return cls(state=state, x_pts=zerod,
                       a_pts=jnp.zeros((0,), jnp.int32), w_pts=zero1,
                       bn=bn or 8, n_rows=0, **common)
        x = jnp.asarray(x, jnp.float32)
        n = x.shape[0]
        cap = capacity or 2 * n
        if cap < n:
            raise ValueError(f"capacity={cap} < n={n} training rows")
        bn = bn or choose_group_bn(cap, k, d, bkn=bkn)
        nbt = resident_capacity(cap, k, bn)
        # parked capacity tail: cluster 0 at weight 0 (module docstring)
        x_pts = jnp.zeros((cap, d), jnp.float32).at[:n].set(x)
        a_pts = jnp.zeros((cap,), jnp.int32).at[:n].set(a0)
        w_pts = jnp.zeros((cap,), jnp.float32).at[:n].set(1.0)
        # training rows enter the stream clock at epoch 0
        e_pts = jnp.full((cap,), -1, jnp.int32).at[:n].set(0)
        xg, pid, wg, b2c, fill, openb = _arena_resort(
            x_pts, a_pts, w_pts, k=k, bn=bn, nbt=nbt)
        zero_s = jnp.zeros((pid.shape[0],), jnp.float32)
        state = ResidentState(
            c=c, prev_nb=neighbors, sums=sums, counts=counts,
            it=jnp.zeros((), jnp.int32), first=jnp.array(False),
            xg=xg, pid=pid, ug=zero_s, lo_g=zero_s, wg=wg, b2c=b2c,
            fill=fill, openb=openb)
        return cls(state=state, x_pts=x_pts, a_pts=a_pts, w_pts=w_pts,
                   bn=bn, n_rows=n, e_pts=e_pts, rows_streamed=n, **common)

    # -- read-side properties ---------------------------------------------

    @property
    def centers(self) -> jax.Array:
        return self.state.c

    @property
    def neighbors(self) -> jax.Array:
        return self.state.prev_nb

    @property
    def counts(self) -> jax.Array:
        return self.state.counts

    @property
    def sums(self) -> jax.Array:
        return self.state.sums

    @property
    def k(self) -> int:
        return self.state.c.shape[0]

    @property
    def d(self) -> int:
        return self.state.c.shape[1]

    @property
    def capacity(self) -> int:
        return self.x_pts.shape[0]

    @property
    def has_arena(self) -> bool:
        return self.state.pid.shape[0] > 0

    def assignment(self) -> jax.Array:
        """Insertion-order assignment of every streamed row, (n_rows,).
        Windowed models park evicted rows at weight 0 in cluster 0 —
        filter by ``w_pts > 0`` (or :meth:`live_rows`) to see only the
        surviving window."""
        return self.a_pts[:self.n_rows]

    @property
    def stream_decay(self) -> float:
        """Effective per-epoch forgetting factor: ``2^(-1/half_life)``
        when a half-life (in stream epochs) is set, else the raw
        ``decay`` field (DESIGN.md §14)."""
        if self.half_life > 0:
            return float(2.0 ** (-1.0 / self.half_life))
        return self.decay

    def live_rows(self) -> int:
        """Rows currently alive in the mirrors (streamed and not yet
        evicted by the sliding window)."""
        return int(jnp.sum(self.w_pts > 0))

    @property
    def route_groups(self) -> int:
        return self.router.gc.shape[0]

    @property
    def route_cap(self) -> int:
        return self.router.members.shape[1]

    def dense_distances_per_query(self) -> int:
        """Dense (unpruned) distance evaluations per predicted query —
        the upper bound on the counted charge; the triangle-inequality
        bounds typically cut the measured charge well below it."""
        return (self.route_groups + self.route_probes * self.route_cap
                + self.kn)

    # -- predict -----------------------------------------------------------

    def _quant_tables(self):
        """The int8 scan tables (DESIGN.md §13): a
        :class:`kernels.quant.CenterQuant` over the centers (member scan
        + resolution slabs) and one over the group centroids (routing).
        Built lazily on the first quantized scan and invalidated by
        ``partial_fit`` (the centers drift every batch)."""
        if self._qt is None:
            self._qt = (_quant.center_quant(self.state.c),
                        _quant.center_quant(self.router.gc))
        return self._qt

    def _route_int8(self, qb: jax.Array, probes: int):
        """Quantized routing with exact fallback: the int8 group scan
        resolves the exact f32 top-probes group set (band re-rank inside
        :func:`_route_groups_int8`), the int8 member scan re-ranks its
        margin survivors exactly (unique-winner test); the rare rows the
        member margin cannot prove are re-routed by the exact f32
        :func:`_route`, so the returned ``routed`` ids always match the
        f32 route's. Returns (routed, u_routed, n_f32) with ``n_f32``
        the per-row f32 distance charge (group band + re-ranked
        survivors, or the full bounded route charge on fallback rows)."""
        cq, gq = self._quant_tables()
        xq, xsc = _quant.quantize_rows(qb)
        gi, n_grp = _route_groups_int8(qb, xq, xsc, self.router.gc, gq,
                                       probes)
        cand = self.router.members[gi].reshape(qb.shape[0], -1)
        routed, u_routed, ok, n_rr = _route_members_int8(
            qb, xq, xsc, self.state.c, cq, cand)
        n_rr = n_rr + n_grp
        if not bool(jnp.all(ok)):
            rf, uf, nf = _route(qb, self.state.c, self.router, probes)
            routed = jnp.where(ok, routed, rf)
            u_routed = jnp.where(ok, u_routed, uf)
            n_rr = jnp.where(ok, n_rr, nf)
        return routed, u_routed, n_rr

    def route(self, q: jax.Array) -> jax.Array:
        """Route queries through the closure router ((m,) int32): the
        best center found among the ``route_probes`` nearest groups'
        member lists. The resolution pass then scans this center's
        kn-neighborhood, which contains it (self-inclusive graph), so the
        final argmin dominates every distance the router computed."""
        q = jnp.asarray(q, jnp.float32)
        routed, _, _ = _route(q, self.state.c, self.router,
                              self.route_probes)
        return routed

    def route_batch(self, qb: jax.Array, probes: int | None = None,
                    precision: str | None = None):
        """The routing stage alone: ``(routed, u_routed, n_scanned)`` for
        one batch, with an optional ``probes`` override (the serving
        executor's degraded rungs shrink the closure probes and, at the
        route-only rung, take ``routed`` as the assignment outright —
        DESIGN.md §12) and an optional ``precision`` override
        ("int8": the quantized route of :meth:`_route_int8`, identical
        routed ids at a ~4x smaller scan)."""
        p = self.route_probes if probes is None else min(
            probes, self.route_groups)
        prec = precision or self.precision
        if prec not in _PRECISIONS:
            raise ValueError(f"unknown precision {prec!r}; "
                             f"expected one of {_PRECISIONS}")
        qb = jnp.asarray(qb, jnp.float32)
        if prec == "int8":
            return self._route_int8(qb, p)
        return _route(qb, self.state.c, self.router, p)

    def _resolve(self, qb: jax.Array, routed: jax.Array):
        if self.backend == "pallas":
            from ..kernels.ops import bounded_predict_assign, choose_group_bn
            bn = choose_group_bn(qb.shape[0], self.k, self.d, bkn=self.bkn)
            return bounded_predict_assign(
                qb, self.state.c, self.state.prev_nb, routed, bn=bn,
                bkn=self.bkn, interpret=self.interpret)
        return _resolve_xla(qb, self.state.c, self.state.prev_nb, routed)

    def _resolve_top2(self, qb: jax.Array, routed: jax.Array):
        """Resolution with the two best squared distances (the Hamerly
        bound pair): ``(a, d1_sq, d2_sq)`` over the routed center's
        kn-neighborhood. Pallas returns squared distances natively; the
        XLA twin returns true distances, squared here so both backends
        share one unit."""
        if self.backend == "pallas":
            from ..kernels.ops import (bounded_predict_assign_top2,
                                       choose_group_bn)
            bn = choose_group_bn(qb.shape[0], self.k, self.d, bkn=self.bkn)
            return bounded_predict_assign_top2(
                qb, self.state.c, self.state.prev_nb, routed, bn=bn,
                bkn=self.bkn, interpret=self.interpret)
        cand = self.state.prev_nb[routed]
        a, d1, d2 = chunked_candidate_top2(qb, self.state.c, cand)
        return a, d1 * d1, d2 * d2

    def _assign_stream(self, qb: jax.Array, stream):
        """Bounded assignment with per-stream warm-start Hamerly bounds
        (DESIGN.md §14): correlated query streams (KV decode) carry
        ``(a, u, lo)`` across batches keyed by stream id. On re-contact
        the bounds are inflated by the query's own motion ``‖q − q_prev‖``
        and the centers' accumulated drift since last contact
        (``c_motion`` deltas — a triangle-inequality upper bound); rows
        whose inflated ``u < lo`` provably keep their previous center
        *within the kn-restricted contract* (the second-best center is
        tracked over the routed neighborhood, the same approximation the
        router already makes) and charge 1 distance (the ‖Δq‖ norm).
        Cold rows pay the full bounded route + top-2 resolution and
        re-arm the bounds exactly. Returns (a, d1_sq, n_counted)."""
        m = qb.shape[0]
        routed, u_routed, n_scan = _route(qb, self.state.c, self.router,
                                          self.route_probes)
        a, d1_sq, d2_sq = self._resolve_top2(qb, routed)
        u_new = jnp.sqrt(d1_sq)
        lo_new = jnp.sqrt(d2_sq)
        n_nb = jnp.maximum(
            jnp.sum(self.nb_dist[routed] < 2.0 * u_routed[:, None],
                    axis=1) - 1, 0)
        n_cold = n_scan + n_nb
        rec = self._streams.get(stream)
        if rec is not None and rec["a"].shape[0] == m \
                and rec["q"].shape == qb.shape:
            drift = self.c_motion - rec["motion"]
            dq = jnp.linalg.norm(qb - rec["q"], axis=1)
            a_prev = rec["a"]
            u_b = rec["u"] + dq + drift[a_prev]
            lo_b = rec["lo"] - dq - jnp.max(
                drift[self.state.prev_nb[a_prev]], axis=1)
            warm = u_b < lo_b
            a = jnp.where(warm, a_prev, a)
            d1_sq = jnp.where(warm, u_b * u_b, d1_sq)
            u_new = jnp.where(warm, u_b, u_new)
            lo_new = jnp.where(warm, jnp.maximum(lo_b, 0.0), lo_new)
            n_counted = jnp.where(warm, 1, n_cold)
        else:
            n_counted = n_cold
        self._streams[stream] = {"q": qb, "a": a, "u": u_new,
                                 "lo": lo_new, "motion": self.c_motion}
        return a, d1_sq, n_counted

    def _predict_batch(self, qb: jax.Array, probes: int | None = None,
                       precision: str | None = None):
        """Route + resolve one batch. Returns (a, sqdist, routed,
        n_counted (m,)) with n_counted the per-query *f32* distance
        charge of the serial bounded algorithm: group scan + surviving
        members (from :func:`_route`) + resolution neighbors passing
        Elkan's ``d(nb, routed) < 2 d(q, routed)`` condition. ``probes``
        overrides ``route_probes`` (the executor's probe-shrink rung).

        ``precision="int8"`` (DESIGN.md §13) swaps both stages for the
        quantized scan + exact re-rank: assignments are identical (the
        margin machinery falls back to f32 whenever it cannot prove the
        row), n_counted shrinks to the re-ranked survivors (plus full
        fallback charges), and the int8 scan traffic is charged by
        :meth:`predict` on the separate int8/bytes lanes."""
        p = self.route_probes if probes is None else min(
            probes, self.route_groups)
        prec = precision or self.precision
        if prec not in _PRECISIONS:
            raise ValueError(f"unknown precision {prec!r}; "
                             f"expected one of {_PRECISIONS}")
        if prec == "int8":
            from ..kernels.ops import (bounded_predict_assign_int8,
                                       choose_group_bn)
            routed, u_routed, n_route = self._route_int8(qb, p)
            cq, _ = self._quant_tables()
            bn = choose_group_bn(qb.shape[0], self.k, self.d, bkn=self.bkn,
                                 itemsize=1)
            a_b, d_b, nsv, fb = bounded_predict_assign_int8(
                qb, self.state.c, cq, self.state.prev_nb, routed, bn=bn,
                bkn=self.bkn, r=_RESOLVE_RERANK, backend=self.backend,
                interpret=self.interpret)
            n_res = jnp.where(fb, self.kn,
                              jnp.minimum(nsv, _RESOLVE_RERANK))
            return a_b, d_b, routed, n_route + n_res
        routed, u_routed, n_scan = _route(qb, self.state.c, self.router, p)
        a_b, d_b = self._resolve(qb, routed)
        # the self-neighbor (distance 0) always passes 2u when u > 0, but
        # the serial algorithm already holds d(q, routed) from the routing
        # stage — don't charge it twice
        n_nb = jnp.maximum(
            jnp.sum(self.nb_dist[routed] < 2.0 * u_routed[:, None],
                    axis=1) - 1, 0)
        return a_b, d_b, routed, n_scan + n_nb

    def predict(self, queries: jax.Array, *, batch_size: int = 8192,
                counter: OpCounter | None = None,
                return_sqdist: bool = False, validate: str = "raise",
                retries: int = 3, precision: str | None = None,
                stream: str | None = None):
        """Bounded nearest-center assignment of ``queries``.

        Processes ``batch_size`` queries at a time (one compiled program:
        the tail batch is padded up). Charges the *measured* bounded
        distance count to ``counter`` (at most
        ``n * dense_distances_per_query()``); the brute-force comparator
        (:func:`core.distance.chunked_argmin_sqdist`) costs ``n * k``.
        Returns the assignment (n,) int32, plus each query's squared
        distance to it when ``return_sqdist``.

        ``precision`` overrides the model default: "int8" runs every
        scan stage (group centroids, closure member lists, resolution
        slabs) over the quantized tables and exactly re-ranks the margin
        survivors in f32 — identical assignments, ~4x less scan traffic
        (charged on ``counter.int8_ops`` / ``counter.bytes_scanned``,
        never mixed into the paper's op metric; DESIGN.md §13).

        ``validate``: "raise" (default) rejects non-finite query rows
        with an error naming them, "sanitize" zeroes them (the caller
        filters), "none" skips the check. Transient per-batch failures
        (``ft.chaos.TransientError``) are absorbed with exponential
        backoff up to ``retries`` times per batch
        (``ft.retry_transient``; absorbed failures land on
        ``counter.retries``).

        Queries may arrive in bf16/f16 (the KV-cache dtypes): they are
        upcast to f32 once, here at the boundary, so the kernel path
        never relies on silent promotion (and integer inputs are
        rejected rather than promoted).

        ``stream`` names a correlated query stream (DESIGN.md §14): the
        f32 path then carries warm-start Hamerly bounds across calls
        (:meth:`_assign_stream`) so repeat regions skip the router for
        1 counted distance per warm row. The int8 path ignores it (the
        quantized scan has its own charge model).
        """
        q = jnp.asarray(queries)
        if not jnp.issubdtype(q.dtype, jnp.floating):
            raise TypeError(f"predict queries must be floating point, "
                            f"got {q.dtype}")
        if q.dtype != jnp.float32:
            q = q.astype(jnp.float32)   # one explicit boundary upcast
        prec = precision or self.precision
        if prec not in _PRECISIONS:
            raise ValueError(f"unknown precision {prec!r}; "
                             f"expected one of {_PRECISIONS}")
        q = _validate_rows(q, validate, what="predict queries")
        nq = q.shape[0]
        if nq == 0:
            empty_a = jnp.zeros((0,), jnp.int32)
            return (empty_a, jnp.zeros((0,), jnp.float32)) \
                if return_sqdist else empty_a
        from ..ft import chaos as _chaos
        from ..ft.runtime import retry_transient
        bs = min(batch_size, nq)
        a_parts, d_parts, counted = [], [], []
        for lo in range(0, nq, bs):
            qb = q[lo:lo + bs]
            m = qb.shape[0]
            pad = bs - m
            if pad:                          # pad the tail batch
                qb = jnp.pad(qb, ((0, pad), (0, 0)))

            warm_key = (stream, lo // bs) \
                if stream is not None and prec == "f32" else None

            def _one_batch(qb=qb):
                inj = _chaos.active()
                if inj is not None:
                    inj.maybe_fail("predict")
                if warm_key is not None:
                    return self._assign_stream(qb, warm_key)
                a_b, d_b, _, n_c = self._predict_batch(qb, precision=prec)
                return a_b, d_b, n_c

            a_b, d_b, n_c = retry_transient(
                _one_batch, retries=retries, counter=counter)
            a_parts.append(a_b[:m])
            d_parts.append(d_b[:m])
            if counter is not None:           # padding rows charge nothing
                counted.append(jnp.sum(n_c[:m]))
        if counter is not None:
            n_f32 = int(sum(int(c) for c in counted))
            counter.add_distances(n_f32)
            # scan-traffic lane: dense table rows each query read — int8
            # rows cost d + 4 scale bytes (+ 4d per f32-re-ranked
            # survivor), f32 rows 4d (§2 counted-op methodology)
            dense = self.dense_distances_per_query()
            if prec == "int8":
                counter.add_int8_ops(nq * dense)
                counter.add_scan_bytes(nq * dense * (self.d + 4)
                                       + n_f32 * 4 * self.d)
            else:
                counter.add_scan_bytes(nq * dense * 4 * self.d)
        a = jnp.concatenate(a_parts) if len(a_parts) > 1 else a_parts[0]
        if not return_sqdist:
            return a
        d1 = jnp.concatenate(d_parts) if len(d_parts) > 1 else d_parts[0]
        return a, d1

    # -- partial_fit -------------------------------------------------------

    def partial_fit(self, batch: jax.Array, w: jax.Array | None = None,
                    *, counter: OpCounter | None = None,
                    validate: str = "raise",
                    on_full: str = "raise",
                    stream: str | None = None) -> jax.Array:
        """Fold one streamed mini-batch into the served clustering.

        Assigns the batch by the bounded route, applies the incremental
        per-center running-mean update, appends the rows into the
        resident arena (sparse repair; full re-sort on free-pool
        exhaustion) and refreshes the center kNN graph every
        ``refresh_every`` batches. Returns the batch assignment.

        Each distinct batch length compiles its own append program —
        stream fixed-size batches (pad with ``w=0`` rows) to stay on one
        program.

        ``validate``: "raise" (default) rejects batches carrying
        non-finite rows with an error naming the batch, "sanitize"
        quarantines those rows to weight 0 (counted on
        ``counter.sanitized_rows``), "none" skips the check.
        ``on_full``: when the batch would overflow the arena capacity,
        "raise" (default) refuses the batch; "degrade" folds it into the
        per-center Sculley statistics only — centers keep tracking the
        stream, member rows are dropped — and surfaces the degradation
        on ``self.degraded_folds`` / ``counter.degraded_folds``
        (DESIGN.md §11.5).

        Streaming semantics (DESIGN.md §14): every batch is one *stream
        epoch*. With ``window = W`` set, rows older than the W newest
        epochs are retired from the resident arena before the append —
        their (decayed) contribution is subtracted from the center
        sums/counts as an incremental delta, so at ``decay = 1`` the
        statistics bit-match a from-scratch fold of the surviving window
        — and ring ids recycle mirror slots modulo the capacity. With a
        ``half_life`` set the Sculley statistics decay by
        ``2^(-1/half_life)`` per epoch, clamped at ``count_floor``. With
        ``drift_guard`` on, per-center EWMA bands over effective counts
        and within-cluster energy flag dying/starved centers each batch,
        and at refresh cadence the worst one is re-seated by one GDI
        Lemma-1 split of the highest-energy donor
        (``ft.invariants.repair_dying_centers``). ``stream`` names a
        correlated stream and carries warm-start Hamerly bounds across
        folds (:meth:`_assign_stream`).
        """
        if on_full not in ("raise", "degrade"):
            raise ValueError(f"on_full must be 'raise' or 'degrade', "
                             f"got {on_full!r}")
        xb = jnp.asarray(batch, jnp.float32)
        if xb.ndim != 2 or xb.shape[1] != self.d:
            raise ValueError(f"batch shape {xb.shape} != (m, {self.d})")
        m = xb.shape[0]
        wb = jnp.ones((m,), jnp.float32) if w is None \
            else jnp.asarray(w, jnp.float32)

        from ..ft import chaos as _chaos
        inj = _chaos.active()
        if inj is not None:
            xb = inj.corrupt_batch(xb)
        if validate not in _VALIDATE_MODES:
            raise ValueError(f"validate must be one of {_VALIDATE_MODES}, "
                             f"got {validate!r}")
        if validate != "none":
            bad = ~jnp.isfinite(xb).all(axis=1)
            n_bad = int(jnp.sum(bad & (wb > 0)))
            if n_bad:
                if validate == "raise":
                    idx = np.flatnonzero(np.asarray(bad))[:8]
                    raise ValueError(
                        f"partial_fit batch {self.batches_seen}: {n_bad} "
                        f"non-finite rows (first at {idx.tolist()}); pass "
                        f"validate='sanitize' to quarantine them")
                xb = jnp.where(bad[:, None], 0.0, xb)
                wb = jnp.where(bad, 0.0, wb)
                if counter is not None:
                    counter.count_sanitized_rows(n_bad)

        if stream is not None:
            ab, d1_sq, n_counted = self._assign_stream(xb, ("fit", stream))
        else:
            ab, d1_sq, _, n_counted = self._predict_batch(xb)

        c_entry = self.state.c
        decay = jnp.float32(self.stream_decay)
        floor = jnp.float32(self.count_floor)
        c2, sums2, counts2 = _delta_update(
            self.state.c, self.state.sums, self.state.counts, xb, wb, ab,
            decay, floor)
        st = self.state._replace(c=c2, sums=sums2, counts=counts2,
                                 it=self.state.it + 1)

        # sliding-window eviction (DESIGN.md §14): the fold above already
        # applied this epoch's decay, so a row folded at epoch e carries
        # weight w·decay^(epoch_now − e) — resident_evict subtracts
        # exactly that, keeping the stats equal to a fold of the window
        epoch_now = self.batches_seen
        m_live = int(jnp.sum(wb > 0))
        n_ev = 0
        if self.window and self.has_arena and m_live:
            cutoff = epoch_now - self.window + 1
            if cutoff > 0:
                eg = _slot_epochs(st.pid, self.e_pts)
                pid_old = st.pid
                st, evict, n_ev_a = resident_evict(
                    st, eg, jnp.int32(cutoff), jnp.int32(epoch_now),
                    decay, floor, masters=self.x_pts)
                n_ev = int(n_ev_a)
                if n_ev:
                    self.a_pts, self.w_pts = _evict_mirrors(
                        self.a_pts, self.w_pts, pid_old, evict)
                    self.evicted_rows += n_ev
                    if counter is not None:
                        counter.count_evicted_rows(n_ev)
                        # subtracting the delta re-reduces sums/counts
                        counter.add_additions(2 * n_ev)
                        # pid + wg lanes cleared per retired slot
                        counter.add_scatter_bytes(n_ev * 8)

        resorted = False
        degraded = False
        ids = None
        if self.has_arena and m_live:
            if self.window:
                ids = _batch_ids(wb, self.rows_streamed, cap=self.capacity)
                # a recycled ring id whose previous occupant is still live
                # means the window outgrew the capacity
                clash = int(jnp.sum(jnp.where(
                    ids >= 0,
                    self.w_pts[jnp.clip(ids, 0, self.capacity - 1)] > 0,
                    False)))
                full = clash > 0
                full_msg = (
                    f"arena ring full: {clash} of {m_live} batch rows "
                    f"would overwrite live rows (window {self.window} "
                    f"epochs x batch size > capacity {self.capacity})")
            else:
                full = self.n_rows + m_live > self.capacity
                full_msg = (f"arena full: {self.n_rows} rows + batch "
                            f"{m_live} > capacity {self.capacity}")
            if full:
                if on_full == "raise":
                    raise ValueError(full_msg)
                # graceful degradation: the Sculley stats fold above
                # already absorbed the batch; skip the member append
                degraded = True
                self.degraded_folds += 1
                if counter is not None:
                    counter.count_degraded_fold()
        if self.has_arena and m_live and not degraded:
            if ids is None:
                ids = _batch_ids(wb, self.n_rows)
            self.x_pts, self.a_pts, self.w_pts, self.e_pts = \
                _update_mirrors(self.x_pts, self.a_pts, self.w_pts,
                                self.e_pts, xb, wb, ab, ids, epoch_now)
            if inj is not None:
                st = inj.corrupt_arena(st)
            xg, pid, wg, b2c, fill, openb, ok = _arena_try_append(
                st, xb, wb, ab, ids, bn=self.bn, cap=self.capacity)
            if not bool(ok):
                resorted = True
                xg, pid, wg, b2c, fill, openb = _arena_resort(
                    self.x_pts, self.a_pts, self.w_pts, k=self.k,
                    bn=self.bn, nbt=st.b2c.shape[0])
            st = st._replace(xg=xg, pid=pid, wg=wg, b2c=b2c, fill=fill,
                             openb=openb)
            self.n_rows = min(self.rows_streamed + m_live, self.capacity) \
                if self.window else self.n_rows + m_live
        self.rows_streamed += m_live

        self.batches_seen += 1
        self.state = st

        dying = None
        if self.drift_guard and m_live:
            from ..ft import invariants as _inv
            if self._dg is None:
                self._dg = _inv.init_drift_guard(self.k)
            eb = jax.ops.segment_sum(jnp.maximum(d1_sq, 0.0) * wb, ab,
                                     num_segments=self.k)
            self._dg, dying = _inv.drift_guard_step(
                self._dg, self.state.counts, eb, floor)

        refreshed = self.batches_seen % self.refresh_every == 0
        if refreshed and dying is not None and bool(jnp.any(dying)):
            from ..ft.invariants import repair_dying_centers
            self.repaired_centers += repair_dying_centers(
                self, dying, counter=counter)
        if refreshed:
            # center-derived structures re-sync with the drifted centers:
            # the kNN graph (resolution) and the closure router (routing)
            nb, self.nb_dist = _graph_with_dists(self.state.c, self.kn)
            self.state = self.state._replace(prev_nb=nb)
            self.router = _build_router(
                self.state.c, self.route_groups, self.route_cap,
                self.router_iters)
        self._qt = None     # centers drifted: quantized tables are stale
        # accumulated per-center drift: one net-displacement increment per
        # fold (a triangle-inequality upper bound on total motion) — the
        # warm-start stream bounds inflate by deltas of this clock
        self.c_motion = self.c_motion + jnp.linalg.norm(
            self.state.c - c_entry, axis=1)

        if counter is not None:
            # w=0 padding rows (the fixed-batch-size idiom) charge nothing
            counter.add_distances(int(jnp.sum(jnp.where(wb > 0, n_counted,
                                                        0))))
            counter.add_additions(2 * m_live)       # incremental delta
            if refreshed:                           # graph + router build
                counter.add_distances(
                    self.k * self.k
                    + (self.router_iters + 1) * self.route_groups * self.k)
            if self.has_arena and m_live and not degraded:
                moved = self.capacity if resorted else m_live
                row_bytes = (self.d + LAYOUT_STATE_LANES) * 4
                counter.add_gather_bytes(moved * row_bytes)
                counter.add_scatter_bytes(moved * row_bytes)
                if resorted:
                    counter.add_sort_bytes(
                        moved * 8 * max(1.0, math.log2(max(moved, 2))))
        return ab

    # -- checkpointing -----------------------------------------------------

    def _config(self) -> dict:
        return {"k": self.k, "d": self.d, "kn": self.kn, "bn": self.bn,
                "nbt": int(self.state.b2c.shape[0]),
                "capacity": self.capacity, "backend": self.backend,
                "bkn": self.bkn, "route_groups": self.route_groups,
                "route_cap": self.route_cap,
                "route_probes": self.route_probes,
                "router_iters": self.router_iters,
                "refresh_every": self.refresh_every, "decay": self.decay,
                "precision": self.precision,
                "n_rows": self.n_rows, "batches_seen": self.batches_seen,
                # streaming config + decay clock (DESIGN.md §14); the
                # stream_v2 flag gates the extra tree leaves so pre-§14
                # checkpoints keep their leaf count and restore unchanged
                "stream_v2": True,
                "window": self.window, "half_life": self.half_life,
                "count_floor": self.count_floor,
                "drift_guard": self.drift_guard,
                "rows_streamed": self.rows_streamed,
                "evicted_rows": self.evicted_rows,
                "repaired_centers": self.repaired_centers,
                "degraded_folds": self.degraded_folds}

    def _tree(self) -> dict:
        tree = {"state": self.state, "router": self.router,
                "nb_dist": self.nb_dist, "x_pts": self.x_pts,
                "a_pts": self.a_pts, "w_pts": self.w_pts,
                # stream_v2 leaves: the per-row epoch mirror (the decay /
                # eviction clock) and the cumulative center-drift clock
                "stream": {"e_pts": self.e_pts, "c_motion": self.c_motion}}
        if self.precision == "int8":
            # quantization scales ride the checkpoint (DESIGN.md §13):
            # restore recomputes the tables from the centers and verifies
            # the stored scales match — a mismatch means centers and
            # quantized tables came from different models. f32 models
            # keep the old leaf count, so existing checkpoints restore.
            cq, gq = self._quant_tables()
            tree["qscale"] = {"c": cq.scale, "gc": gq.scale}
        return tree

    @classmethod
    def _like_tree(cls, cfg: dict) -> dict:
        k, d, kn = cfg["k"], cfg["d"], cfg["kn"]
        nbt, bn, cap = cfg["nbt"], cfg["bn"], cfg["capacity"]
        s = nbt * bn if nbt else 0
        f32, i32 = jnp.float32, jnp.int32
        state = ResidentState(
            c=jnp.zeros((k, d), f32), prev_nb=jnp.zeros((k, kn), i32),
            sums=jnp.zeros((k, d), f32), counts=jnp.zeros((k,), f32),
            it=jnp.zeros((), i32), first=jnp.array(False),
            xg=jnp.zeros((s, d), f32), pid=jnp.zeros((s,), i32),
            ug=jnp.zeros((s,), f32), lo_g=jnp.zeros((s,), f32),
            wg=jnp.zeros((s,), f32), b2c=jnp.zeros((nbt,), i32),
            fill=jnp.zeros((k,), i32), openb=jnp.zeros((k,), i32))
        g, rcap = cfg["route_groups"], cfg["route_cap"]
        router = Router(gc=jnp.zeros((g, d), f32),
                        members=jnp.zeros((g, rcap), i32),
                        mdist=jnp.zeros((g, rcap), f32),
                        mowner=jnp.zeros((g, rcap), i32),
                        modist=jnp.zeros((g, rcap), f32))
        tree = {"state": state, "router": router,
                "nb_dist": jnp.zeros((k, kn), f32),
                "x_pts": jnp.zeros((cap, d), f32),
                "a_pts": jnp.zeros((cap,), i32),
                "w_pts": jnp.zeros((cap,), f32)}
        if cfg.get("stream_v2"):
            tree["stream"] = {"e_pts": jnp.zeros((cap,), i32),
                              "c_motion": jnp.zeros((k,), f32)}
        if cfg.get("precision", "f32") == "int8":
            tree["qscale"] = {"c": jnp.zeros((k,), f32),
                              "gc": jnp.zeros((g,), f32)}
        return tree

    def save(self, ckpt_dir: str, step: int = 0) -> str:
        """Atomic checkpoint of the full model (arrays + config)."""
        from ..checkpoint import save_checkpoint
        return save_checkpoint(ckpt_dir, step, self._tree(),
                               extra_meta={"kmeans_model": self._config()})

    @classmethod
    def restore(cls, ckpt_dir: str, step: int | None = None) -> "KMeansModel":
        from ..checkpoint import latest_step, load_meta, restore_checkpoint
        if step is None:
            step = latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
        cfg = load_meta(ckpt_dir, step)["extra"]["kmeans_model"]
        tree = restore_checkpoint(ckpt_dir, step, cls._like_tree(cfg))
        stream = tree.get("stream", {})
        model = cls(state=tree["state"], router=tree["router"],
                    nb_dist=tree["nb_dist"], x_pts=tree["x_pts"],
                    a_pts=tree["a_pts"], w_pts=tree["w_pts"],
                    kn=cfg["kn"], bn=cfg["bn"], backend=cfg["backend"],
                    bkn=cfg["bkn"], route_probes=cfg["route_probes"],
                    router_iters=cfg["router_iters"],
                    refresh_every=cfg["refresh_every"],
                    decay=cfg["decay"],
                    precision=cfg.get("precision", "f32"),
                    n_rows=cfg["n_rows"],
                    batches_seen=cfg["batches_seen"],
                    window=cfg.get("window", 0),
                    half_life=cfg.get("half_life", 0.0),
                    count_floor=cfg.get("count_floor", 0.0),
                    drift_guard=cfg.get("drift_guard", False),
                    rows_streamed=cfg.get("rows_streamed", cfg["n_rows"]),
                    evicted_rows=cfg.get("evicted_rows", 0),
                    repaired_centers=cfg.get("repaired_centers", 0),
                    degraded_folds=cfg.get("degraded_folds", 0),
                    e_pts=stream.get("e_pts"),
                    c_motion=stream.get("c_motion"))
        if "qscale" in tree:
            # rebuild the quantized tables from the restored centers and
            # verify the checkpointed scales (see _tree)
            cq, gq = model._quant_tables()
            if not (bool(jnp.array_equal(cq.scale, tree["qscale"]["c"]))
                    and bool(jnp.array_equal(gq.scale,
                                             tree["qscale"]["gc"]))):
                from ..checkpoint import CheckpointCorruptError
                raise CheckpointCorruptError(
                    f"checkpoint step {step}: stored quantization scales "
                    f"do not match tables recomputed from the restored "
                    f"centers")
        return model


@functools.partial(jax.jit, static_argnames=("chunk",))
def _resolve_xla(q, c, neighbors, routed, chunk: int = 2048):
    cand = neighbors[routed]                                # (m, kn)
    return chunked_candidate_argmin(q, c, cand, chunk=chunk)


__all__ = ["KMeansModel", "Router"]
