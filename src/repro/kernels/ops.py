"""jit'd public wrappers around the Pallas kernels: padding, block-size
selection (VMEM budget), cluster-grouped layout construction, and the
call-time choice between compiled kernels (TPU) and the Pallas
interpreter (any other backend), so the same call sites run on both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import quant
from .candidate_assign import (PAD_SQDIST, candidate_assign,
                               candidate_assign_int8_tiled,
                               candidate_assign_rowwise,
                               candidate_assign_tiled, candidate_tables,
                               pad_candidates, rowwise_grid_steps,
                               tiled_grid_steps)
from .cluster_attend import (cluster_attend, cluster_major_pack,
                             select_clusters)
from .center_knn import center_knn, center_sqdist
from .distance_argmin import distance_argmin
from .segmented_scan import segmented_scan as _segmented_scan_kernel

_VMEM_BUDGET = 12 * 2 ** 20 // 4          # ~12 MiB of f32 working set


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The kernels' ``interpret`` flag, resolved when the call runs (never
    at import, which would start a backend): an explicit value wins;
    ``None`` compiles the kernels on a TPU and runs them in the Pallas
    interpreter on any other backend."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def choose_blocks(d: int, k: int):
    """Pick (bn, bk) so bn*d + bk*d + 2*bn*bk floats fit the VMEM budget,
    keeping MXU-aligned multiples of 128 where possible; very large d
    (e.g. yale's 32256) shrinks both block dims."""
    for bk in (128, 64, 32, 16, 8):
        if k < 128 and bk > max(8, k):
            continue
        for bn in (512, 256, 128, 64, 32, 16, 8):
            if bn * d + bk * d + 2 * bn * bk <= _VMEM_BUDGET:
                return bn, bk
    return 8, 8


def choose_group_bn(n: int, k: int, d: int | None = None,
                    bn_max: int = 128, bkn: int = 8,
                    itemsize: int = 4) -> int:
    """Point-block size for the cluster-grouped layout: the largest power of
    two <= the expected cluster size n/k (clamped to [8, bn_max]), so the
    per-cluster padding overhead stays bounded even at small n/k.

    When ``d`` is given the block additionally respects the VMEM budget the
    same way :func:`choose_blocks` does — the tiled kernel holds a (bn, d)
    point tile, a (bkn, d) candidate slab and ~4 bn-length f32 scratch
    lanes per step, so huge-d inputs (e.g. the yale config, d=32256) must
    shrink bn below the n/k heuristic or the tile overflows the budget.
    ``itemsize`` is the element byte width of the point/candidate tiles
    (1 for the int8 scan, 2 for bf16/f16 inputs, 4 for f32): the budget is
    counted in bytes, so narrower tiles earn proportionally larger bn
    instead of being charged as if they were f32."""
    per = max(8, n // max(k, 1))
    cap = bn_max
    if d is not None:
        budget = _VMEM_BUDGET * 4                   # bytes
        while cap > 8 and \
                (cap * d + bkn * d) * itemsize + 4 * cap * 4 > budget:
            cap //= 2
    bn = 8
    while bn * 2 <= min(per, cap):
        bn *= 2
    return bn


def _pad_rows(x, mult):
    n = x.shape[0]
    pad = (-n) % mult
    return (jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)), n)


def assign_nearest_pallas(x: jax.Array, c: jax.Array,
                          interpret: bool | None = None):
    """Drop-in fused assignment: (n,d),(k,d) -> (a (n,), sqdist (n,))."""
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    k = c.shape[0]
    bn, bk = choose_blocks(d, k)
    xp, n0 = _pad_rows(x, bn)
    cp, k0 = _pad_rows(c, bk)
    if k0 < cp.shape[0]:  # pad centers far away so they never win
        cp = cp.at[k0:].set(jnp.full((cp.shape[0] - k0, d), 1e30, cp.dtype))
    a, dist = distance_argmin(xp, cp, bn=bn, bk=bk, interpret=interpret)
    return a[:n0], dist[:n0]


def grouped_capacity(n: int, k: int, bn: int) -> int:
    """Static block capacity of the grouped layout: every cluster adds at
    most one partial block on top of the ceil(n/bn) data blocks."""
    return -(-n // bn) + k


def _cluster_pack(a: jax.Array, k: int, bn: int, nb_total: int):
    """Shared packing math of the grouped layout (DESIGN.md §3.3): stable
    argsort by cluster, every cluster padded to a bn multiple, inside an
    ``nb_total``-block arena. Returns (perm (nb_total*bn,) int32 with -1
    padding, b2c (nb_total,) int32 — valid for blocks below the packed
    extent, clamped to k-1 beyond it —, sizes (k,), sizes_pad (k,),
    starts_pad (k,)). Both layout builders (per-iteration
    :func:`group_by_cluster_device` and resident
    :func:`resident_regroup`) are thin wrappers so a packing fix can
    never break rebuild/resident parity."""
    n = a.shape[0]
    order = jnp.argsort(a, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(a, length=k)                       # (k,)
    sizes_pad = ((sizes + bn - 1) // bn) * bn               # empty -> 0 blocks
    starts_data = jnp.cumsum(sizes) - sizes                 # exclusive cumsum
    starts_pad = jnp.cumsum(sizes_pad) - sizes_pad
    ci = a[order]                                           # sorted cluster id
    rank = jnp.arange(n, dtype=jnp.int32) - starts_data[ci].astype(jnp.int32)
    dest = starts_pad[ci].astype(jnp.int32) + rank
    perm = jnp.full((nb_total * bn,), -1, jnp.int32).at[dest].set(order)
    bounds = jnp.cumsum(sizes_pad)                          # inclusive
    block_starts = jnp.arange(nb_total, dtype=bounds.dtype) * bn
    b2c = jnp.searchsorted(bounds, block_starts, side="right")
    b2c = jnp.minimum(b2c, k - 1).astype(jnp.int32)
    return perm, b2c, sizes, sizes_pad, starts_pad


@functools.partial(jax.jit, static_argnames=("k", "bn"))
def group_by_cluster_device(a: jax.Array, k: int, bn: int):
    """Device-side layout pass: sort point ids by cluster, pad every cluster
    to a bn multiple. Shapes are static (capacity = grouped_capacity(n,k,bn)
    blocks) so this jits and fuses into the k²-means device step — no host
    roundtrip between iterations. Returns (perm (cap*bn,) int32 with -1
    padding, block2cluster (cap,) int32; trailing capacity blocks beyond the
    data are all-padding with block2cluster clamped into range).
    """
    nbcap = grouped_capacity(a.shape[0], k, bn)
    perm, b2c, _, _, _ = _cluster_pack(a, k, bn, nbcap)
    return perm, b2c


def group_by_cluster(a: np.ndarray, k: int, bn: int):
    """Host-side layout pass (reference implementation of
    group_by_cluster_device, without the trailing all-padding capacity
    blocks). Returns (perm (n_pad,) int32 with -1 padding,
    block2cluster (nb,) int32)."""
    order = np.argsort(a, kind="stable")
    sizes = np.bincount(a, minlength=k)
    perm_blocks, block2cluster = [], []
    off = 0
    for j in range(k):
        sz = int(sizes[j])
        if sz == 0:
            continue
        ids = order[off:off + sz]
        off += sz
        pad = (-sz) % bn
        ids = np.concatenate([ids, np.full(pad, -1, np.int64)])
        perm_blocks.append(ids)
        block2cluster += [j] * (len(ids) // bn)
    perm = np.concatenate(perm_blocks).astype(np.int32)
    return perm, np.asarray(block2cluster, np.int32)


def scatter_from_grouped(perm: jax.Array, values: jax.Array,
                         prev: jax.Array) -> jax.Array:
    """Scatter grouped-layout ``values`` (one per perm row) back to original
    point order on top of ``prev``. Padding rows (perm == -1) are routed to
    an out-of-range index and dropped — a duplicate ``.at[0].set`` from
    padding rows would race with point 0's real row."""
    n = prev.shape[0]
    idx = jnp.where(perm >= 0, perm, n)
    return prev.at[idx].set(values, mode="drop")


# ---------------------------------------------------------------------------
# Resident grouped layout (DESIGN.md §9): the cluster-grouped layout as a
# persistent, incrementally repaired structure instead of a per-iteration
# rebuild. Blocks need not be cluster-contiguous — the tiled kernel only
# requires every point in a block to share the block's cluster (its rowsel
# entry), so repairs move rows between blocks without re-sorting.
# ---------------------------------------------------------------------------


def resident_capacity(n: int, k: int, bn: int, spare: int | None = None) -> int:
    """Static block capacity of the resident layout.

    ``grouped_capacity`` is the re-sort worst case (every cluster size a bn
    multiple); real assignments leave most of the +k partial-block slack
    unused, and those unused blocks are the free pool the sparse repairs
    allocate from. ``spare`` adds explicit headroom blocks on top (default
    0: extra blocks enlarge the kernel grid, and a repair that would
    exhaust the pool falls back to a full re-sort anyway)."""
    return grouped_capacity(n, k, bn) + (spare or 0)


@functools.partial(jax.jit, static_argnames=("k", "bn", "nb_total"))
def resident_regroup(a: jax.Array, k: int, bn: int, nb_total: int):
    """Full layout (re)build with resident free-slot metadata.

    Same packing as :func:`group_by_cluster_device` (stable argsort by
    cluster, every cluster padded to a bn multiple) inside a fixed
    ``nb_total``-block arena, but with the resident-layout bookkeeping:
    unowned blocks carry ``b2c == -1`` (the free pool), and every cluster's
    append watermark is returned so sparse repairs can allocate without
    re-sorting. Returns ``(perm (nb_total*bn,), b2c (nb_total,),
    fill (k,), openb (k,))`` where ``perm`` holds point ids (-1 = free
    slot), ``openb[c]`` is cluster c's open (append) block (-1 when the
    cluster is empty) and ``fill[c]`` its watermark in (0, bn] (0 when
    empty): slots >= fill of the open block have never been appended to
    since the last re-sort and are guaranteed free."""
    perm, b2c, sizes, sizes_pad, starts_pad = _cluster_pack(a, k, bn,
                                                            nb_total)
    used = (jnp.sum(sizes_pad) // bn).astype(jnp.int32)     # owned blocks
    b2c = jnp.where(jnp.arange(nb_total) < used, b2c, -1).astype(jnp.int32)
    empty = sizes == 0
    openb = jnp.where(empty, -1,
                      (starts_pad + sizes_pad) // bn - 1).astype(jnp.int32)
    fill = jnp.where(empty, 0, sizes - (sizes_pad - bn)).astype(jnp.int32)
    return perm, b2c, fill, openb


def plan_layout_repair(b2c: jax.Array, fill: jax.Array, openb: jax.Array,
                       active: jax.Array, dst: jax.Array, *, bn: int):
    """Vectorized append-only slot allocation for a batch of moved rows.

    ``active`` (M,) flags the live lanes of the move buffer and ``dst``
    (M,) their destination clusters. Each move is appended at its
    cluster's watermark: first into the remaining free tail of the open
    block, then into fresh blocks popped from the free pool (``b2c ==
    -1``), lowest block id first. Departing rows are *not* reclaimed —
    they become holes below the watermark that only the next full
    re-sort (:func:`resident_regroup`) repacks (DESIGN.md §9).

    Returns ``(dst_slot, b2c', fill', openb', total_new, n_free)`` where
    ``dst_slot`` (M,) carries the allocated slot per lane (inactive lanes
    get the out-of-range sentinel ``nb*bn``, for ``mode="drop"``
    scatters) and ``total_new``/``n_free`` let the caller detect pool
    exhaustion (``total_new > n_free``) *before* committing — the
    returned arrays are only valid when the pool sufficed.
    """
    k = fill.shape[0]
    nbt = b2c.shape[0]
    sentinel = nbt * bn
    m = dst.shape[0]
    seg = jnp.where(active, dst, k)
    inc = jax.ops.segment_sum(active.astype(jnp.int32), seg,
                              num_segments=k + 1)[:k]
    # rank of each move within its destination cluster (stable in lane
    # order so repair results are deterministic)
    order = jnp.argsort(seg, stable=True)
    sd = seg[order]
    starts = jnp.searchsorted(sd, sd, side="left")
    rank = jnp.zeros((m,), jnp.int32).at[order].set(
        (jnp.arange(m) - starts).astype(jnp.int32))
    rem = jnp.where(openb >= 0, bn - fill, 0)               # (k,) open tail
    nf = (jnp.maximum(inc - rem, 0) + bn - 1) // bn         # fresh blocks
    total_new = jnp.sum(nf)
    free_mask = b2c < 0
    n_free = jnp.sum(free_mask)
    free_list = jnp.nonzero(free_mask, size=nbt,
                            fill_value=nbt)[0].astype(jnp.int32)
    base = jnp.cumsum(nf) - nf                              # exclusive
    # per-lane placement
    c_m = jnp.where(active, dst, 0)
    rem_m = rem[c_m]
    in_open = rank < rem_m
    r2 = jnp.maximum(rank - rem_m, 0)
    blk_fresh = free_list[jnp.minimum(base[c_m] + r2 // bn, nbt - 1)]
    blk = jnp.where(in_open, openb[c_m], blk_fresh)
    off = jnp.where(in_open, fill[c_m] + rank, r2 % bn)
    dst_slot = jnp.where(active, blk * bn + off, sentinel).astype(jnp.int32)
    # commit ownership of the allocated fresh blocks + new watermarks
    alloc_blk = jnp.where(active & ~in_open, blk_fresh, nbt)
    b2c2 = b2c.at[alloc_blk].set(c_m.astype(jnp.int32), mode="drop")
    grew = inc > rem
    last_fresh = free_list[jnp.minimum(base + jnp.maximum(nf - 1, 0),
                                       nbt - 1)]
    openb2 = jnp.where(grew, last_fresh, openb).astype(jnp.int32)
    fill2 = jnp.where(grew, inc - rem - (nf - 1) * bn,
                      jnp.where(inc > 0, fill + inc, fill)).astype(jnp.int32)
    return dst_slot, b2c2, fill2, openb2, total_new, n_free


@functools.partial(jax.jit, static_argnames=())
def plan_layout_evict(pid: jax.Array, wg: jax.Array, eg: jax.Array,
                      cutoff: jax.Array):
    """Sliding-window eviction plan over the resident arena (DESIGN.md
    §14): retire every *live* slot whose stream epoch predates
    ``cutoff``.

    ``pid``/``wg`` are the arena slot arrays, ``eg`` (S,) the per-slot
    stream epoch (any value on free/parked slots — only live slots,
    ``pid >= 0 and wg > 0``, are eligible). Eviction rides
    :func:`plan_layout_repair`'s hole machinery in reverse: a retired
    slot becomes a hole below its cluster's watermark (``pid = -1``,
    ``wg = 0``) exactly like a departing row of a sparse repair, so
    nothing else moves — ``b2c``/``fill``/``openb`` are untouched and
    the holes are reclaimed only by the next full
    :func:`resident_regroup`. Returns ``(evict (S,) bool, pid2, wg2,
    n_evicted)``; the caller subtracts the evicted rows from the center
    sums/counts as an incremental delta (``core.engine.resident_evict``)
    so the fit trajectory matches a from-scratch fit on the surviving
    window."""
    live = (pid >= 0) & (wg > 0)
    evict = live & (eg < cutoff)
    pid2 = jnp.where(evict, -1, pid).astype(jnp.int32)
    wg2 = jnp.where(evict, 0.0, wg).astype(wg.dtype)
    n_evicted = jnp.sum(evict.astype(jnp.int32))
    return evict, pid2, wg2, n_evicted


def k2_bounded_assign(x: jax.Array, c: jax.Array, neighbors: jax.Array,
                      a: jax.Array, u: jax.Array, lo: jax.Array,
                      need: jax.Array, *, bn: int, bkn: int = 8,
                      interpret: bool | None = None):
    """Bound-gated grouped tiled assignment — the Pallas inner loop of the
    *rebuild-residency* k²-means iteration (engine layer, DESIGN.md §3 +
    §8; the resident iteration of §9 drives the tiled kernel directly
    over its carried layout instead of rebuilding one here).

    Builds the cluster-grouped layout on device, derives the per-block
    Hamerly skip flags from ``need`` (a block is skipped iff no point in it
    needs recomputation), runs the tiled candidate kernel, and refreshes
    the true-distance bounds only on fresh (recomputed) lanes so stale
    lanes avoid the sqrt(u^2) roundtrip. u/lo are true distances in and
    out. Returns (a_new, u_new, lo_new) in original point order.
    """
    n = x.shape[0]
    k = c.shape[0]
    perm, b2c = group_by_cluster_device(a, k, bn)
    valid = perm >= 0
    safe_perm = jnp.maximum(perm, 0)
    needp = need[safe_perm] & valid
    nb = perm.shape[0] // bn
    # trailing all-padding capacity blocks are skipped for free (needp all
    # False)
    skip = (~jnp.any(needp.reshape(nb, bn), axis=1)).astype(jnp.int32)
    a_new, d1_sq, d2_sq = k2_assign_grouped(
        x, c, neighbors, perm, b2c, skip, a, u * u, lo * lo,
        bn=bn, bkn=bkn, interpret=interpret)
    fresh = scatter_from_grouped(perm, jnp.repeat(skip == 0, bn),
                                 jnp.zeros((n,), bool))
    u_new = jnp.where(fresh, jnp.sqrt(d1_sq), u)
    lo_new = jnp.where(fresh, jnp.sqrt(d2_sq), lo)
    return a_new, u_new, lo_new


@functools.partial(jax.jit, static_argnames=("bn", "bkn", "interpret"))
def bounded_predict_assign(q: jax.Array, c: jax.Array, neighbors: jax.Array,
                           routed: jax.Array, *, bn: int = 128, bkn: int = 8,
                           interpret: bool | None = None):
    """Query-time analogue of :func:`k2_bounded_assign` (DESIGN.md §10):
    resolve routed queries against their route center's k_n-neighborhood
    through the bkn-tiled candidate kernel.

    q: (m, d) queries; c: (k, d) centers; neighbors: (k, kn) per-center
    candidate lists (self-inclusive); routed: (m,) int32 route center per
    query (from the kNN-graph descent). Queries are grouped by route
    center on device so every point block shares one candidate list —
    the same layout contract as the fit-time iteration — and only blocks
    that hold at least one real query compute (all-padding capacity
    blocks ride the skip flag). Returns (assignment (m,) int32,
    best squared distance (m,) f32) in query order.
    """
    m = q.shape[0]
    k = c.shape[0]
    perm, b2c = group_by_cluster_device(routed, k, bn)
    nb = perm.shape[0] // bn
    skip = (~jnp.any((perm >= 0).reshape(nb, bn), axis=1)).astype(jnp.int32)
    zeros = jnp.zeros((m,), jnp.float32)
    a, d1, _ = k2_assign_grouped(q, c, neighbors, perm, b2c, skip,
                                 routed.astype(jnp.int32), zeros, zeros,
                                 bn=bn, bkn=bkn, interpret=interpret)
    return a, d1


@functools.partial(jax.jit, static_argnames=("bn", "bkn", "interpret"))
def bounded_predict_assign_top2(q: jax.Array, c: jax.Array,
                                neighbors: jax.Array, routed: jax.Array,
                                *, bn: int = 128, bkn: int = 8,
                                interpret: bool | None = None):
    """:func:`bounded_predict_assign` that also returns the second-best
    squared distance within the resolved k_n-neighborhood — the Hamerly
    lower bound the per-stream warm-start machinery carries across
    batches (DESIGN.md §14). Returns (assignment (m,), best sqdist (m,),
    second-best sqdist (m,)) in query order."""
    m = q.shape[0]
    k = c.shape[0]
    perm, b2c = group_by_cluster_device(routed, k, bn)
    nb = perm.shape[0] // bn
    skip = (~jnp.any((perm >= 0).reshape(nb, bn), axis=1)).astype(jnp.int32)
    zeros = jnp.zeros((m,), jnp.float32)
    return k2_assign_grouped(q, c, neighbors, perm, b2c, skip,
                             routed.astype(jnp.int32), zeros, zeros,
                             bn=bn, bkn=bkn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bn", "bkn", "r", "backend",
                                             "interpret"))
def quantized_scan_rerank(xf: jax.Array, xq: jax.Array, xsc: jax.Array,
                          c: jax.Array, cq, cidx: jax.Array,
                          rowsel: jax.Array, skip: jax.Array,
                          prev_a: jax.Array, prev_d1: jax.Array,
                          prev_d2: jax.Array, *, bn: int = 128,
                          bkn: int = 8, r: int = 8,
                          backend: str = "pallas",
                          interpret: bool | None = None):
    """Int8 approximate scan + exact f32 re-rank (DESIGN.md §13) — the
    drop-in quantized replacement for :func:`candidate_assign_tiled`.

    xf: (n, d) f32 master rows (grouped layout; the re-rank reads these),
    xq/xsc their int8 quantization; c: (k, d) f32 centers; cq: a
    quant.CenterQuant of ``c``; cidx: (T, kn_pad) candidate ids;
    rowsel/skip/prev_* exactly as in the f32 kernel. The int8 stage (the
    Pallas survivor kernel on backend="pallas", the chunked jnp scan on
    "xla") emits per-row survivor sets under the quantization margin
    bound; survivors are re-ranked in exact f32 with the oracle's
    formula, so the returned argmins are bit-identical to the f32 path.
    Rows whose survivor set overflows ``r`` fall back to an exact f32
    pass over their full candidate list (lax.cond — free when no row
    overflows). Returns (a (n,), d1_sq (n,), d2_sq (n,), n_surv (n,),
    fallback (n,) bool); d2_sq is the exact second-best among survivors
    floored by the non-survivor margin bound — a valid (possibly looser)
    Hamerly lower bound, never an invalid one."""
    interpret = resolve_interpret(interpret)
    n, d = xf.shape
    nb = n // bn
    # exact per-row residual norms: the margin's query radius (the f32
    # masters are already here for the re-rank, so this is one cheap
    # elementwise pass — no extra memory traffic lane)
    xerr = jnp.linalg.norm(
        xf - xq.astype(jnp.float32) * xsc[:, None], axis=1)
    if backend == "pallas":
        qtab, qsc, qerrtab, csqtab = quant.quantized_candidate_slabs(
            cq, cidx)
        surv, nsv, lbm = candidate_assign_int8_tiled(
            xq, xsc, xerr, qtab, qsc, qerrtab, csqtab, rowsel, skip,
            bn=bn, bkn=bkn, r=r, interpret=interpret)
    else:
        cand_rows = cidx[rowsel]                     # (nb, kn_pad)
        surv, nsv, lbm = quant.approx_scan(
            xq, xsc, xerr, cq, jnp.repeat(cand_rows, bn, axis=0), r=r)
    fresh = jnp.repeat(skip == 0, bn)
    nsv = jnp.where(fresh, nsv, 0)
    cand_all = cidx[jnp.repeat(rowsel, bn)]          # (n, kn_pad)
    ids = jnp.where(surv >= 0,
                    jnp.take_along_axis(cand_all, jnp.maximum(surv, 0),
                                        axis=1), -1)
    sq = quant.rerank_exact(xf, c, ids)
    a_sv, d1_sv, d2_sv = quant.first_min_top2(sq, ids)
    lo_rest = jnp.square(
        jnp.maximum(jnp.minimum(lbm, 1e15) - xerr, 0.0))
    d2_sv = jnp.minimum(d2_sv, lo_rest)
    fb = fresh & (nsv > r)
    a_f, d1_f, d2_f = jax.lax.cond(
        jnp.any(fb),
        lambda: quant.full_candidate_top2_sq(xf, c, cand_all),
        lambda: (a_sv, d1_sv, d2_sv))
    a_new = jnp.where(fb, a_f, a_sv)
    d1_new = jnp.where(fb, d1_f, d1_sv)
    d2_new = jnp.where(fb, d2_f, d2_sv)
    return (jnp.where(fresh, a_new, prev_a).astype(jnp.int32),
            jnp.where(fresh, d1_new, prev_d1),
            jnp.where(fresh, d2_new, prev_d2),
            nsv, fb)


@functools.partial(jax.jit, static_argnames=("bn", "bkn", "r", "backend",
                                             "interpret"))
def bounded_predict_assign_int8(q: jax.Array, c: jax.Array, cq,
                                neighbors: jax.Array, routed: jax.Array,
                                *, bn: int = 128, bkn: int = 8, r: int = 8,
                                backend: str = "pallas",
                                interpret: bool | None = None):
    """Quantized-resolution analogue of :func:`bounded_predict_assign`:
    routed queries resolve against their route center's k_n-neighborhood
    through the int8 scan + exact f32 re-rank instead of the f32 kernel.

    cq: quant.CenterQuant of ``c`` (callers cache it across batches).
    Returns (assignment (m,), best sqdist (m,), n_surv (m,),
    fallback (m,) bool) in query order — the survivor/fallback lanes feed
    the counted f32-distance charge (only re-ranked candidates cost f32
    distances; the dense int8 scan is charged on its own lane)."""
    m = q.shape[0]
    k = c.shape[0]
    cidx = pad_candidates(neighbors.astype(jnp.int32), bkn)
    perm, b2c = group_by_cluster_device(routed, k, bn)
    nb = perm.shape[0] // bn
    skip = (~jnp.any((perm >= 0).reshape(nb, bn), axis=1)).astype(jnp.int32)
    safe_perm = jnp.maximum(perm, 0)
    qg = q[safe_perm]
    qq, qs = quant.quantize_rows(qg)
    pa = routed.astype(jnp.int32)[safe_perm]
    zeros_g = jnp.zeros((perm.shape[0],), jnp.float32)
    a_g, d1_g, _, nsv_g, fb_g = quantized_scan_rerank(
        qg, qq, qs, c, cq, cidx, b2c, skip, pa, zeros_g, zeros_g,
        bn=bn, bkn=bkn, r=r, backend=backend, interpret=interpret)
    a = scatter_from_grouped(perm, a_g, routed.astype(jnp.int32))
    d1 = scatter_from_grouped(perm, d1_g, jnp.zeros((m,), jnp.float32))
    nsv = scatter_from_grouped(perm, nsv_g, jnp.zeros((m,), jnp.int32))
    fb = scatter_from_grouped(perm, fb_g, jnp.zeros((m,), bool))
    return a, d1, nsv, fb


def segmented_scan(x: jax.Array, w: jax.Array, block2seg: jax.Array,
                   *, bn: int = 128, interpret: bool | None = None):
    """Segmented inclusive scan of (x, ||x||^2, 1) over the cluster-grouped
    layout (see kernels/segmented_scan.py for the contract); interpret mode
    auto-selected off-TPU."""
    interpret = resolve_interpret(interpret)
    return _segmented_scan_kernel(x, w, block2seg, bn=bn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bn", "bkn", "interpret"))
def k2_assign_grouped(x: jax.Array, c: jax.Array, neighbors: jax.Array,
                      perm: jax.Array, block2cluster: jax.Array,
                      skip: jax.Array, prev_a: jax.Array, prev_d1: jax.Array,
                      prev_d2: jax.Array, *, bn: int = 128, bkn: int = 8,
                      interpret: bool | None = None):
    """Full k²-means assignment through the tiled Pallas kernel.

    neighbors: (k, kn) per-cluster candidate lists; the candidate-center
    table is built per *cluster* (k rows), and the scalar-prefetched
    block2cluster array routes each point block to its cluster's slabs.
    perm/block2cluster from group_by_cluster_device; -1 entries of perm are
    padding (they replicate point 0 but are dropped from the scatter-back).
    prev_d1/prev_d2 are squared distances (best / second-best candidate).
    Returns updated (a, sqdist1, sqdist2) in original point order; entries
    of skipped blocks keep their prev values exactly.
    """
    interpret = resolve_interpret(interpret)
    cidx = pad_candidates(neighbors.astype(jnp.int32), bkn)
    ctab, csqtab = candidate_tables(c, cidx)
    safe_perm = jnp.maximum(perm, 0)
    xg = x[safe_perm]
    pa = prev_a[safe_perm]
    pd1 = prev_d1[safe_perm]
    pd2 = prev_d2[safe_perm]
    a_g, d1_g, d2_g = candidate_assign_tiled(
        xg, ctab, csqtab, cidx, block2cluster, skip, pa, pd1, pd2,
        bn=bn, bkn=bkn, interpret=interpret)
    a_new = scatter_from_grouped(perm, a_g, prev_a)
    d1_new = scatter_from_grouped(perm, d1_g, prev_d1)
    d2_new = scatter_from_grouped(perm, d2_g, prev_d2)
    return a_new, d1_new, d2_new


__all__ = ["assign_nearest_pallas", "bounded_predict_assign",
           "bounded_predict_assign_int8", "bounded_predict_assign_top2",
           "candidate_assign",
           "candidate_assign_int8_tiled",
           "candidate_assign_rowwise", "candidate_assign_tiled",
           "candidate_tables", "center_knn", "center_sqdist",
           "choose_blocks", "choose_group_bn", "cluster_attend",
           "cluster_major_pack", "distance_argmin", "group_by_cluster",
           "group_by_cluster_device", "grouped_capacity",
           "k2_assign_grouped", "k2_bounded_assign", "pad_candidates",
           "plan_layout_evict", "plan_layout_repair", "quant",
           "quantized_scan_rerank",
           "resident_capacity", "resolve_interpret", "resident_regroup",
           "rowwise_grid_steps",
           "scatter_from_grouped", "segmented_scan", "select_clusters",
           "tiled_grid_steps"]
