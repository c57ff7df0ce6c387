"""Pallas TPU kernels: k_n-restricted assignment — the k²-means hotspot.

Two generations of the kernel live here (DESIGN.md §3):

``candidate_assign`` (tiled, the fast path)
    Candidates are processed ``bkn`` at a time: grid ``(nb, kn_pad/bkn)``
    instead of the per-row ``(nb, kn)``.  Each grid step DMAs one
    ``(bkn, d)`` slab of a *neighbor-center table* — candidate centers
    pre-gathered contiguously per candidate-list row — and issues one
    MXU-shaped ``(bn, d) x (d, bkn)`` matmul.  The slab to fetch is picked
    by the BlockSpec index_map reading the scalar-prefetched ``rowsel``
    array (block -> table row), so Pallas streams exactly the candidate
    rows each block needs, ``bkn`` per DMA, instead of issuing ``kn``
    single-row DMAs and ``(bn, d) x (d, 1)`` dots that waste the MXU.
    The kernel tracks the best *and second-best* squared distance per
    point, which feeds the Hamerly-style lower bound directly.

``candidate_assign_rowwise`` (legacy, one candidate row per grid step)
    Kept as the comparison baseline for ``benchmarks/assign_bench.py``
    and as the simplest correct realisation of the layout contract.

Contract (both): points are pre-grouped so that every point block (bn
points) shares one candidate list of k_n center indices. Blocks need NOT
be cluster-contiguous or hole-free — the scalar-prefetched ``rowsel``
array is the only block -> candidate-list routing — which is what lets
the resident layout (DESIGN.md §9) repair blocks in place across
iterations instead of re-sorting. Rebuild callers derive the layout
per call from the current assignment (ops.group_by_cluster_device:
points sorted by cluster, clusters padded to block multiples); resident
callers pass the carried arena (ops.resident_regroup /
ops.plan_layout_repair) whose free blocks simply arrive with their skip
flag set. The same contract serves *queries* at decode time: the
query-time subsystem (DESIGN.md §10, ops.bounded_predict_assign) groups
queries by their routed center and resolves each block against that
center's neighbor list — fit-time and query-time assignment share this
one kernel.

Triangle-inequality adaptation (DESIGN.md §3): a per-block skip flag (from
the Hamerly-style bounds) gates the whole compute with @pl.when — an entire
(bn, k_n) distance tile is elided when no point in the block can change
assignment. Tile-level pruning is the TPU analogue of Elkan's per-point
branch; the flag also suppresses the candidate-slab DMA via a zero index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Padded candidate columns carry this squared "distance" so they never win
# an argmin; finite (not inf) so no inf-inf NaNs can appear downstream.
PAD_SQDIST = 1e30


def pad_candidates(cand: jax.Array, bkn: int) -> jax.Array:
    """Pad candidate lists (rows, kn) -> (rows, kn_pad) with -1 sentinels so
    kn divides into bkn tiles. -1 columns are masked to PAD_SQDIST."""
    kn = cand.shape[-1]
    pad = (-kn) % bkn
    if pad == 0:
        return cand
    return jnp.pad(cand, ((0, 0), (0, pad)), constant_values=-1)


def candidate_tables(c: jax.Array, cidx: jax.Array):
    """Gather the candidate-center table for ``candidate_assign_tiled``.

    c: (k, d) centers; cidx: (T, kn_pad) int32 candidate ids (-1 = padding).
    Returns (ctab (T, kn_pad, d), csqtab (T, kn_pad)) where padded columns
    get PAD_SQDIST so they can never win. This O(T * kn * d) XLA gather is
    the price of turning kn arbitrary-row DMAs into kn/bkn contiguous slab
    DMAs inside the kernel; for the grouped path T = k (one row per
    cluster), so it is the same order as the O(k^2 d) graph build.
    """
    ctab = c[jnp.maximum(cidx, 0)]
    csqtab = jnp.where(cidx >= 0, jnp.sum(ctab * ctab, axis=-1), PAD_SQDIST)
    return ctab, csqtab.astype(jnp.float32)


def lane_sqnorms(x: jax.Array) -> jax.Array:
    """(rows, d) tile -> (1, rows) squared row norms laid out along lanes
    (exact f32 on the VPU: one transpose, one sublane reduction)."""
    xt = x.astype(jnp.float32).T
    return jnp.sum(xt * xt, axis=0, keepdims=True)


def first_min_rows(dist: jax.Array):
    """Column-wise best and second best of a (rows, cols) tile: returns
    (d1 (1, cols), loc (1, cols) int32 first-min row, d2 (1, cols))."""
    row = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 0)
    d1 = jnp.min(dist, axis=0, keepdims=True)
    loc = jnp.min(jnp.where(dist == d1, row, dist.shape[0]), axis=0,
                  keepdims=True)
    d2 = jnp.min(jnp.where(row == loc, jnp.inf, dist), axis=0,
                 keepdims=True)
    return d1, loc, d2


def block_rows(v: jax.Array, bn: int) -> jax.Array:
    """(n,) per-row vector -> (n // bn, 1, bn): one lane-dense row per
    point block, the layout every kernel here reads and writes per-row
    scalars in (a bitcast of the flat vector at bn = 128)."""
    return v.reshape(-1, 1, bn)


def _row_spec(bn: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, 1, bn), lambda i, j, rs, sk: (i, 0, 0))


def _cand_row_spec(knp: int) -> pl.BlockSpec:
    # a table row's per-candidate scalars, fetched once per point block
    return pl.BlockSpec((1, 1, knp),
                        lambda i, j, rs, sk: (rs[i] * (1 - sk[i]), 0, 0))


def _tile_rows(j, bkn: int):
    return pl.ds(pl.multiple_of(j * bkn, bkn), bkn)


def _tiled_kernel(rowsel_ref, skip_ref,              # scalar prefetch (SMEM)
                  x_ref, ctab_ref, csq_ref,
                  pos_ref, d1_ref, d2_ref,
                  xsq, csq_col):
    # Transposed tile: candidates on sublanes, the block's points on lanes,
    # so every per-point result is a lane-dense (1, bn) row.
    i, j = pl.program_id(0), pl.program_id(1)
    bkn = ctab_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        pos_ref[0] = jnp.zeros_like(pos_ref[0])
        d1_ref[0] = jnp.full_like(d1_ref[0], jnp.inf)
        d2_ref[0] = jnp.full_like(d2_ref[0], jnp.inf)
        xsq[...] = lane_sqnorms(x_ref[...])
        csq_col[...] = csq_ref[0].T                  # (kn_pad, 1)

    @pl.when(skip_ref[i] == 0)
    def _compute():
        ct = ctab_ref[0]                             # (bkn, d) candidate slab
        # HIGHEST: these distances decide the exact assignment; a TPU's
        # default f32 matmul is one bf16 pass
        cross = jax.lax.dot_general(ct, x_ref[...], (((1,), (1,)), ((), ())),
                                    precision=jax.lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)
        dist = jnp.maximum(xsq[...] - 2.0 * cross
                           + csq_col[_tile_rows(j, bkn), :], 0.0)
        d1, loc, d2 = first_min_rows(dist)
        # merge into the running best; strict < keeps the earlier tile on
        # ties, matching a flat argmin
        best1 = d1_ref[0]
        d2_ref[0] = jnp.minimum(jnp.maximum(best1, d1),
                                jnp.minimum(d2_ref[0], d2))
        pos_ref[0] = jnp.where(d1 < best1, loc + j * bkn, pos_ref[0])
        d1_ref[0] = jnp.minimum(best1, d1)


@functools.partial(jax.jit, static_argnames=("bn", "bkn", "interpret"))
def candidate_assign_tiled(x: jax.Array, ctab: jax.Array, csqtab: jax.Array,
                           cidx: jax.Array, rowsel: jax.Array,
                           skip: jax.Array, prev_a: jax.Array,
                           prev_d1: jax.Array, prev_d2: jax.Array,
                           *, bn: int = 256, bkn: int = 8,
                           interpret: bool = False):
    """Tiled k_n-restricted assignment over a candidate-center table.

    x: (n, d) points, grouped so block b (rows b*bn:(b+1)*bn) shares the
       candidate list ``cidx[rowsel[b]]``.
    ctab: (T, kn_pad, d) candidate centers; csqtab: (T, kn_pad) their
       squared norms (PAD_SQDIST for -1 padding); cidx: (T, kn_pad) int32.
    rowsel: (nb,) int32 block -> table row.  skip: (nb,) int32.
    prev_a/prev_d1/prev_d2: fallbacks for skipped blocks, (n,).
    Returns (assignment int32 (n,), best sqdist f32 (n,),
             second-best sqdist f32 (n,)).

    The kernel emits the winning candidate *column*; the column -> center
    id lookup through ``cidx`` and the skipped-block fallbacks are one
    fused elementwise pass here, so the kernel streams no per-candidate
    ids and no fallback rows.
    """
    n, d = x.shape
    assert n % bn == 0
    t, knp = cidx.shape
    assert knp % bkn == 0 and ctab.shape == (t, knp, d)
    nb = n // bn
    assert rowsel.shape == (nb,) and skip.shape == (nb,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, knp // bkn),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j, rs, sk: (i, 0)),
            # the gather: candidate slab j of table row rs[i], one DMA of
            # bkn contiguous candidate centers (zero row when skipped)
            pl.BlockSpec((1, bkn, d),
                         lambda i, j, rs, sk: (rs[i] * (1 - sk[i]), j, 0)),
            _cand_row_spec(knp),
        ],
        out_specs=[_row_spec(bn), _row_spec(bn), _row_spec(bn)],
        scratch_shapes=[
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((knp, 1), jnp.float32),
        ],
    )
    rows = jax.ShapeDtypeStruct((nb, 1, bn), jnp.float32)
    pos, d1, d2 = pl.pallas_call(
        _tiled_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nb, 1, bn), jnp.int32), rows, rows],
        interpret=interpret,
    )(rowsel, skip, x, ctab, csqtab[:, None, :])
    a = jnp.take_along_axis(cidx[rowsel], pos[:, 0, :], axis=1)
    stale = jnp.repeat(skip != 0, bn)
    return (jnp.where(stale, prev_a, a.reshape(n)).astype(jnp.int32),
            jnp.where(stale, prev_d1, d1.reshape(n)),
            jnp.where(stale, prev_d2, d2.reshape(n)))


@functools.partial(jax.jit, static_argnames=("bn", "bkn", "interpret"))
def candidate_assign(x: jax.Array, c: jax.Array, cand: jax.Array,
                     skip: jax.Array, prev_a: jax.Array, prev_d1: jax.Array,
                     prev_d2: jax.Array, *, bn: int = 256, bkn: int = 8,
                     interpret: bool = False):
    """Tiled k_n-restricted assignment with per-block candidate lists.

    Convenience entry: builds the candidate-center table from ``cand``
    (nb, kn) with one table row per block and calls the tiled kernel.
    The grouped k²-means path uses ``candidate_assign_tiled`` directly
    with the more compact per-cluster table (ops.k2_assign_grouped).
    Returns (assignment (n,), best sqdist (n,), second-best sqdist (n,)).
    """
    nb = cand.shape[0]
    cidx = pad_candidates(cand.astype(jnp.int32), bkn)
    ctab, csqtab = candidate_tables(c, cidx)
    rowsel = jnp.arange(nb, dtype=jnp.int32)
    return candidate_assign_tiled(x, ctab, csqtab, cidx, rowsel, skip,
                                  prev_a, prev_d1, prev_d2, bn=bn, bkn=bkn,
                                  interpret=interpret)


# ---------------------------------------------------------------------------
# Int8 variant (DESIGN.md §13): same grid and slab streaming as the tiled
# kernel, but the (bn, d) x (d, bkn) matmul runs on int8 inputs with an
# int32 accumulator, and instead of exact distances the kernel emits the
# margin-test survivor set per row — the column positions of every
# candidate whose quantized lower bound cannot be excluded from the true
# argmin. The caller re-ranks survivors in exact f32 (kernels/quant.py
# derives the bound; ops.quantized_scan_rerank does the re-rank).
# ---------------------------------------------------------------------------


def _int8_tiled_kernel(rowsel_ref, skip_ref,         # scalar prefetch (SMEM)
                       xq_ref, xsc_ref, xerr_ref, qtab_ref, qsc_ref,
                       qerr_ref, csq_ref,
                       surv_ref, nsv_ref, lbm_ref,
                       lb_buf, ub_min, xhsq, qsc_col, qerr_col, csq_col,
                       *, r):
    # Same transposed tile as the f32 kernel: candidates on sublanes,
    # points on lanes.
    i, j = pl.program_id(0), pl.program_id(1)
    nt = pl.num_programs(1)
    bkn = qtab_ref.shape[1]
    skipped = skip_ref[i] != 0

    @pl.when(j == 0)
    def _init():
        ub_min[...] = jnp.full_like(ub_min, PAD_SQDIST)
        s = xsc_ref[0]                               # (1, bn)
        # widen to int32 first: int8 -> float is the dequantization the
        # int8 path budgets (DESIGN.md §13), and this is not one
        xhsq[...] = s * s * lane_sqnorms(xq_ref[...].astype(jnp.int32))
        qsc_col[...] = qsc_ref[0].T                  # (kn_pad, 1) each
        qerr_col[...] = qerr_ref[0].T
        csq_col[...] = csq_ref[0].T

    @pl.when(jnp.logical_not(skipped))
    def _compute():
        cross = jax.lax.dot_general(qtab_ref[0], xq_ref[...],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.int32)
        tile = _tile_rows(j, bkn)
        sc = xsc_ref[0] * qsc_col[tile, :]           # (bkn, bn)
        dist = jnp.maximum(
            xhsq[...] - 2.0 * sc * cross.astype(jnp.float32)
            + csq_col[tile, :], 0.0)
        shat = jnp.sqrt(dist)                        # approx true distance
        rc = qerr_col[tile, :]                       # exact candidate radii
        lb_buf[tile, :] = shat - rc
        ub_min[...] = jnp.minimum(
            ub_min[...], jnp.min(shat + rc, axis=0, keepdims=True))

    @pl.when(j == nt - 1)
    def _flush():
        lb = lb_buf[...]                             # (kn_pad, bn)
        cut = ub_min[...] + 2.0 * xerr_ref[0]        # exact query radius
        mask = jnp.logical_and(lb <= cut, jnp.logical_not(skipped))
        nsv_ref[0] = jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True)
        # survivor s is the s-th set row: peel the first set row r times
        knp = mask.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 0)
        left = mask
        for s in range(r):                           # static unroll
            col = jnp.min(jnp.where(left, row, knp), axis=0, keepdims=True)
            surv_ref[0, s:s + 1, :] = jnp.where(col < knp, col, -1)
            left = jnp.logical_and(left, row != col)
        rest = jnp.min(jnp.where(mask, PAD_SQDIST, lb), axis=0,
                       keepdims=True)
        lbm_ref[0] = jnp.where(skipped, PAD_SQDIST, rest)


@functools.partial(jax.jit, static_argnames=("bn", "bkn", "r", "interpret"))
def candidate_assign_int8_tiled(xq: jax.Array, xsc: jax.Array,
                                xerr: jax.Array,
                                qtab: jax.Array, qsc: jax.Array,
                                qerrtab: jax.Array,
                                csqtab: jax.Array, rowsel: jax.Array,
                                skip: jax.Array, *, bn: int = 256,
                                bkn: int = 8, r: int = 8,
                                interpret: bool = False):
    """Int8 tiled scan: per-row survivor sets instead of exact argmins.

    xq: (n, d) int8 quantized points (grouped per the tiled-kernel layout
    contract), xsc: (n,) their per-row scales, xerr: (n,) the exact
    residual norms ``||x - dequant(xq)||`` (the margin's query radius —
    much tighter than the worst-case scale bound). qtab/qsc/qerrtab/
    csqtab: quantized candidate slabs from
    quant.quantized_candidate_slabs ((T, kn_pad, d) int8 / (T, kn_pad)
    scales, 0 at padding / (T, kn_pad) exact residual norms, 0 at
    padding / (T, kn_pad) exact ||dequant||^2, PAD_SQDIST at padding).
    rowsel/skip as in :func:`candidate_assign_tiled`. Returns (surv_col
    (n, r) int32 column positions into the block's candidate list, -1
    beyond the survivor count; n_surv (n,) int32 — may exceed ``r``,
    flagging f32 fallback; lb_min (n,) f32 the smallest quantized lower
    bound among non-survivors, for the caller's Hamerly second-best
    bound). Skipped blocks emit (all -1, 0, PAD_SQDIST)."""
    n, d = xq.shape
    assert n % bn == 0
    t, knp, _ = qtab.shape
    assert knp % bkn == 0 and qsc.shape == (t, knp)
    nb = n // bn
    assert rowsel.shape == (nb,) and skip.shape == (nb,)

    kern = functools.partial(_int8_tiled_kernel, r=r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, knp // bkn),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j, rs, sk: (i, 0)),
            _row_spec(bn),
            _row_spec(bn),
            pl.BlockSpec((1, bkn, d),
                         lambda i, j, rs, sk: (rs[i] * (1 - sk[i]), j, 0)),
            _cand_row_spec(knp),
            _cand_row_spec(knp),
            _cand_row_spec(knp),
        ],
        out_specs=[
            pl.BlockSpec((1, r, bn), lambda i, j, rs, sk: (i, 0, 0)),
            _row_spec(bn),
            _row_spec(bn),
        ],
        scratch_shapes=[
            pltpu.VMEM((knp, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((knp, 1), jnp.float32),
            pltpu.VMEM((knp, 1), jnp.float32),
            pltpu.VMEM((knp, 1), jnp.float32),
        ],
    )
    surv, nsv, lbm = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb, r, bn), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, bn), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, bn), jnp.float32),
        ],
        interpret=interpret,
    )(rowsel, skip, xq, block_rows(xsc, bn), block_rows(xerr, bn), qtab,
      qsc[:, None, :], qerrtab[:, None, :], csqtab[:, None, :])
    return (surv.transpose(0, 2, 1).reshape(n, r), nsv.reshape(n),
            lbm.reshape(n))


def tiled_grid_steps(n: int, kn: int, bn: int, bkn: int) -> int:
    """Grid steps the tiled kernel issues (vs rowwise_grid_steps)."""
    return (n // bn) * (-(-kn // bkn))


def rowwise_grid_steps(n: int, kn: int, bn: int) -> int:
    return (n // bn) * kn


# ---------------------------------------------------------------------------
# Legacy per-row kernel: one candidate center per grid step. Kept as the
# baseline for benchmarks/assign_bench.py; prefer candidate_assign.
# ---------------------------------------------------------------------------

def _rowwise_kernel(cand_ref, skip_ref,              # scalar prefetch (SMEM)
                    x_ref, c_ref, csq_ref, prev_a_ref, prev_d_ref,
                    a_ref, d_ref,
                    best_d, best_a, xsq):
    i, j = pl.program_id(0), pl.program_id(1)
    kn = pl.num_programs(1)
    skipped = skip_ref[i] != 0

    @pl.when(j == 0)
    def _init():
        best_d[...] = jnp.full_like(best_d, jnp.inf)
        best_a[...] = jnp.zeros_like(best_a)
        xsq[...] = jnp.sum(x_ref[...] * x_ref[...], axis=-1)

    @pl.when(jnp.logical_not(skipped))
    def _compute():
        x = x_ref[...]                               # (bn, d)
        c = c_ref[...]                               # (1, d) candidate row
        cross = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        dist = jnp.maximum(xsq[...] - 2.0 * cross[:, 0] + csq_ref[0, 0], 0.0)
        cidx = cand_ref[i, j]
        better = dist < best_d[...]
        best_d[...] = jnp.where(better, dist, best_d[...])
        best_a[...] = jnp.where(better, cidx, best_a[...])

    @pl.when(j == kn - 1)
    def _flush():
        a_ref[...] = jnp.where(skipped, prev_a_ref[...], best_a[...])
        d_ref[...] = jnp.where(skipped, prev_d_ref[...], best_d[...])


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def candidate_assign_rowwise(x: jax.Array, c: jax.Array, cand: jax.Array,
                             skip: jax.Array, prev_a: jax.Array,
                             prev_d: jax.Array, *, bn: int = 256,
                             interpret: bool = False):
    """Per-row k_n-restricted assignment (grid (nb, kn), one DMA per
    candidate). Same contract as ``candidate_assign`` minus the
    second-best distance output."""
    n, d = x.shape
    assert n % bn == 0
    nb, kn = cand.shape
    assert nb == n // bn
    csq = jnp.sum(c * c, axis=-1)[None, :]

    grid = (nb, kn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j, cand, skip: (i, 0)),
            pl.BlockSpec((1, d),
                         lambda i, j, cand, skip: (cand[i, j] * (1 - skip[i]), 0)),
            pl.BlockSpec((1, 1),
                         lambda i, j, cand, skip: (0, cand[i, j] * (1 - skip[i]))),
            pl.BlockSpec((bn,), lambda i, j, cand, skip: (i,)),
            pl.BlockSpec((bn,), lambda i, j, cand, skip: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((bn,), lambda i, j, cand, skip: (i,)),
            pl.BlockSpec((bn,), lambda i, j, cand, skip: (i,)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn,), jnp.float32),
            pltpu.VMEM((bn,), jnp.int32),
            pltpu.VMEM((bn,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _rowwise_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
    )(cand, skip, x, c, csq, prev_a, prev_d)
