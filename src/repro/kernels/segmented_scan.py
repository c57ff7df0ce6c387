"""Pallas TPU kernel: segmented inclusive scan over a leaf-grouped layout.

The divisive-initialization hotspot (DESIGN.md §4). Rows arrive grouped by
leaf (ops.group_by_cluster_device layout: every leaf padded to a ``bn``
multiple, so segment boundaries only occur at block boundaries) and sorted
by the split-direction projection within each leaf. One sequential pass
over the blocks then yields, for every candidate split position at once,
the running sums Lemma 1 needs:

    csum[r] = sum_{r' <= r, same leaf} w[r'] * x[r']        (d lanes)
    qsum[r] = sum_{r' <= r, same leaf} w[r'] * ||x[r']||^2
    cnt[r]  = sum_{r' <= r, same leaf} w[r']

The TPU grid executes in order, so the running carry lives in scratch and
resets whenever the scalar-prefetched ``block2seg`` changes between
consecutive blocks — the segmented analogue of a grid-carried cumsum.
Padding rows (w = 0) contribute nothing, so within-leaf padding and the
trailing all-padding capacity blocks of the grouped layout are harmless.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .candidate_assign import block_rows, lane_sqnorms

# the in-block prefix sums are triangular matmuls: at a TPU's default
# f32 precision (one bf16 pass) every summand would be rounded
_HI = jax.lax.Precision.HIGHEST


def _kernel(b2s_ref,                                  # scalar prefetch (SMEM)
            x_ref, w_ref,
            csum_ref, qsum_ref, cnt_ref,
            carry_x, carry_q, carry_c):
    i = pl.program_id(0)
    seg = b2s_ref[i]
    prev = b2s_ref[jnp.maximum(i - 1, 0)]
    reset = jnp.logical_or(i == 0, seg != prev)

    @pl.when(reset)
    def _():
        carry_x[...] = jnp.zeros_like(carry_x)
        carry_q[...] = jnp.zeros_like(carry_q)
        carry_c[...] = jnp.zeros_like(carry_c)

    x = x_ref[...]                                    # (bn, d)
    w = w_ref[0]                                      # (1, bn) row weights
    bn = x.shape[0]
    # the in-block inclusive scans as triangular matmuls (Mosaic has no
    # cumsum): lower[r, r'] = w[r'] for r' <= r sums rows down the block,
    # upper[r', r] = [r' <= r] sums a lane row left to right
    r_ = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    c_ = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    lower = jnp.where(c_ <= r_, w, 0.0)
    upper = (r_ <= c_).astype(jnp.float32)
    cx = jax.lax.dot_general(lower, x, (((1,), (0,)), ((), ())),
                             precision=_HI,
                             preferred_element_type=jnp.float32) \
        + carry_x[...]
    lane_scan = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=_HI, preferred_element_type=jnp.float32)
    cq = lane_scan(w * lane_sqnorms(x), upper) + carry_q[...]
    cc = lane_scan(w, upper) + carry_c[...]
    csum_ref[...] = cx
    qsum_ref[0] = cq
    cnt_ref[0] = cc
    carry_x[...] = cx[bn - 1:bn, :]
    carry_q[...] = cq[:, bn - 1:bn]
    carry_c[...] = cc[:, bn - 1:bn]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def segmented_scan(x: jax.Array, w: jax.Array, block2seg: jax.Array,
                   *, bn: int = 128, interpret: bool = False):
    """Segmented inclusive scan of (x, ||x||^2, 1) weighted by ``w``.

    x: (R, d) rows in leaf-grouped order (R = nb * bn); w: (R,) f32 row
    weights (1 real, 0 padding); block2seg: (nb,) int32 leaf id per block,
    non-decreasing, segment boundaries block-aligned.
    Returns (csum (R, d), qsum (R,), cnt (R,)), each inclusive within its
    segment.
    """
    r, d = x.shape
    assert r % bn == 0
    nb = r // bn
    assert block2seg.shape == (nb,)

    row = pl.BlockSpec((1, 1, bn), lambda i, b2s: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((bn, d), lambda i, b2s: (i, 0)), row],
        out_specs=[pl.BlockSpec((bn, d), lambda i, b2s: (i, 0)), row, row],
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    rows = jax.ShapeDtypeStruct((nb, 1, bn), jnp.float32)
    csum, qsum, cnt = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, d), jnp.float32), rows, rows],
        interpret=interpret,
    )(block2seg, x, block_rows(w, bn))
    return csum, qsum.reshape(r), cnt.reshape(r)
