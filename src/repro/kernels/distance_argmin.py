"""Pallas TPU kernel: fused pairwise-distance + running argmin.

The Lloyd/GDI hotspot. Never materialises the (n, k) distance matrix in
HBM: the grid is (n/bn, k/bk) with the k-axis minor, so a VMEM scratch
carries the running (min, argmin) for a point block while center blocks
stream through. The -2*X@C^T term hits the MXU; block shapes default to
MXU-aligned (128-multiples on the contracted/lane dims).

VMEM budget per step ~ bn*d + bk*d + 2*bn*bk floats; callers shrink bn for
very large d (e.g. yale's d=32256) — see ops.choose_blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .candidate_assign import first_min_rows, lane_sqnorms


def _kernel(x_ref, c_ref, a_ref, d_ref):
    # Transposed tile: centers on sublanes, points on lanes, so the
    # running (min, argmin) is a lane-dense (1, bn) row held in the
    # output blocks across the k axis.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        d_ref[0] = jnp.full_like(d_ref[0], jnp.inf)
        a_ref[0] = jnp.zeros_like(a_ref[0])

    x = x_ref[...]                                   # (bn, d)
    c = c_ref[...]                                   # (bk, d)
    # HIGHEST: the exact argmin; a TPU's default f32 matmul is one bf16 pass
    cross = jax.lax.dot_general(c, x, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    csq = jnp.sum(c * c, axis=1, keepdims=True)      # (bk, 1)
    dist = jnp.maximum(lane_sqnorms(x) - 2.0 * cross + csq, 0.0)
    dmin, loc, _ = first_min_rows(dist)
    better = dmin < d_ref[0]
    a_ref[0] = jnp.where(better, j * c.shape[0] + loc, a_ref[0])
    d_ref[0] = jnp.minimum(d_ref[0], dmin)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "interpret"))
def distance_argmin(x: jax.Array, c: jax.Array, *, bn: int = 256,
                    bk: int = 128, interpret: bool = False):
    """Nearest center per point. Returns (assignment int32 (n,), sqdist (n,)).

    n must be a multiple of bn and k of bk (ops.py pads).
    """
    n, d = x.shape
    k = c.shape[0]
    assert n % bn == 0 and k % bk == 0, (n, bn, k, bk)
    nb = n // bn
    row = pl.BlockSpec((1, 1, bn), lambda i, j: (i, 0, 0))
    a, dist = pl.pallas_call(
        _kernel,
        grid=(nb, k // bk),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, bn), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, bn), jnp.float32),
        ],
        interpret=interpret,
    )(x, c)
    return a.reshape(n), dist.reshape(n)
