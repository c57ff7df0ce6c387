"""Standard k-means (Lloyd's algorithm) — the paper's accuracy reference.

The update step is a segment-sum; empty clusters retain their previous
center (standard tie-break, matches the reference Matlab behaviour).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from .distance import chunked_argmin_sqdist, clustering_energy
from .opcount import OpCounter


@dataclasses.dataclass
class KMeansResult:
    centers: jax.Array
    assignment: jax.Array
    energy: float
    iterations: int
    ops: float
    # (cumulative_ops, energy) after every iteration — drives the paper's
    # "ops to reach reference energy" speedup tables.
    history: list
    # counted-op + memory-traffic breakdown (OpCounter.profile()), attached
    # by ``api.fit(..., profile=True)``; None otherwise.
    profile: dict | None = None
    # {state array: {device id: rows}} of a mesh fit's row-sharded bound
    # state (and resident arena), as placed for its last iteration; None
    # on a single device.
    placement: dict | None = None


def update_centers(x: jax.Array, a: jax.Array, c_prev: jax.Array) -> jax.Array:
    """Mean of members per cluster; empty clusters keep their old center."""
    k = c_prev.shape[0]
    sums = jax.ops.segment_sum(x, a, num_segments=k)
    counts = jax.ops.segment_sum(jnp.ones((x.shape[0],), x.dtype), a,
                                 num_segments=k)
    safe = jnp.maximum(counts, 1.0)[:, None]
    means = sums / safe
    return jnp.where(counts[:, None] > 0, means, c_prev)


@functools.partial(jax.jit, static_argnames=("chunk",))
def lloyd_step(x: jax.Array, c: jax.Array, chunk: int = 4096):
    a, dmin = chunked_argmin_sqdist(x, c, chunk=chunk)
    c_new = update_centers(x, a, c)
    return c_new, a, jnp.sum(dmin)


def fit_lloyd(x: jax.Array, centers: jax.Array, *, max_iters: int = 100,
              counter: OpCounter | None = None,
              callback: Callable | None = None) -> KMeansResult:
    counter = counter or OpCounter()
    n, d = x.shape
    k = centers.shape[0]
    c = centers
    a_prev = None
    history = []
    it = 0
    for it in range(1, max_iters + 1):
        c, a, energy = lloyd_step(x, c)
        counter.add_distances(n * k)      # assignment: n*k distances
        counter.add_additions(n)          # update: n vector additions
        history.append((counter.snapshot(), float(energy)))
        if callback is not None:
            callback(it, c, a, float(energy))
        a_host = jax.device_get(a)
        if a_prev is not None and (a_host == a_prev).all():
            break
        a_prev = a_host
    energy = float(clustering_energy(x, c, a))
    return KMeansResult(c, a, energy, it, counter.total, history)
