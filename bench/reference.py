"""The benchmark's yardstick: seeded data and the plain references.

Nothing here imports the program under test. ``gmm_rows`` is a copy of
``repro.data.gmm_blobs`` (same key splits, so the same key gives the same
rows), extended so that a second key draws fresh rows of the same
mixture. ``nearest`` is a brute-force argmin, ``lloyd`` plain Lloyd; both
take the cross term at HIGHEST precision unless ``dtype`` asks for the
lower-precision control (rows and centers held in that dtype, products
accumulated in float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, the bits above 32 folded in
    (``PRNGKey`` alone drops them)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


@functools.partial(jax.jit, static_argnames=("n", "d", "true_k", "spread",
                                             "noise", "components"))
def gmm_rows(key, n: int, d: int, true_k: int, spread: float = 4.0,
             noise: float = 1.0, row_key=None, components: bool = False):
    """n rows from the true_k-component Gaussian mixture that ``key``
    fixes, with power-law weights. ``row_key`` (default: drawn from
    ``key``, as ``repro.data.gmm_blobs`` does) picks the components and
    the noise, so another ``row_key`` gives fresh rows of one mixture.
    ``components=True`` also returns each row's component, (n,) int32."""
    k_mu, _, k_a, k_n = jax.random.split(key, 4)
    if row_key is not None:
        k_a, k_n = jax.random.split(row_key)
    mus = jax.random.normal(k_mu, (true_k, d), jnp.float32) * spread
    w = 1.0 / jnp.arange(1, true_k + 1, dtype=jnp.float32)
    w = w / jnp.sum(w)
    comp = jax.random.choice(k_a, true_k, shape=(n,), p=w)
    x = mus[comp] + noise * jax.random.normal(k_n, (n, d), jnp.float32)
    return (x, comp.astype(jnp.int32)) if components else x


def _sqdist(xb, c, dtype):
    """Squared distances of a row block to every center, (m, k) float32.
    float32 takes the cross term at HIGHEST; a lower ``dtype`` rounds
    rows and centers to it and accumulates the products in float32."""
    if dtype == jnp.float32:
        cross = jnp.dot(xb, c.T, precision=HIGHEST)
    else:
        xb, c = xb.astype(dtype), c.astype(dtype)
        cross = jnp.dot(xb, c.T, preferred_element_type=jnp.float32)
    xsq = jnp.sum(jnp.square(xb.astype(jnp.float32)), axis=1)
    csq = jnp.sum(jnp.square(c.astype(jnp.float32)), axis=1)
    return xsq[:, None] - 2.0 * cross + csq[None, :]


@functools.partial(jax.jit, static_argnames=("chunk", "dtype"))
def nearest(x, c, chunk: int = 32768, dtype=jnp.float32):
    """Exact nearest center of every row: (n,) int32 and the squared
    distance, ``chunk`` rows at a time (first index on ties)."""
    n, d = x.shape
    chunk = min(chunk, n)
    pad = (-n) % chunk

    def block(xb):
        sq = _sqdist(xb, c, dtype)
        return jnp.argmin(sq, axis=1).astype(jnp.int32), jnp.min(sq, axis=1)

    xp = jnp.pad(x, ((0, pad), (0, 0)))
    a, sq = jax.lax.map(block, xp.reshape(-1, chunk, d))
    return a.reshape(-1)[:n], jnp.maximum(sq.reshape(-1)[:n], 0.0)


def forgy(x, k: int, key) -> jax.Array:
    """k distinct rows drawn by ``key``: the Forgy start."""
    return x[jax.random.choice(key, x.shape[0], shape=(k,), replace=False)]


@functools.partial(jax.jit, static_argnames=("iters", "dtype"))
def lloyd(x, c, iters: int, dtype=jnp.float32):
    """Plain Lloyd from ``c``: exact assignment, then the mean update,
    ``iters`` times. Returns the last (updated centers, assignment) pair,
    the pair whose energy a fit reports. A lower ``dtype`` holds rows and
    centers in it (the control); sums accumulate in float32."""
    k = c.shape[0]
    xs = x.astype(dtype)
    ones = jnp.ones((x.shape[0],), jnp.float32)

    def step(_, carry):
        c, _ = carry
        a, _ = nearest(xs, c, dtype=dtype)
        sums = jax.ops.segment_sum(xs.astype(jnp.float32), a,
                                   num_segments=k)
        cnt = jax.ops.segment_sum(ones, a, num_segments=k)
        mean = sums / jnp.maximum(cnt, 1.0)[:, None]
        c = jnp.where(cnt[:, None] > 0, mean.astype(dtype), c)
        return c, a

    c, a = jax.lax.fori_loop(0, iters, step,
                             (c.astype(dtype),
                              jnp.zeros((x.shape[0],), jnp.int32)))
    return c.astype(jnp.float32), a


@jax.jit
def energy(x, c, a) -> jax.Array:
    """Clustering energy of (c, a) on x, elementwise in float32."""
    return jnp.sum(jnp.square(x - c[a]))


@jax.jit
def center_gap(x, c, a) -> jax.Array:
    """How far the centers lie from the means of the rows assigned to
    them: the widest ``|c_j - mean_j|`` over non-empty clusters, against
    ``|mean_j|`` or the median ``|mean|``, whichever is larger. An
    assignment index out of range counts as a gap of 1."""
    k = c.shape[0]
    bad = jnp.any((a < 0) | (a >= k))
    live = jax.ops.segment_sum(jnp.ones((x.shape[0],), jnp.float32), a,
                               num_segments=k) > 0
    m = center_of(x, a, k)
    mnorm = jnp.linalg.norm(m, axis=1)
    scale = jnp.maximum(mnorm, jnp.nanmedian(jnp.where(live, mnorm,
                                                       jnp.nan)))
    gap = jnp.where(live, jnp.linalg.norm(c - m, axis=1) / scale, 0.0)
    return jnp.where(bad, 1.0, jnp.max(gap))


@functools.partial(jax.jit, static_argnames=("iters",))
def lloyd_gain(x, c, a, iters: int) -> jax.Array:
    """How much plain HIGHEST Lloyd, run ``iters`` iterations from the
    centers ``c``, still lowers the energy of (c, a): E(c, a) over
    Lloyd's energy, less 1. Lloyd never raises the energy, so the gain
    is at least 0 up to rounding; a fit that stopped short of a local
    minimum reads high."""
    c2, a2 = lloyd(x, c, iters)
    return energy(x, c, a) / energy(x, c2, a2) - 1.0


def truth_energy(x, comp, true_k: int) -> jax.Array:
    """Energy of the mixture's own partition: each row to the mean of
    the rows its component drew."""
    return energy(x, center_of(x, comp, true_k), comp)


@functools.partial(jax.jit, static_argnames=("k",))
def center_of(x, a, k: int) -> jax.Array:
    """The mean of the rows each of the k labels holds (0 where none)."""
    sums = jax.ops.segment_sum(x, a, num_segments=k)
    cnt = jax.ops.segment_sum(jnp.ones((x.shape[0],), jnp.float32), a,
                              num_segments=k)
    return sums / jnp.maximum(cnt, 1.0)[:, None]
