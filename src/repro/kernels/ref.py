"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def distance_argmin_ref(x: jax.Array, c: jax.Array):
    """(n,d),(k,d) -> (assignment (n,) int32, min sqdist (n,) f32)."""
    sq = jnp.maximum(
        jnp.sum(x * x, -1)[:, None] - 2.0 * (x @ c.T) + jnp.sum(c * c, -1),
        0.0)
    return jnp.argmin(sq, axis=1).astype(jnp.int32), jnp.min(sq, axis=1)


def candidate_assign_ref(x, c, cand, skip, prev_a, prev_d, bn: int):
    """Oracle for the grouped k_n-restricted assignment kernel."""
    n, d = x.shape
    nb, kn = cand.shape
    xb = x.reshape(nb, bn, d)
    cc = c[cand]                                     # (nb, kn, d)
    cross = jnp.einsum("bnd,bkd->bnk", xb, cc)
    sq = jnp.maximum(
        jnp.sum(xb * xb, -1)[..., None] - 2.0 * cross
        + jnp.sum(cc * cc, -1)[:, None, :], 0.0)     # (nb, bn, kn)
    loc = jnp.argmin(sq, axis=-1)
    a = jnp.take_along_axis(cand[:, None, :].repeat(bn, 1), loc[..., None],
                            axis=-1)[..., 0]
    dmin = jnp.min(sq, axis=-1)
    a = a.reshape(-1).astype(jnp.int32)
    dmin = dmin.reshape(-1)
    skip_pt = jnp.repeat(skip.astype(bool), bn)
    return (jnp.where(skip_pt, prev_a, a).astype(jnp.int32),
            jnp.where(skip_pt, prev_d, dmin))


def candidate_assign_tiled_ref(x, c, cand, skip, prev_a, prev_d1, prev_d2,
                               bn: int):
    """Oracle for the tiled kernel: like candidate_assign_ref but also
    returns the second-best squared candidate distance (the Hamerly lower
    bound input)."""
    n, d = x.shape
    nb, kn = cand.shape
    xb = x.reshape(nb, bn, d)
    cc = c[cand]                                     # (nb, kn, d)
    cross = jnp.einsum("bnd,bkd->bnk", xb, cc)
    sq = jnp.maximum(
        jnp.sum(xb * xb, -1)[..., None] - 2.0 * cross
        + jnp.sum(cc * cc, -1)[:, None, :], 0.0)     # (nb, bn, kn)
    loc = jnp.argmin(sq, axis=-1)
    a = jnp.take_along_axis(cand[:, None, :].repeat(bn, 1), loc[..., None],
                            axis=-1)[..., 0].reshape(-1).astype(jnp.int32)
    if kn >= 2:
        top2_neg, _ = jax.lax.top_k(-sq, 2)
        d1 = (-top2_neg[..., 0]).reshape(-1)
        d2 = (-top2_neg[..., 1]).reshape(-1)
    else:
        d1 = jnp.min(sq, axis=-1).reshape(-1)
        d2 = jnp.full_like(d1, jnp.inf)
    skip_pt = jnp.repeat(skip.astype(bool), bn)
    return (jnp.where(skip_pt, prev_a, a).astype(jnp.int32),
            jnp.where(skip_pt, prev_d1, d1),
            jnp.where(skip_pt, prev_d2, d2))


def segmented_scan_ref(x, w, block2seg, bn: int, num_segments: int):
    """jax.ops.segment_* oracle for the segmented-scan kernel.

    Same contract as ``segmented_scan``: rows grouped by segment (block
    aligned, ``block2seg`` non-decreasing), ``w`` zero on padding rows.
    Realised as a global inclusive cumsum minus the per-segment exclusive
    offset (``segment_sum`` totals, exclusive-scanned over segments).
    """
    row_seg = jnp.repeat(block2seg, bn)
    xw = x * w[:, None]
    q = jnp.sum(xw * x, axis=-1)
    gx = jnp.cumsum(xw, axis=0)
    gq = jnp.cumsum(q)
    gc = jnp.cumsum(w)
    tot_x = jax.ops.segment_sum(xw, row_seg, num_segments=num_segments)
    tot_q = jax.ops.segment_sum(q, row_seg, num_segments=num_segments)
    tot_c = jax.ops.segment_sum(w, row_seg, num_segments=num_segments)
    off_x = (jnp.cumsum(tot_x, axis=0) - tot_x)[row_seg]
    off_q = (jnp.cumsum(tot_q) - tot_q)[row_seg]
    off_c = (jnp.cumsum(tot_c) - tot_c)[row_seg]
    return gx - off_x, gq - off_q, gc - off_c


def center_sqdist_ref(c):
    sq = jnp.sum(c * c, -1)
    return jnp.maximum(sq[:, None] - 2.0 * (c @ c.T) + sq[None, :], 0.0)


def clustered_attend_ref(q, k_cache, v_cache, centroids, members,
                         member_mask, top_p: int):
    """Oracle for clustered-KV sparse decode attention (see cluster_attend).

    q: (h, dh); k_cache/v_cache: (h, S, dh); centroids: (h, kc, dh);
    members: (h, kc, cap) int32 indices into S; member_mask: same shape bool.
    Attends to the union of the top_p closest clusters' members.
    """
    h, s, dh = k_cache.shape
    kc, cap = members.shape[1], members.shape[2]
    # nearest clusters by squared distance between q and centroids
    d2 = (jnp.sum(q * q, -1)[:, None]
          - 2.0 * jnp.einsum("hd,hkd->hk", q, centroids)
          + jnp.sum(centroids * centroids, -1))
    _, top = jax.lax.top_k(-d2, top_p)               # (h, p)
    sel = jnp.take_along_axis(members, top[:, :, None], axis=1)       # (h,p,cap)
    sel_mask = jnp.take_along_axis(member_mask, top[:, :, None], axis=1)
    sel = sel.reshape(h, -1)
    sel_mask = sel_mask.reshape(h, -1)
    kk = jnp.take_along_axis(k_cache, sel[:, :, None], axis=1)        # (h,p*cap,dh)
    vv = jnp.take_along_axis(v_cache, sel[:, :, None], axis=1)
    logits = jnp.einsum("hd,hmd->hm", q, kk) / jnp.sqrt(dh).astype(q.dtype)
    logits = jnp.where(sel_mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(sel_mask, w, 0.0)
    return jnp.einsum("hm,hmd->hd", w, vv)
