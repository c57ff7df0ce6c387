"""Attention variants: GQA (optional qk-norm), MLA (DeepSeek-V2), and
clustered-KV sparse decode attention ("k²-attention" — the paper's technique
applied to the KV cache; see DESIGN.md §5).

Memory discipline: training/prefill attention is query-chunked (scan over
query blocks, full KV per block) so the compiled program never materialises
an (S, S) logit tensor — required for the 32k-prefill dry-run cells to fit.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import (DP, TP, apply_rope, dense, dense_init, head_spec,
                     rmsnorm, rmsnorm_init, shard)


# --------------------------------------------------------------------------
# chunked causal attention core
# --------------------------------------------------------------------------

def causal_attention(q, k, v, *, causal: bool = True,
                     q_chunk: int = 512) -> jax.Array:
    """q: (B, S, H, dh); k, v: (B, Skv, Hkv, dh) -> (B, S, H, dh).

    Grouped-query: H = g * Hkv. Chunked over queries; logits per chunk are
    (B, Hkv, g, qc, Skv) — O(S) memory, never O(S^2). causal=False gives
    bidirectional/cross attention (whisper encoder, cross-attn)."""
    B, S, H, dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = dh ** -0.5
    qc = min(q_chunk, S)
    assert S % qc == 0
    nq = S // qc
    qr = (q.reshape(B, nq, qc, Hkv, g, dh) * scale).astype(q.dtype)
    qr = jnp.moveaxis(qr, 1, 0)                       # (nq, B, qc, Hkv, g, dh)

    kpos = jnp.arange(Skv)

    def one_chunk(i, qb):
        logits = jnp.einsum("bqhgd,bshd->bhgqs", qb.astype(jnp.float32),
                            k.astype(jnp.float32))
        if causal:
            qpos = i * qc + jnp.arange(qc)
            mask = qpos[:, None] >= kpos[None, :]
            logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhgqs,bshd->bqhgd", w.astype(v.dtype), v)

    out = jax.lax.map(lambda iq: one_chunk(iq[0], iq[1]),
                      (jnp.arange(nq), qr))
    # value head dim may differ from query head dim (MLA)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])
    return out


def decode_attention(q, k, v, valid=None) -> jax.Array:
    """One-token decode: q (B, H, dh) against cache k/v stored in the
    decode-native layout (B, Hkv, S, dh) — no transpose touches the cache
    (the §Perf layout lever). valid: optional (S,) mask of live slots."""
    B, H, dh = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    qr = q.reshape(B, Hkv, g, dh) * dh ** -0.5
    logits = jnp.einsum("bhgd,bhsd->bhgs", qr.astype(jnp.float32),
                        k.astype(jnp.float32))
    if valid is not None:
        logits = jnp.where(valid[None, None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", w.astype(v.dtype), v)
    return out.reshape(B, H, dh)


def clustered_decode_attention(q, k, v, centroids, members, member_mask,
                               top_p: int, self_kv=None) -> jax.Array:
    """k²-attention decode: attend only to members of the top_p nearest
    KV clusters (paper's k_n-restriction applied to the KV cache).

    q: (B, H, dh); k, v: (B, Hkv, S, dh) decode-native layout;
    centroids: (B, Hkv, kc, dh); members: (B, Hkv, kc, cap) int32 into S;
    member_mask: bool same shape. self_kv: optional (k_new, v_new) each
    (B, Hkv, dh) — the token being decoded joins the softmax exactly even
    before it is clustered. Cost O(kc + top_p*cap) per head, O(S) never
    touched (no transpose of the cache)."""
    B, H, dh = q.shape
    Hkv, kc, cap = centroids.shape[1], centroids.shape[2], members.shape[3]
    g = H // Hkv
    qr = q.reshape(B, Hkv, g, dh)
    # nearest clusters by squared distance (same metric as the paper)
    d2 = (jnp.sum(qr * qr, -1)[..., None]
          - 2.0 * jnp.einsum("bhgd,bhkd->bhgk", qr, centroids)
          + jnp.sum(centroids * centroids, -1)[:, :, None, :])
    _, top = jax.lax.top_k(-d2, top_p)                # (B, Hkv, g, p)
    sel = jnp.take_along_axis(members[:, :, None], top[..., None], axis=3)
    selm = jnp.take_along_axis(member_mask[:, :, None], top[..., None], axis=3)
    sel = sel.reshape(B, Hkv, g, top_p * cap)         # token indices
    selm = selm.reshape(B, Hkv, g, top_p * cap)
    kk = jnp.take_along_axis(k[:, :, None], sel[..., None], axis=3)
    vv = jnp.take_along_axis(v[:, :, None], sel[..., None], axis=3)
    if self_kv is not None:
        k_new, v_new = self_kv
        kk = jnp.concatenate(
            [kk, jnp.broadcast_to(k_new[:, :, None, None],
                                  (B, Hkv, g, 1, dh))], axis=3)
        vv = jnp.concatenate(
            [vv, jnp.broadcast_to(v_new[:, :, None, None],
                                  (B, Hkv, g, 1, dh)).astype(vv.dtype)],
            axis=3)
        selm = jnp.concatenate(
            [selm, jnp.ones((B, Hkv, g, 1), bool)], axis=3)
    logits = jnp.einsum("bhgd,bhgmd->bhgm", qr.astype(jnp.float32),
                        kk.astype(jnp.float32)) * dh ** -0.5
    logits = jnp.where(selm, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(selm, w, 0.0).astype(vv.dtype)
    out = jnp.einsum("bhgm,bhgmd->bhgd", w, vv)
    return out.reshape(B, H, dh)


def _select_top_clusters(qr, centroids, top_p):
    """(B,Hkv,g,dh) x (B,Hkv,kc,dh) -> (B,Hkv,g,p) nearest-cluster ids."""
    d2 = (jnp.sum(qr * qr, -1)[..., None]
          - 2.0 * jnp.einsum("bhgd,bhkd->bhgk", qr, centroids)
          + jnp.sum(centroids * centroids, -1)[:, :, None, :])
    _, top = jax.lax.top_k(-d2, top_p)
    return top


def _cm_partial(qr, kt, vt, sizes, sel, local_base, dh):
    """Online-softmax partials over the locally available selected
    clusters. kt/vt: (B,Hkv,KC_loc,cap,dh); sel: (B,Hkv,g,p) GLOBAL ids;
    local ids are sel - local_base when within [0, KC_loc).
    Returns (m (B,Hkv,g), l (B,Hkv,g), acc (B,Hkv,g,dh)) f32."""
    B, Hkv, kc_loc, cap, _ = kt.shape
    loc = sel - local_base
    here = (loc >= 0) & (loc < kc_loc)                # (B,Hkv,g,p)
    loc = jnp.clip(loc, 0, kc_loc - 1)
    kk = jnp.take_along_axis(kt[:, :, None], loc[..., None, None], axis=3)
    vv = jnp.take_along_axis(vt[:, :, None], loc[..., None, None], axis=3)
    sz = jnp.take_along_axis(sizes[:, :, None], loc, axis=3)   # (B,Hkv,g,p)
    valid = (jnp.arange(cap)[None, None, None, None, :]
             < sz[..., None]) & here[..., None]
    logits = jnp.einsum("bhgd,bhgpcd->bhgpc", qr.astype(jnp.float32),
                        kk.astype(jnp.float32)) * dh ** -0.5
    logits = jnp.where(valid, logits, -jnp.inf)
    logits = logits.reshape(*logits.shape[:3], -1)             # (B,Hkv,g,p*cap)
    vv = vv.reshape(*vv.shape[:3], -1, vv.shape[-1])
    m = jnp.max(logits, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w = jnp.where(jnp.isfinite(logits), jnp.exp(logits - m_safe[..., None]),
                  0.0)
    l = jnp.sum(w, axis=-1)
    acc = jnp.einsum("bhgm,bhgmd->bhgd", w, vv.astype(jnp.float32))
    return m, l, acc


def cluster_major_decode_attention(q, kt, vt, centroids, sizes, top_p: int,
                                   self_kv=None, ring=None) -> jax.Array:
    """k²-attention over the cluster-major KV cache.

    q: (B, H, dh); kt/vt: (B, Hkv, kc, cap, dh) — the cache stored sorted
    by k²-means cluster; centroids: (B, Hkv, kc, dh); sizes: (B, Hkv, kc).
    ring: optional (ring_k, ring_v, fill) — a small exact recent-token
    buffer ((B, Hkv, R, dh) x2 + scalar fill); decoded tokens append there
    so the big tables stay READ-ONLY during decode (no O(cache) copy per
    layer; a maintenance recluster() absorbs the ring periodically).

    Distribution (§Perf, beyond-paper): the kc axis shards over the data
    axes. Under a mesh, a shard_map computes each shard's online-softmax
    partials over ITS selected clusters (selection is replicated, the
    top-p read never crosses shards) and merges with a tiny psum of
    (max, sum, acc) — collective volume O(B*H*dh), independent of S."""
    from jax.interpreters import pxla

    from jax import shard_map

    B, H, dh = q.shape
    Hkv, kc, cap = centroids.shape[1], centroids.shape[2], kt.shape[3]
    g = H // Hkv
    qr = q.reshape(B, Hkv, g, dh)
    sel = _select_top_clusters(qr, centroids, top_p)           # replicated

    mesh = pxla.thread_resources.env.physical_mesh
    data_axes = tuple(a for a in getattr(mesh, "axis_names", ())
                      if a in ("pod", "data"))
    dsz = 1
    for a in data_axes:
        dsz *= mesh.shape[a]
    if mesh.empty or dsz <= 1 or kc % dsz != 0:
        m, l, acc = _cm_partial(qr, kt, vt, sizes, sel, 0, dh)
    else:
        spec_t = P(None, None, data_axes, None, None)
        spec_s = P(None, None, data_axes)

        def partial_fn(qr_l, kt_l, vt_l, sizes_l, sel_l):
            idx = jax.lax.axis_index(data_axes[0]) if len(data_axes) == 1 \
                else (jax.lax.axis_index(data_axes[0]) * mesh.shape[data_axes[1]]
                      + jax.lax.axis_index(data_axes[1]))
            base = idx * (kc // dsz)
            m, l, acc = _cm_partial(qr_l, kt_l, vt_l, sizes_l, sel_l,
                                    base, dh)
            # logsumexp merge across cluster shards (tiny collective)
            gm = jax.lax.pmax(m, data_axes)
            gm_safe = jnp.where(jnp.isfinite(gm), gm, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - gm_safe), 0.0)
            l = jax.lax.psum(l * corr, data_axes)
            acc = jax.lax.psum(acc * corr[..., None], data_axes)
            return gm, l, acc

        m, l, acc = shard_map(
            partial_fn, mesh=mesh,
            in_specs=(P(), spec_t, spec_t, spec_s, P()),
            out_specs=(P(), P(), P()),
            check_vma=False)(qr, kt, vt, sizes, sel)

    if ring is not None:
        ring_k, ring_v, fill = ring                            # (B,Hkv,R,dh)
        R = ring_k.shape[2]
        r_log = jnp.einsum("bhgd,bhrd->bhgr", qr.astype(jnp.float32),
                           ring_k.astype(jnp.float32)) * dh ** -0.5
        live = jnp.arange(R)[None, None, None, :] < jnp.minimum(fill, R)
        r_log = jnp.where(live, r_log, -jnp.inf)
        m_r = jnp.max(r_log, axis=-1)
        m_new = jnp.maximum(m, m_r)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        w_r = jnp.where(live, jnp.exp(r_log - m_safe[..., None]), 0.0)
        l = l * corr + jnp.sum(w_r, -1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgr,bhrd->bhgd", w_r, ring_v.astype(jnp.float32))
        m = m_new
    if self_kv is not None:
        k_new, v_new = self_kv                                 # (B,Hkv,dh)
        s_log = jnp.einsum("bhgd,bhd->bhg", qr.astype(jnp.float32),
                           k_new.astype(jnp.float32)) * dh ** -0.5
        m_new = jnp.maximum(m, s_log)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        w_self = jnp.exp(s_log - m_safe)
        l = l * corr + w_self
        acc = acc * corr[..., None] + w_self[..., None] \
            * v_new[:, :, None].astype(jnp.float32)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, H, dh).astype(q.dtype)


# --------------------------------------------------------------------------
# GQA block
# --------------------------------------------------------------------------

def gqa_init(key, d: int, n_heads: int, n_kv: int, d_head: int,
             qk_norm: bool, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], d, n_heads * d_head, dtype),
         "wk": dense_init(ks[1], d, n_kv * d_head, dtype),
         "wv": dense_init(ks[2], d, n_kv * d_head, dtype),
         "wo": dense_init(ks[3], n_heads * d_head, d, dtype)}
    if qk_norm:
        p["qn"] = rmsnorm_init(d_head, dtype)
        p["kn"] = rmsnorm_init(d_head, dtype)
    return p


def gqa_project(p, x, n_heads: int, n_kv: int, d_head: int, positions,
                rope_theta: float, qk_norm: bool):
    B = x.shape[0]
    q = dense(p["wq"], x).reshape(B, -1, n_heads, d_head)
    k = dense(p["wk"], x).reshape(B, -1, n_kv, d_head)
    v = dense(p["wv"], x).reshape(B, -1, n_kv, d_head)
    if qk_norm:
        q = rmsnorm(p["qn"], q)
        k = rmsnorm(p["kn"], k)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    q = shard(q, head_spec(n_heads))
    k = shard(k, head_spec(n_kv))
    return q, k, v


def gqa_apply(p, x, *, n_heads, n_kv, d_head, rope_theta=1e4, qk_norm=False,
              q_chunk=512):
    """Training/prefill self-attention. x: (B, S, d)."""
    B, S, d = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = gqa_project(p, x, n_heads, n_kv, d_head, positions,
                          rope_theta, qk_norm)
    out = causal_attention(q, k, v, q_chunk=q_chunk)
    return dense(p["wo"], out.reshape(B, S, n_heads * d_head)), (k, v)


def gqa_decode_cluster_major(p, x, cache_l, cur_pos, *, n_heads, n_kv,
                             d_head, rope_theta=1e4, qk_norm=False,
                             top_p: int = 16):
    """One-token decode against a cluster-major cache (no flat K/V at all).
    cache_l: {"kt","vt","cent","sizes","ring_k","ring_v","ring_fill"}.
    Attention = top-p clusters + exact recent ring + self token; the fresh
    K/V is appended to the RING only — the big tables are read-only inside
    the decode step (no O(cache) copy per layer; recluster() maintenance
    absorbs the ring every R steps). Returns (out, updated-mutable-fields)
    — kt/vt are intentionally NOT in the update (they pass through)."""
    B = x.shape[0]
    positions = jnp.full((B, 1), cur_pos)
    q, k_new, v_new = gqa_project(p, x, n_heads, n_kv, d_head, positions,
                                  rope_theta, qk_norm)
    q = q[:, 0]
    k1, v1 = k_new[:, 0], v_new[:, 0]                 # (B, n_kv, dh)
    ring = (cache_l["ring_k"], cache_l["ring_v"], cache_l["ring_fill"])
    out = cluster_major_decode_attention(
        q, cache_l["kt"], cache_l["vt"], cache_l["cent"], cache_l["sizes"],
        top_p, self_kv=(k1, v1), ring=ring)
    R = cache_l["ring_k"].shape[2]
    slot = cache_l["ring_fill"] % R
    ring_k = jax.lax.dynamic_update_slice(
        cache_l["ring_k"], k1[:, :, None].astype(cache_l["ring_k"].dtype),
        (0, 0, slot, 0))
    ring_v = jax.lax.dynamic_update_slice(
        cache_l["ring_v"], v1[:, :, None].astype(cache_l["ring_v"].dtype),
        (0, 0, slot, 0))
    return (dense(p["wo"], out.reshape(B, 1, n_heads * d_head)),
            {"ring_k": ring_k, "ring_v": ring_v,
             "ring_fill": cache_l["ring_fill"] + 1})


def gqa_decode(p, x, cache_k, cache_v, cur_pos, *, n_heads, n_kv, d_head,
               rope_theta=1e4, qk_norm=False, clusters=None, top_p: int = 16):
    """One-token decode with an in-place (positional) KV cache.

    x: (B, 1, d); cache_k/v: (B, n_kv, S, d_head) decode-native layout;
    the new K/V is written at slot ``cur_pos`` and attention masks slots
    > cur_pos. clusters: optional (centroids, members, member_mask)
    enables k²-attention (sub-quadratic).
    Returns (out (B, 1, d), new_cache_k, new_cache_v, k_new (B, n_kv, dh))."""
    B = x.shape[0]
    S = cache_k.shape[2]
    positions = jnp.full((B, 1), cur_pos)
    q, k_new, v_new = gqa_project(p, x, n_heads, n_kv, d_head, positions,
                                  rope_theta, qk_norm)
    q = q[:, 0]                                       # (B, H, dh)
    k_row = jnp.moveaxis(k_new, 1, 2)                 # (B, n_kv, 1, dh)
    v_row = jnp.moveaxis(v_new, 1, 2)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k_row.astype(cache_k.dtype), (0, 0, cur_pos, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v_row.astype(cache_v.dtype), (0, 0, cur_pos, 0))
    if clusters is None:
        valid = jnp.arange(S) <= cur_pos
        out = decode_attention(q, cache_k, cache_v, valid)
    else:
        centroids, members, member_mask = clusters
        # the fresh token joins the softmax exactly (its key may not be in
        # any cluster yet)
        out = clustered_decode_attention(q, cache_k, cache_v, centroids,
                                         members, member_mask, top_p,
                                         self_kv=(k_new[:, 0], v_new[:, 0]))
    return (dense(p["wo"], out.reshape(B, 1, n_heads * d_head)),
            cache_k, cache_v, k_new[:, 0])


# --------------------------------------------------------------------------
# MLA (Multi-head Latent Attention, DeepSeek-V2) — caches only the latent
# --------------------------------------------------------------------------

class MLADims(NamedTuple):
    kv_lora: int
    nope: int
    rope: int
    v_dim: int


def mla_init(key, d: int, n_heads: int, dims: MLADims, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], d, n_heads * (dims.nope + dims.rope), dtype),
        "wdkv": dense_init(ks[1], d, dims.kv_lora, dtype),
        "wkpe": dense_init(ks[2], d, dims.rope, dtype),
        "wuk": dense_init(ks[3], dims.kv_lora, n_heads * dims.nope, dtype),
        "wuv": dense_init(ks[4], dims.kv_lora, n_heads * dims.v_dim, dtype),
        "wo": dense_init(ks[5], n_heads * dims.v_dim, d, dtype),
        "kvn": rmsnorm_init(dims.kv_lora, dtype),
    }


def mla_apply(p, x, *, n_heads: int, dims: MLADims, rope_theta=1e4,
              q_chunk=512):
    """Training/prefill MLA. Returns (out, latent_cache (B, S, r + rope))."""
    B, S, d = x.shape
    positions = jnp.arange(S)[None, :]
    q = dense(p["wq"], x).reshape(B, S, n_heads, dims.nope + dims.rope)
    q_nope, q_pe = q[..., :dims.nope], q[..., dims.nope:]
    q_pe = apply_rope(q_pe, positions, rope_theta)

    c_kv = rmsnorm(p["kvn"], dense(p["wdkv"], x))     # (B, S, r)
    k_pe = apply_rope(dense(p["wkpe"], x)[:, :, None], positions,
                      rope_theta)                     # (B, S, 1, rope)
    k_nope = dense(p["wuk"], c_kv).reshape(B, S, n_heads, dims.nope)
    v = dense(p["wuv"], c_kv).reshape(B, S, n_heads, dims.v_dim)

    qf = jnp.concatenate([q_nope, q_pe], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, (B, S, n_heads, dims.rope))], -1)
    out = causal_attention(qf, kf, v, q_chunk=q_chunk)
    latent = jnp.concatenate([c_kv, k_pe[:, :, 0]], -1)
    return dense(p["wo"], out.reshape(B, S, -1)), latent


def mla_decode(p, x, latent_cache, cur_pos, *, n_heads: int, dims: MLADims,
               rope_theta=1e4):
    """One-token MLA decode; positional update of the latent cache
    (B, S, r + rope). Returns (out, new_latent_cache)."""
    B = x.shape[0]
    S = latent_cache.shape[1]
    positions = jnp.full((B, 1), cur_pos)
    q = dense(p["wq"], x).reshape(B, 1, n_heads, dims.nope + dims.rope)
    q_nope, q_pe = q[..., :dims.nope], q[..., dims.nope:]
    q_pe = apply_rope(q_pe, positions, rope_theta)

    c_new = rmsnorm(p["kvn"], dense(p["wdkv"], x))
    kpe_new = apply_rope(dense(p["wkpe"], x)[:, :, None], positions,
                         rope_theta)[:, :, 0]
    latent_new = jnp.concatenate([c_new, kpe_new], -1)  # (B, 1, r+rope)
    lat = jax.lax.dynamic_update_slice(
        latent_cache, latent_new.astype(latent_cache.dtype), (0, cur_pos, 0))
    c_kv, k_pe = lat[..., :dims.kv_lora], lat[..., dims.kv_lora:]
    valid = jnp.arange(S) <= cur_pos

    # absorbed attention: score = (q_nope W_uk^T) . c + q_pe . k_pe — the
    # per-head key up-projection is folded into the query so decode works
    # directly on the latent cache (MLA's memory win).
    wuk = p["wuk"]["w"].reshape(dims.kv_lora, n_heads,
                                dims.nope).astype(jnp.float32)
    q_abs = jnp.einsum("bohn,rhn->bohr", q_nope.astype(jnp.float32), wuk)
    # q_abs: (B, 1, H, r); logits against latent cache
    logits = (jnp.einsum("bohr,bsr->bhos", q_abs,
                         c_kv.astype(jnp.float32))[:, :, 0]
              + jnp.einsum("bohe,bse->bhos", q_pe.astype(jnp.float32),
                           k_pe.astype(jnp.float32))[:, :, 0])
    logits = logits * (dims.nope + dims.rope) ** -0.5
    logits = jnp.where(valid[None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)               # (B, H, S)
    ctx = jnp.einsum("bhs,bsr->bhr", w, c_kv.astype(jnp.float32))
    out = jnp.einsum("bhr,rhv->bhv", ctx,
                     p["wuv"]["w"].reshape(dims.kv_lora, n_heads,
                                           dims.v_dim).astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, 1, n_heads * dims.v_dim)
    return dense(p["wo"], out), lat
