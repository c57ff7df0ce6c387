"""Greedy Divisive Initialization (GDI) — the paper's Algorithm 2 + 3.

Two executions of the same algorithm live here:

``gdi_init`` (host loop, the parity/benchmark baseline)
    One leaf at a time. ProjectiveSplit runs over the *full* (n, d) array
    with a membership mask so every split reuses one fixed-shape XLA
    program. Lemma 1's incremental energy update becomes a vectorised
    cumulative-sum identity:

        phi(prefix_l) = cumsum(||x||^2)_l - ||cumsum(x)_l||^2 / l

    which yields every candidate split energy of the scanned hyperplane in
    a single pass, exactly matching the paper's O(|X_j|) per-iteration
    cost in counted vector ops (members only are charged). Structural
    cost: k-1 sequential dispatches, each O(n (d + log n)) regardless of
    leaf size, with two device->host syncs per split.

``gdi_device_init`` (frontier-batched, the fast path — DESIGN.md §4)
    One jitted *round step* splits every frontier leaf at once over the
    cluster-grouped layout (kernels.ops.group_by_cluster_device): the
    direction projection + Lemma-1 sweep run as a *segmented* sort/cumsum
    (kernels/segmented_scan.py on TPU, the jax.ops.segment_* reference
    off-TPU), split positions fall out of per-segment masked argmins, and
    greedy leaf selection is a device-side energy argsort. Each round
    costs O(n (d + log n)) *total* — independent of the frontier size —
    and the host reads back a single scalar (the leaf count) per round,
    so a k-way init takes ~log2 k round dispatches instead of k-1 split
    dispatches.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..kernels.ops import (choose_group_bn, group_by_cluster_device,
                           grouped_capacity, resolve_interpret,
                           segmented_scan)
from .opcount import OpCounter

_INF = jnp.inf


@functools.partial(jax.jit, static_argnames=("iters",))
def projective_split(x: jax.Array, mask: jax.Array, key: jax.Array,
                     iters: int = 2):
    """Min-energy split of the masked subset along the c_a - c_b direction.

    Returns (mask_a, mask_b, c_a, c_b, phi_a, phi_b).
    """
    n, d = x.shape
    fmask = mask.astype(x.dtype)
    m = jnp.sum(fmask)

    # Two random member samples as the initial centers (Algorithm 3 line 2).
    p = fmask / jnp.maximum(m, 1.0)
    k1, k2 = jax.random.split(key)
    i_a = jax.random.choice(k1, n, p=p)
    # Draw the second sample excluding the first (approximate distinctness —
    # identical duplicates are harmless, the scan still yields a valid split).
    p2 = p.at[i_a].set(0.0)
    p2 = p2 / jnp.maximum(jnp.sum(p2), 1e-30)
    i_b = jax.random.choice(k2, n, p=p2)
    c_a, c_b = x[i_a], x[i_b]

    x_sq = jnp.sum(x * x, axis=-1)

    def body(carry, _):
        c_a, c_b = carry
        direction = c_a - c_b
        proj = x @ direction
        sort_key = jnp.where(mask, proj, _INF)
        order = jnp.argsort(sort_key)
        xs = x[order]
        ms = fmask[order]
        xs_sq = x_sq[order] * ms
        xs_m = xs * ms[:, None]

        csum = jnp.cumsum(xs_m, axis=0)              # (n, d) running sums
        qsum = jnp.cumsum(xs_sq)                     # (n,)  running sq-norms
        cnt = jnp.cumsum(ms)                         # (n,)  running counts
        tot_s, tot_q, tot_c = csum[-1], qsum[-1], cnt[-1]

        phi_p = qsum - jnp.sum(csum * csum, axis=-1) / jnp.maximum(cnt, 1.0)
        sc = tot_c - cnt
        sfx = tot_s[None, :] - csum
        phi_s = (tot_q - qsum) - jnp.sum(sfx * sfx, axis=-1) / jnp.maximum(sc, 1.0)
        score = phi_p + phi_s
        valid = (cnt >= 1.0) & (sc >= 1.0) & (ms > 0)
        score = jnp.where(valid, score, _INF)
        l = jnp.argmin(score)

        c_a_new = csum[l] / jnp.maximum(cnt[l], 1.0)
        c_b_new = (tot_s - csum[l]) / jnp.maximum(tot_c - cnt[l], 1.0)
        # Membership of the A side, scattered back to original order.
        in_a_sorted = (jnp.arange(n) <= l) & (ms > 0)
        mask_a = jnp.zeros((n,), bool).at[order].set(in_a_sorted)
        return (c_a_new, c_b_new), (mask_a, phi_p[l], phi_s[l])

    (c_a, c_b), (masks_a, phis_a, phis_b) = jax.lax.scan(
        body, (c_a, c_b), None, length=iters)
    mask_a = masks_a[-1]
    mask_b = mask & ~mask_a
    return mask_a, mask_b, c_a, c_b, phis_a[-1], phis_b[-1]


def gdi_init(x: jax.Array, k: int, key: jax.Array, *,
             split_iters: int = 2,
             counter: OpCounter | None = None, info: dict | None = None):
    """Algorithm 2: greedy divisive initialization.

    Returns (centers (k, d), assignment (n,)); ``info``, when given, is
    filled with the splits made (``rounds``) and the ``leaves`` reached.
    """
    counter = counter or OpCounter()
    n, d = x.shape
    assert 1 <= k <= n

    mu = jnp.mean(x, axis=0)
    centers = [mu]
    energies = [float(jnp.sum(jnp.square(x - mu)))]
    counter.host_reads += 1
    masks = [jnp.ones((n,), bool)]
    sizes = [n]
    counter.add_additions(n)  # initial mean

    keys = jax.random.split(key, k)
    while len(centers) < k:
        j = int(max(range(len(energies)), key=lambda i: energies[i]))
        if sizes[j] < 2:  # cannot split a singleton; fall back to largest
            j = int(max(range(len(sizes)), key=lambda i: sizes[i]))
            if sizes[j] < 2:
                break
        mask_a, mask_b, c_a, c_b, phi_a, phi_b = projective_split(
            x, masks[j], keys[len(centers)], iters=split_iters)
        m = sizes[j]
        # Paper §2.2 accounting per ProjectiveSplit iteration on X_j:
        # |X_j| inner products + |X_j| incremental mean/energy updates
        # + the sort charged as |X_j| log2 |X_j| / d vector ops.
        counter.add_inner(split_iters * m)
        counter.add_additions(split_iters * m)
        for _ in range(split_iters):
            counter.add_sort(m, d)
        sa = int(jnp.sum(mask_a))
        masks[j] = mask_a
        centers[j] = c_a
        energies[j] = float(phi_a)
        sizes[j] = sa
        masks.append(mask_b)
        centers.append(c_b)
        energies.append(float(phi_b))
        sizes.append(m - sa)
        counter.host_reads += 3                 # sa, phi_a, phi_b

    if info is not None:
        info.update(rounds=len(centers) - 1, leaves=len(centers))
    centers_arr = jnp.stack(centers)
    if len(centers) < k:  # pathological tiny-n fallback: pad with copies
        reps = k - len(centers)
        centers_arr = jnp.concatenate(
            [centers_arr, jnp.tile(centers_arr[-1:], (reps, 1))])
    assignment = jnp.zeros((n,), jnp.int32)
    for j, mk in enumerate(masks):
        assignment = jnp.where(mk, j, assignment)
    return centers_arr, assignment


# ---------------------------------------------------------------------------
# Device-resident frontier-batched GDI (DESIGN.md §4)
# ---------------------------------------------------------------------------


def _segment_argmax(g: jax.Array, a: jax.Array, k: int) -> jax.Array:
    """Per-segment argmax of ``g`` over segments ``a``: (k,) row indices,
    ``n`` for empty segments (earliest row wins ties)."""
    n = g.shape[0]
    m = jax.ops.segment_max(g, a, num_segments=k)
    idx = jnp.where(g >= m[a], jnp.arange(n, dtype=jnp.int32), n)
    return jnp.minimum(jax.ops.segment_min(idx, a, num_segments=k), n)


def _grouped_layout(a: jax.Array, k: int, bn: int):
    """Leaf-grouped row layout (reuses the k²-means grouping pass):
    (row_seg (R,), valid (R,), perm (R,), block2seg (R/bn,))."""
    perm, b2s = group_by_cluster_device(a, k, bn)
    return jnp.repeat(b2s, bn), perm >= 0, perm, b2s


def _hier_cumsum(v: jax.Array, bs: int = 2048) -> jax.Array:
    """Inclusive cumsum along axis 0 as blockwise scans + block offsets —
    markedly faster than a flat jnp.cumsum for long 2-D operands."""
    r = v.shape[0]
    pad = (-r) % bs
    vp = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
    vb = vp.reshape((vp.shape[0] // bs, bs) + vp.shape[1:])
    within = jnp.cumsum(vb, axis=1)
    tot = within[:, -1]
    off = jnp.cumsum(tot, axis=0) - tot
    return (within + off[:, None]).reshape(vp.shape)[:r]


def _segmented_sweep(x, x_sq, a, row_seg, valid, perm, b2s, dirs,
                     tot_s, tot_q, tot_c, split_flag, *, k: int, bn: int,
                     impl: str, interpret: bool):
    """One Lemma-1 sweep over every flagged leaf at once.

    Projects each point onto its leaf's direction, sorts rows within each
    segment by projection (one stable two-key sort over the whole layout),
    runs the segmented scan, and picks the min-energy split per segment
    with a masked argmin. All O(R (d + log R)) regardless of how many
    leaves are flagged. Returns (perm2, rmin, found, cnt_a, c_a, c_b,
    phi_a, phi_b); rmin is the split row in the sorted layout (R when no
    valid split), side A = rows <= rmin of the leaf's segment, perm2 the
    sorted layout's row -> original point map.
    """
    n, d = x.shape
    r = row_seg.shape[0]
    proj_pt = jnp.sum(x * dirs[a], axis=-1)          # O(n d), not O(R d)
    proj = jnp.where(valid, proj_pt[jnp.maximum(perm, 0)], _INF)
    rows = jnp.arange(r, dtype=jnp.int32)
    _, _, order2 = jax.lax.sort((row_seg, proj, rows), num_keys=2,
                                is_stable=True)
    perm2 = perm[order2]
    safe2 = jnp.maximum(perm2, 0)
    ws = (perm2 >= 0).astype(x.dtype)
    xgs = x[safe2]                                   # the one (R, d) gather
    if impl == "pallas":
        csum, qsum, cnt = segmented_scan(xgs, ws, b2s, bn=bn,
                                         interpret=interpret)
    else:
        # Device-resident segment_* formulation (kernels.ref oracle shape),
        # with the exclusive segment offsets gathered at the block-aligned
        # segment starts instead of re-reduced per row.
        gx = _hier_cumsum(xgs * ws[:, None])
        gq = jnp.cumsum(jnp.where(perm2 >= 0, x_sq[safe2], 0.0))
        gc = jnp.cumsum(ws)
        psz = (jnp.ceil(tot_c / bn) * bn).astype(jnp.int32)
        starts = jnp.cumsum(psz) - psz               # (k,) padded row starts
        prev_row = jnp.maximum(starts - 1, 0)
        off_x = jnp.where((starts > 0)[:, None], gx[prev_row], 0.0)
        off_q = jnp.where(starts > 0, gq[prev_row], 0.0)
        off_c = jnp.where(starts > 0, gc[prev_row], 0.0)
        csum = gx - off_x[row_seg]
        qsum = gq - off_q[row_seg]
        cnt = gc - off_c[row_seg]
    rem = tot_c[row_seg] - cnt
    phi_p = qsum - jnp.sum(csum * csum, axis=-1) / jnp.maximum(cnt, 1.0)
    sfx = tot_s[row_seg] - csum
    phi_s = (tot_q[row_seg] - qsum) \
        - jnp.sum(sfx * sfx, axis=-1) / jnp.maximum(rem, 1.0)
    ok = (ws > 0) & (cnt >= 1.0) & (rem >= 1.0) & split_flag[row_seg]
    score = jnp.where(ok, phi_p + phi_s, _INF)
    smin = jax.ops.segment_min(score, row_seg, num_segments=k)
    hit = ok & (score <= smin[row_seg])
    rmin = jnp.minimum(
        jax.ops.segment_min(jnp.where(hit, rows, r), row_seg,
                            num_segments=k), r)
    found = rmin < r
    rsafe = jnp.minimum(rmin, r - 1)
    cnt_a = cnt[rsafe]
    c_a = csum[rsafe] / jnp.maximum(cnt_a, 1.0)[:, None]
    c_b = (tot_s - csum[rsafe]) \
        / jnp.maximum(tot_c - cnt_a, 1.0)[:, None]
    phi_a = jnp.maximum(phi_p[rsafe], 0.0)
    phi_b = jnp.maximum(phi_s[rsafe], 0.0)
    return perm2, rmin, found, cnt_a, c_a, c_b, phi_a, phi_b


@functools.partial(jax.jit,
                   static_argnames=("k", "bn", "impl", "interpret"))
def segmented_split_sweep(x: jax.Array, a: jax.Array, c_a: jax.Array,
                          c_b: jax.Array, *, k: int, bn: int = 8,
                          impl: str = "xla",
                          interpret: bool | None = None):
    """Standalone single sweep (the testable unit of the round step).

    Splits every leaf of the assignment ``a`` with >= 2 members along its
    (c_a - c_b) direction. Returns (found (k,), cnt_a (k,), c_a' (k, d),
    c_b' (k, d), phi_a (k,), phi_b (k,)). interpret=None auto-selects
    interpret mode off-TPU.
    """
    interpret = resolve_interpret(interpret)
    n = x.shape[0]
    x_sq = jnp.sum(x * x, -1)
    tot_s = jax.ops.segment_sum(x, a, num_segments=k)
    tot_q = jax.ops.segment_sum(x_sq, a, num_segments=k)
    tot_c = jax.ops.segment_sum(jnp.ones((n,), x.dtype), a, num_segments=k)
    row_seg, valid, perm, b2s = _grouped_layout(a, k, bn)
    out = _segmented_sweep(x, x_sq, a, row_seg, valid, perm, b2s, c_a - c_b,
                           tot_s, tot_q, tot_c, tot_c >= 2.0,
                           k=k, bn=bn, impl=impl, interpret=interpret)
    return out[2], out[3], out[4], out[5], out[6], out[7]


@functools.partial(jax.jit,
                   static_argnames=("k", "bn", "split_iters", "impl",
                                    "interpret", "frontier"))
def gdi_round_step(x, a, centers, energies, sizes, nleaf, key, *, k: int,
                   bn: int, split_iters: int = 2, impl: str = "xla",
                   interpret: bool | None = None,
                   frontier: float = 0.125):
    """One frontier round: split the top-t leaves by energy all at once.

    State: a (n,) leaf assignment, centers (k, d), energies (k,),
    sizes (k,) int32, nleaf () int32 — all device-resident; nothing here
    forces a host sync. t = min(#splittable, k - nleaf,
    max(1, floor(frontier * min(nleaf, k - nleaf)))): leaves are re-ranked
    by energy every round and only the top ``frontier`` fraction splits,
    so low-energy leaves are left alone exactly as the sequential greedy
    would (``frontier=1.0`` is blind doubling, the round-parallel
    variant).
    Side A of leaf j keeps id j; side B gets the next free slot. Returns
    the updated state tuple. interpret=None auto-selects interpret mode
    off-TPU.
    """
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    slot = jnp.arange(k, dtype=jnp.int32)
    eligible = (slot < nleaf) & (sizes >= 2)
    n_elig = jnp.sum(eligible.astype(jnp.int32))
    t = jnp.minimum(n_elig, k - nleaf)
    if frontier < 1.0:
        # batches shrink with the remaining split budget k - L as well as
        # grow with L: committing a large batch against a stale ranking
        # is most costly when few splits remain
        t = jnp.minimum(
            t, jnp.maximum(1, (jnp.minimum(nleaf, k - nleaf)
                               * jnp.float32(frontier)).astype(jnp.int32)))
    order = jnp.argsort(jnp.where(eligible, -energies, _INF))
    rank = jnp.zeros((k,), jnp.int32).at[order].set(slot)
    split_flag = eligible & (rank < t)

    x_sq = jnp.sum(x * x, axis=-1)
    tot_s = jax.ops.segment_sum(x, a, num_segments=k)
    tot_q = jax.ops.segment_sum(x_sq, a, num_segments=k)
    tot_c = jax.ops.segment_sum(jnp.ones((n,), x.dtype), a, num_segments=k)

    # Two uniform random members per leaf as the initial split direction
    # (Algorithm 3 line 2), all leaves at once via per-segment argmax of
    # uniform draws; the second draw excludes the first member.
    k1, k2 = jax.random.split(key)
    g1 = jax.random.uniform(k1, (n,))
    g2 = jax.random.uniform(k2, (n,))
    i_a = _segment_argmax(g1, a, k)
    g2 = g2.at[jnp.where(i_a < n, i_a, n)].set(-1.0, mode="drop")
    i_b = _segment_argmax(g2, a, k)
    c_a = x[jnp.minimum(i_a, n - 1)]
    c_b = x[jnp.minimum(i_b, n - 1)]

    row_seg, valid, perm, b2s = _grouped_layout(a, k, bn)
    for _ in range(split_iters):
        perm2, rmin, found, cnt_a, c_a_new, c_b_new, phi_a, phi_b = \
            _segmented_sweep(x, x_sq, a, row_seg, valid, perm, b2s,
                             c_a - c_b, tot_s, tot_q, tot_c, split_flag,
                             k=k, bn=bn, impl=impl, interpret=interpret)
        upd = (split_flag & found)[:, None]
        c_a = jnp.where(upd, c_a_new, c_a)
        c_b = jnp.where(upd, c_b_new, c_b)

    success = split_flag & found
    # children take the next free slots in slot order (dense, so nleaf
    # stays the exact count of live leaves even if a flagged leaf found
    # no valid split)
    child = nleaf + jnp.cumsum(success.astype(jnp.int32)) - 1
    child_idx = jnp.where(success, child, k)

    r = row_seg.shape[0]
    in_b = (jnp.arange(r, dtype=jnp.int32) > rmin[row_seg]) \
        & success[row_seg]
    new_id = jnp.where(in_b, child[row_seg], row_seg).astype(jnp.int32)
    a_new = a.at[jnp.where(perm2 >= 0, perm2, n)].set(new_id, mode="drop")

    size_a = cnt_a.astype(jnp.int32)
    centers = jnp.where(success[:, None], c_a, centers)
    centers = centers.at[child_idx].set(
        jnp.where(success[:, None], c_b, 0.0), mode="drop")
    energies = jnp.where(success, phi_a, energies)
    energies = energies.at[child_idx].set(
        jnp.where(success, phi_b, 0.0), mode="drop")
    sizes_new = jnp.where(success, size_a, sizes)
    sizes_new = sizes_new.at[child_idx].set(
        jnp.where(success, sizes - size_a, 0), mode="drop")
    nleaf = nleaf + jnp.sum(success.astype(jnp.int32))
    return a_new, centers, energies, sizes_new, nleaf


def _device_state(x, k: int):
    """Initial round-step state: one leaf holding everything."""
    n, d = x.shape
    mu = jnp.mean(x, axis=0)
    centers = jnp.zeros((k, d), x.dtype).at[0].set(mu)
    energies = jnp.zeros((k,), x.dtype).at[0].set(
        jnp.sum(jnp.square(x - mu)))
    sizes = jnp.zeros((k,), jnp.int32).at[0].set(n)
    return (jnp.zeros((n,), jnp.int32), centers, energies, sizes,
            jnp.asarray(1, jnp.int32))


def _auto_impl(impl: str | None, interpret: bool | None):
    if impl is None:
        impl = "xla" if resolve_interpret() else "pallas"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl {impl!r}; expected 'pallas' or 'xla'")
    return impl, resolve_interpret(interpret)


def _charge_round(counter: OpCounter, r: int, n: int, d: int,
                  split_iters: int) -> None:
    """Paper-unit accounting of what one device round actually executes:
    one grouping sort, the totals segment-sum, and split_iters x
    (projection inner products + sweep sort + scan additions) over the
    full R-row layout."""
    counter.add_inner(split_iters * r)
    counter.add_additions(split_iters * r + n)
    for _ in range(split_iters + 1):
        counter.add_sort(r, d)


def _frontier_rounds(x, state, key, counter: OpCounter, *, k: int,
                     bn: int, r: int, split_iters: int, impl: str,
                     interpret: bool, frontier: float,
                     max_rounds: int | None = None):
    """Frontier rounds from ``state`` until ``k`` leaves, ``max_rounds``
    rounds, or a round that splits nothing. Each round is the span
    ``kmeans.init.round``: its dispatch and its one host read, the leaf
    count. Returns (state, leaves, rounds run)."""
    n, d = x.shape
    nleaf, rounds = 1, 0
    while nleaf < k and (max_rounds is None or rounds < max_rounds):
        with jax.profiler.TraceAnnotation("kmeans.init.round") as span:
            key, sub = jax.random.split(key)
            state = gdi_round_step(x, *state, sub, k=k, bn=bn,
                                   split_iters=split_iters, impl=impl,
                                   interpret=interpret, frontier=frontier)
            _charge_round(counter, r, n, d, split_iters)
            new_nleaf = int(state[4])           # the round's one host read
            counter.host_reads += 1
            if span.is_enabled():
                span.set_metadata(round=rounds, leaves=new_nleaf)
        rounds += 1
        if new_nleaf == nleaf:
            break                               # nothing splittable left
        nleaf = new_nleaf
    return state, nleaf, rounds


def gdi_device_init(x: jax.Array, k: int, key: jax.Array, *,
                    split_iters: int = 2,
                    counter: OpCounter | None = None,
                    bn: int | None = None, impl: str | None = None,
                    interpret: bool | None = None,
                    frontier: float = 0.125, info: dict | None = None):
    """Frontier-batched greedy divisive initialization, device-resident.

    Same algorithm as ``gdi_init`` (greedy: highest-energy leaves split
    first) but batched: each round re-ranks the leaves by energy on
    device and splits the top ``frontier`` fraction at once through
    ``gdi_round_step``, so a k-way init is ~log_{1+frontier}(k) jitted
    dispatches with one scalar host read each instead of k-1 splits with
    two syncs each. impl: "pallas" routes the segmented scan through the
    Pallas kernel, "xla" through the segment_* reference (the off-TPU
    default — interpret-mode Pallas would serialize on the grid).
    Returns (centers (k, d), assignment (n,)); ``info``, when given, is
    filled with the ``rounds`` run and the ``leaves`` reached.
    """
    counter = counter or OpCounter()
    n, d = x.shape
    assert 1 <= k <= n
    impl, interpret = _auto_impl(impl, interpret)
    # the Pallas scan wants MXU-sized blocks; the XLA path has no block
    # constraint, so it minimizes the grouped layout's padding (R -> ~n)
    bn = bn or (choose_group_bn(n, k, d) if impl == "pallas" else 8)
    r = grouped_capacity(n, k, bn) * bn

    counter.add_additions(n)                    # initial mean
    state, nleaf, rounds = _frontier_rounds(
        x, _device_state(x, k), key, counter, k=k, bn=bn, r=r,
        split_iters=split_iters, impl=impl, interpret=interpret,
        frontier=frontier)
    if info is not None:
        info.update(rounds=rounds, leaves=nleaf)
    a, centers = state[0], state[1]
    if nleaf < k:   # pathological tiny-n fallback: pad with copies
        centers = jnp.where((jnp.arange(k) < nleaf)[:, None], centers,
                            centers[max(nleaf - 1, 0)])
    return centers, a


def frontier_round_bound(k: int, frontier: float) -> int:
    """Rounds the frontier schedule needs to reach ``k`` leaves when every
    flagged leaf splits (the optimistic trip count — mirrors
    ``gdi_round_step``'s t formula with n_elig = nleaf). Fixed-trip-count
    callers add slack rounds to absorb failed splits; surplus rounds
    no-op once nleaf == k."""
    leaves, rounds = 1, 0
    while leaves < k:
        t = min(leaves, k - leaves)
        if frontier < 1.0:
            t = min(t, max(1, int(frontier * min(leaves, k - leaves))))
        leaves += t
        rounds += 1
    return rounds


def gdi_fixed_rounds(x: jax.Array, kcap: int, key: jax.Array, *,
                     rounds: int | None = None, split_iters: int = 2,
                     bn: int = 8, impl: str = "xla",
                     interpret: bool = False, frontier: float = 1.0):
    """Traceable GDI: a *fixed* trip count of frontier rounds toward
    ``kcap`` leaves, with no host reads — the per-shard seeding program
    of the distributed path (``core.distributed``, DESIGN.md §7): every
    shard-group runs this under shard_map on its local rows, then the
    driver merges the per-shard leaf centers globally. ``rounds``
    defaults to :func:`frontier_round_bound` for the given ``frontier``
    (``1.0`` = blind doubling, ceil(log2 kcap) rounds; the greedy
    ``0.125`` default of ``gdi_device_init`` takes more rounds but keeps
    its energy fidelity). Returns the raw round-step state
    ``(a, centers, energies, sizes, nleaf)``.
    """
    if rounds is None:
        rounds = frontier_round_bound(kcap, frontier)
    state = _device_state(x, kcap)
    if rounds == 0:
        return state
    # lax.scan over round keys: the round program is traced/compiled once
    # regardless of the trip count

    def body(st, sub):
        return tuple(gdi_round_step(x, *st, sub, k=kcap, bn=bn,
                                    split_iters=split_iters, impl=impl,
                                    interpret=interpret,
                                    frontier=frontier)), None

    state, _ = jax.lax.scan(body, state, jax.random.split(key, rounds))
    return state


def gdi_parallel_init(x: jax.Array, k: int, key: jax.Array, *,
                      split_iters: int = 2,
                      counter: OpCounter | None = None,
                      bn: int | None = None, impl: str | None = None,
                      interpret: bool | None = None,
                      info: dict | None = None):
    """Round-parallel divisive variant (paper footnote 2): every round
    splits *all* current leaves at once — O(log2 k) rounds. (The
    distributed path seeds per shard through ``gdi_fixed_rounds`` with
    the greedy frontier instead; see core.distributed.) Runs on the same
    device round step as ``gdi_device_init`` with the frontier cap off,
    over a power-of-two slot capacity; if k is not a power of two the k
    highest-energy leaves are kept and the rest reassigned to the nearest
    kept center. ``info``, when given, is filled as by
    :func:`gdi_device_init`.
    """
    counter = counter or OpCounter()
    n, d = x.shape
    assert 1 <= k <= n
    impl, interpret = _auto_impl(impl, interpret)
    k2 = 1 << math.ceil(math.log2(k)) if k > 1 else 1
    bn = bn or (choose_group_bn(n, k2, d) if impl == "pallas" else 8)
    r = grouped_capacity(n, k2, bn) * bn

    counter.add_additions(n)
    state, nleaf, rounds = _frontier_rounds(
        x, _device_state(x, k2), key, counter, k=k2, bn=bn, r=r,
        split_iters=split_iters, impl=impl, interpret=interpret,
        frontier=1.0,
        max_rounds=math.ceil(math.log2(k2)) if k2 > 1 else 0)
    if info is not None:
        info.update(rounds=rounds, leaves=nleaf)
    a, centers, energies = state[0], state[1], state[2]
    if k2 == k:
        if nleaf < k:   # degenerate data stalled the rounds short of k
            centers = jnp.where((jnp.arange(k) < nleaf)[:, None], centers,
                                centers[max(nleaf - 1, 0)])
        return centers, a
    # Keep the k highest-energy leaves; dropped leaves -> nearest kept.
    from .distance import chunked_argmin_sqdist
    exists = jnp.arange(k2) < nleaf
    _, keep = jax.lax.top_k(jnp.where(exists, energies, -_INF), k)
    kept_centers = centers[keep]
    kept_centers = jnp.where(exists[keep][:, None], kept_centers,
                             kept_centers[0])
    remap = jnp.full((k2,), -1, jnp.int32).at[keep].set(
        jnp.arange(k, dtype=jnp.int32))
    near, _ = chunked_argmin_sqdist(x, kept_centers)
    counter.add_distances(n * k)
    a_new = jnp.where(remap[a] >= 0, remap[a], near.astype(jnp.int32))
    return kept_centers, a_new
