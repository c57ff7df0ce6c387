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
    One jitted *round step* splits every frontier leaf at once over a
    leaf-grouped layout of the flagged leaves' rows only: the direction
    projection + Lemma-1 sweep run as a *segmented* sort/cumsum
    (kernels/segmented_scan.py on TPU, the jax.ops.segment_* reference
    off-TPU), split positions fall out of per-segment masked argmins, and
    greedy leaf selection is a device-side energy argsort. A round costs
    O(n + C (d + log C)) for its C layout rows, C being the smallest rung
    of a fixed ladder of static sizes that holds the flagged leaves; the
    host reads back two scalars per round (the leaf count and the next
    round's row count, which picks its rung), so a k-way init takes
    ~log_{1+frontier} k round dispatches instead of k-1 split dispatches.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import (choose_group_bn, grouped_capacity,
                           resolve_interpret, segmented_scan)
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


def _segment_argmax(g: jax.Array, seg: jax.Array, ids: jax.Array, k: int,
                    none: int) -> jax.Array:
    """Per-segment argmax of ``g`` over segments ``seg`` (ids >= k are
    dropped): (k,) the ``ids`` of the winning rows, ``none`` for empty
    segments (the smallest id wins ties)."""
    m = jax.ops.segment_max(g, seg, num_segments=k)
    idx = jnp.where(g >= m[seg], ids, none)
    return jnp.minimum(jax.ops.segment_min(idx, seg, num_segments=k), none)


def _block_segmented_cumsum(v: jax.Array, b2s: jax.Array,
                            bn: int) -> jax.Array:
    """Inclusive cumsum of ``v`` (R, ...) along axis 0 within segments
    whose boundaries fall between bn-row blocks (``b2s`` the block ->
    segment map): a scan inside each block, then a segmented scan of the
    block totals. Each value sums only its own segment's rows, so no
    segment's rounding depends on the segments before it."""
    nb = b2s.shape[0]
    within = jnp.cumsum(v.reshape((nb, bn) + v.shape[1:]), axis=1)
    tot = within[:, -1]
    head = jnp.concatenate([jnp.ones((1,), bool), b2s[1:] != b2s[:-1]])
    head = head.reshape((nb,) + (1,) * (v.ndim - 1))

    def add(lhs, rhs):
        return lhs[0] | rhs[0], jnp.where(rhs[0], rhs[1], lhs[1] + rhs[1])

    _, inc = jax.lax.associative_scan(add, (head, tot))
    off = jnp.where(head, 0.0, jnp.concatenate([jnp.zeros_like(inc[:1]),
                                                 inc[:-1]]))
    return (within + off[:, None]).reshape(v.shape)


def _blocked_cumsum(v: jax.Array, bs: int = 1024) -> jax.Array:
    """Inclusive cumsum of a 1-D ``v`` as scans of ``bs``-element blocks
    plus block offsets: over millions of elements the TPU compiles and
    runs this far faster than a flat cumsum."""
    r = v.shape[0]
    vb = jnp.pad(v, (0, (-r) % bs)).reshape(-1, bs)
    within = jnp.cumsum(vb, axis=1)
    tot = within[:, -1]
    return (within + (jnp.cumsum(tot) - tot)[:, None]).reshape(-1)[:r]


def _frontier_flags(energies, sizes, nleaf, *, k: int, frontier: float):
    """The leaves a round splits: the top t eligible leaves by energy,
    t = min(#splittable, k - nleaf, max(1, floor(frontier * min(nleaf,
    k - nleaf)))) (no frontier cap at ``frontier=1.0``)."""
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
    return eligible & (rank < t)


def _padded(sizes, bn: int):
    """Rows a leaf of ``sizes`` members takes in the grouped layout."""
    return (sizes + bn - 1) // bn * bn


# halvings of the full layout in the rung ladder: its smallest rung is
# 1/64 of the full layout, about where the greedy init's last rounds sit
_RUNG_HALVINGS = 6


def rung_ladder(n: int, k: int, bn: int) -> tuple:
    """The static row capacities a frontier round's grouped layout can
    take, ascending: the full layout's ``grouped_capacity(n, k, bn)``
    blocks halved up to ``_RUNG_HALVINGS`` times, in whole blocks. A
    round runs at the smallest rung that holds its flagged leaves' padded
    rows (:func:`pick_rung`); the top rung holds any set of leaves."""
    blocks = grouped_capacity(n, k, bn)
    return tuple(sorted({-(-blocks // (1 << j)) * bn
                         for j in range(_RUNG_HALVINGS + 1)}))


def pick_rung(ladder: tuple, need: int) -> int:
    """The smallest rung of ``ladder`` that holds ``need`` rows."""
    return next(r for r in ladder if r >= need)


class _Layout(NamedTuple):
    """A round's grouped layout of its flagged leaves (see
    :func:`_flagged_layout`); C = its static row count."""
    xc: jax.Array      # (C, d) the compact rows' points
    xc_sq: jax.Array   # (C,) their squared norms
    src: jax.Array     # (C,) compact row -> point id (n past the last)
    seg: jax.Array     # (C,) compact row -> leaf (k past the last)
    b2s: jax.Array     # (C/bn,) padded block -> leaf, non-decreasing
    slot: jax.Array    # (C,) padded row -> compact row in leaf order (C: pad)
    last: jax.Array    # (k,) padded row of each flagged leaf's last member


def _flagged_layout(x, a, sizes, flag, *, k: int, bn: int,
                    rows: int) -> _Layout:
    """The grouped layout of the flagged leaves' rows only, in a
    ``rows``-row arena (``rows`` >= the flagged leaves' padded rows).

    The flagged rows are first compacted, in original order, to the front
    of ``rows`` slots and gathered (one (rows, d) gather). Each flagged
    leaf then takes ``_padded(size)`` rows of the padded layout, leaf
    after leaf: ``b2s`` gives each block's leaf (clamped to k-1 past the
    packed extent) and ``slot`` each padded row's position in the compact
    rows once they are sorted by leaf."""
    n = a.shape[0]
    fsz = jnp.where(flag, sizes, 0)
    psz = _padded(fsz, bn)
    bounds = jnp.cumsum(psz)                         # inclusive padded ends
    starts = bounds - psz
    dstart = jnp.cumsum(fsz) - fsz                   # unpadded starts
    f = flag[a]
    pos = jnp.where(f, _blocked_cumsum(f.astype(jnp.int32)) - 1, rows)
    src = jnp.full((rows,), n, jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    seg = jnp.where(src < n, a[jnp.minimum(src, n - 1)], k)
    b2s = jnp.searchsorted(bounds, jnp.arange(rows // bn) * bn,
                           side="right")
    b2s = jnp.minimum(b2s, k - 1).astype(jnp.int32)
    row_seg = jnp.repeat(b2s, bn)
    off = jnp.arange(rows, dtype=jnp.int32) - starts[row_seg]
    slot = jnp.where(off < fsz[row_seg], dstart[row_seg] + off, rows)
    xc = x[jnp.minimum(src, n - 1)]
    return _Layout(xc, jnp.sum(xc * xc, axis=-1), src, seg, b2s,
                   slot.astype(jnp.int32), jnp.maximum(starts + fsz - 1, 0))


def _segmented_sweep(lay: _Layout, dirs, split_flag, *, k: int, bn: int,
                     impl: str, interpret: bool):
    """One Lemma-1 sweep over every flagged leaf at once.

    Projects each compact row onto its leaf's direction, sorts the rows
    by (leaf, projection) (one stable two-key sort, ties to the earlier
    point), lays them out in the padded grouped layout, runs the
    segmented scan — whose value at a leaf's last row is the leaf's total
    — and picks the min-energy split per segment with a masked argmin,
    reduced per block before the per-leaf reduction. All O(C (d + log
    C)) for the layout's C rows. Returns (perm2, rmin, found, cnt_a, c_a,
    c_b, phi_a, phi_b); rmin is the split row in the padded layout (C
    when no valid split), side A = rows <= rmin of the leaf's segment,
    perm2 the layout's row -> original point map (-1 for padding).
    """
    r = lay.slot.shape[0]
    nb = r // bn
    rows = jnp.arange(r, dtype=jnp.int32)
    proj = jnp.where(lay.seg < k,
                     jnp.sum(lay.xc * dirs[lay.seg], axis=-1), _INF)
    _, _, order = jax.lax.sort((lay.seg, proj, rows), num_keys=2,
                               is_stable=True)
    real = lay.slot < r
    pick = order[jnp.minimum(lay.slot, r - 1)]       # padded row -> compact
    perm2 = jnp.where(real, lay.src[pick], -1)
    ws = real.astype(lay.xc.dtype)
    xgs = lay.xc[pick]                               # (C, d) gather
    row_seg = jnp.repeat(lay.b2s, bn)
    if impl == "pallas":
        csum, qsum, cnt = segmented_scan(xgs, ws, lay.b2s, bn=bn,
                                         interpret=interpret)
    else:
        # the kernel's contract in plain XLA (kernels.ref oracle shape)
        csum = _block_segmented_cumsum(xgs * ws[:, None], lay.b2s, bn)
        qsum = _block_segmented_cumsum(
            jnp.where(real, lay.xc_sq[pick], 0.0), lay.b2s, bn)
        cnt = _block_segmented_cumsum(ws, lay.b2s, bn)
    tot_s, tot_q, tot_c = csum[lay.last], qsum[lay.last], cnt[lay.last]
    rem = tot_c[row_seg] - cnt
    phi_p = qsum - jnp.sum(csum * csum, axis=-1) / jnp.maximum(cnt, 1.0)
    sfx = tot_s[row_seg] - csum
    phi_s = (tot_q[row_seg] - qsum) \
        - jnp.sum(sfx * sfx, axis=-1) / jnp.maximum(rem, 1.0)
    ok = (ws > 0) & (cnt >= 1.0) & (rem >= 1.0) & split_flag[row_seg]
    score = jnp.where(ok, phi_p + phi_s, _INF)

    def leaf_min(v):     # per-leaf min: per block first, then per leaf
        return jax.ops.segment_min(v.reshape(nb, bn).min(axis=1), lay.b2s,
                                   num_segments=k, indices_are_sorted=True)

    smin = leaf_min(score)
    hit = ok & (score <= smin[row_seg])
    rmin = jnp.minimum(leaf_min(jnp.where(hit, rows, r)), r)
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
    sizes = jnp.bincount(a, length=k).astype(jnp.int32)
    flag = sizes >= 2
    lay = _flagged_layout(x, a, sizes, flag, k=k, bn=bn,
                          rows=grouped_capacity(n, k, bn) * bn)
    out = _segmented_sweep(lay, c_a - c_b, flag, k=k, bn=bn, impl=impl,
                           interpret=interpret)
    return out[2:]


@functools.partial(jax.jit,
                   static_argnames=("k", "bn", "split_iters", "impl",
                                    "interpret", "frontier", "rows"))
def gdi_round_step(x, a, centers, energies, sizes, nleaf, key, *, k: int,
                   bn: int, split_iters: int = 2, impl: str = "xla",
                   interpret: bool | None = None,
                   frontier: float = 0.125, rows: int | None = None):
    """One frontier round: split the top-t leaves by energy all at once.

    State: a (n,) leaf assignment, centers (k, d), energies (k,),
    sizes (k,) int32, nleaf () int32 — all device-resident; nothing here
    forces a host sync. t = min(#splittable, k - nleaf,
    max(1, floor(frontier * min(nleaf, k - nleaf)))): leaves are re-ranked
    by energy every round and only the top ``frontier`` fraction splits,
    so low-energy leaves are left alone exactly as the sequential greedy
    would (``frontier=1.0`` is blind doubling, the round-parallel
    variant).
    Only the flagged leaves' rows enter the grouped layout, whose static
    capacity is ``rows`` (a rung of :func:`rung_ladder`, at least the
    flagged leaves' padded rows — the ``need`` the previous round
    returned; None is the full layout, which holds any round).
    Side A of leaf j keeps id j; side B gets the next free slot. Returns
    (state, counts): the updated state tuple and (2,) int32 [nleaf,
    need], need being the padded rows of the leaves the next round
    flags. interpret=None auto-selects interpret mode off-TPU.
    """
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    rows = rows or grouped_capacity(n, k, bn) * bn
    split_flag = _frontier_flags(energies, sizes, nleaf, k=k,
                                 frontier=frontier)
    lay = _flagged_layout(x, a, sizes, split_flag, k=k, bn=bn, rows=rows)

    # Two uniform random members per leaf as the initial split direction
    # (Algorithm 3 line 2), all flagged leaves at once via per-segment
    # argmax of uniform draws over the points; the second draw excludes
    # the first member.
    k1, k2 = jax.random.split(key)
    srcc = jnp.minimum(lay.src, n - 1)
    g1 = jax.random.uniform(k1, (n,))[srcc]
    g2 = jax.random.uniform(k2, (n,))[srcc]
    i_a = _segment_argmax(g1, lay.seg, lay.src, k, n)
    g2 = jnp.where(lay.src == i_a[jnp.minimum(lay.seg, k - 1)], -1.0, g2)
    i_b = _segment_argmax(g2, lay.seg, lay.src, k, n)
    c_a = x[jnp.minimum(i_a, n - 1)]
    c_b = x[jnp.minimum(i_b, n - 1)]

    for _ in range(split_iters):
        perm2, rmin, found, cnt_a, c_a_new, c_b_new, phi_a, phi_b = \
            _segmented_sweep(lay, c_a - c_b, split_flag, k=k, bn=bn,
                             impl=impl, interpret=interpret)
        upd = (split_flag & found)[:, None]
        c_a = jnp.where(upd, c_a_new, c_a)
        c_b = jnp.where(upd, c_b_new, c_b)

    success = split_flag & found
    # children take the next free slots in slot order (dense, so nleaf
    # stays the exact count of live leaves even if a flagged leaf found
    # no valid split)
    child = nleaf + jnp.cumsum(success.astype(jnp.int32)) - 1
    child_idx = jnp.where(success, child, k)

    row_seg = jnp.repeat(lay.b2s, bn)
    in_b = (jnp.arange(rows, dtype=jnp.int32) > rmin[row_seg]) \
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
    nxt = _frontier_flags(energies, sizes_new, nleaf, k=k,
                          frontier=frontier)
    need = jnp.sum(jnp.where(nxt, _padded(sizes_new, bn), 0))
    counts = jnp.stack([nleaf, need]).astype(jnp.int32)
    return (a_new, centers, energies, sizes_new, nleaf), counts


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
    """Paper-unit accounting of what one device round actually executes
    over its r-row layout: the flag compaction over the n points and
    split_iters x (projection inner products + sweep sort + scan
    additions)."""
    counter.add_inner(split_iters * r)
    counter.add_additions(split_iters * r + n)
    for _ in range(split_iters):
        counter.add_sort(r, d)


def _frontier_rounds(x, state, key, counter: OpCounter, *, k: int,
                     bn: int, split_iters: int, impl: str,
                     interpret: bool, frontier: float,
                     max_rounds: int | None = None):
    """Frontier rounds from ``state`` (one leaf of all n rows) until
    ``k`` leaves, ``max_rounds`` rounds, or a round that splits nothing.
    Each round runs at the smallest rung of :func:`rung_ladder` that
    holds its flagged rows, and is the span ``kmeans.init.round``: its
    dispatch and its one host read, the leaf count and the next round's
    padded rows. Returns (state, leaves, rounds run, rows swept)."""
    n, d = x.shape
    ladder = rung_ladder(n, k, bn)
    need = -(-n // bn) * bn                     # the one leaf of round 0
    nleaf, rounds, swept = 1, 0, 0
    while nleaf < k and (max_rounds is None or rounds < max_rounds):
        rows = pick_rung(ladder, need)
        with jax.profiler.TraceAnnotation("kmeans.init.round") as span:
            key, sub = jax.random.split(key)
            state, counts = gdi_round_step(
                x, *state, sub, k=k, bn=bn, split_iters=split_iters,
                impl=impl, interpret=interpret, frontier=frontier,
                rows=rows)
            _charge_round(counter, rows, n, d, split_iters)
            new_nleaf, need = (int(v) for v in np.asarray(counts))
            counter.host_reads += 1             # the round's one host read
            if span.is_enabled():
                span.set_metadata(round=rounds, leaves=new_nleaf, rows=rows)
        rounds += 1
        swept += rows
        if new_nleaf == nleaf:
            break                               # nothing splittable left
        nleaf = new_nleaf
    return state, nleaf, rounds, swept


def _round_info(info: dict | None, n: int, k: int, bn: int, nleaf: int,
                rounds: int, swept: int) -> None:
    if info is not None:
        info.update(rounds=rounds, leaves=nleaf, rows_swept=swept,
                    rows_full=rounds * grouped_capacity(n, k, bn) * bn)


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
    dispatches with one small host read each instead of k-1 splits with
    two syncs each. impl: "pallas" routes the segmented scan through the
    Pallas kernel, "xla" through the segment_* reference (the off-TPU
    default — interpret-mode Pallas would serialize on the grid).
    Returns (centers (k, d), assignment (n,)); ``info``, when given, is
    filled with the ``rounds`` run, the ``leaves`` reached, and the
    layout rows the rounds swept (``rows_swept``) against what full
    layouts would have (``rows_full``).
    """
    counter = counter or OpCounter()
    n, d = x.shape
    assert 1 <= k <= n
    impl, interpret = _auto_impl(impl, interpret)
    # the Pallas scan wants MXU-sized blocks; the XLA path has no block
    # constraint, so it minimizes the grouped layout's padding (R -> ~n)
    bn = bn or (choose_group_bn(n, k, d) if impl == "pallas" else 8)

    counter.add_additions(n)                    # initial mean
    state, nleaf, rounds, swept = _frontier_rounds(
        x, _device_state(x, k), key, counter, k=k, bn=bn,
        split_iters=split_iters, impl=impl, interpret=interpret,
        frontier=frontier)
    _round_info(info, n, k, bn, nleaf, rounds, swept)
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
        return gdi_round_step(x, *st, sub, k=kcap, bn=bn,
                              split_iters=split_iters, impl=impl,
                              interpret=interpret, frontier=frontier)[0], None

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

    counter.add_additions(n)
    state, nleaf, rounds, swept = _frontier_rounds(
        x, _device_state(x, k2), key, counter, k=k2, bn=bn,
        split_iters=split_iters, impl=impl, interpret=interpret,
        frontier=1.0,
        max_rounds=math.ceil(math.log2(k2)) if k2 > 1 else 0)
    _round_info(info, n, k2, bn, nleaf, rounds, swept)
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
