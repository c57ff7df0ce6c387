"""Device-resident frontier-batched GDI (DESIGN.md §4).

Covers the segmented-scan kernel against its segment_* oracle, the
round-step state invariants, the rounds sized to their flagged leaves
against the full layout, the pinned device-vs-host-loop parity, and the
wiring into fit(backend="pallas") / the distributed fit.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (clustering_energy, fit, gdi_device_init, gdi_init,
                        gdi_parallel_init)
from repro.core.gdi import (_device_state, _frontier_flags, _padded,
                            gdi_round_step, pick_rung, rung_ladder,
                            segmented_split_sweep)
from repro.data import gmm_blobs
from repro.kernels.ops import (group_by_cluster_device, grouped_capacity,
                               segmented_scan)
from repro.kernels.ref import segmented_scan_ref

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def blobs():
    return gmm_blobs(KEY, 2048, 16, true_k=24)


@pytest.mark.parametrize("n,d,k,bn", [
    (100, 5, 7, 8),
    (256, 32, 4, 16),      # multi-block segments
    (64, 3, 64, 8),        # k == n: many empty/singleton leaves
    (512, 128, 16, 32),
])
def test_segmented_scan_matches_ref(n, d, k, bn):
    ks = jax.random.split(jax.random.PRNGKey(n + d), 2)
    x = jax.random.normal(ks[0], (n, d))
    a = jax.random.randint(ks[1], (n,), 0, k, jnp.int32)
    perm, b2s = group_by_cluster_device(a, k, bn)
    xg = x[jnp.maximum(perm, 0)]
    w = (perm >= 0).astype(jnp.float32)
    cs, qs, cc = segmented_scan(xg, w, b2s, bn=bn, interpret=True)
    csr, qsr, ccr = segmented_scan_ref(xg, w, b2s, bn, k)
    np.testing.assert_allclose(np.asarray(cs), np.asarray(csr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(qs), np.asarray(qsr),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(cc), np.asarray(ccr))


def test_segmented_scan_brute_per_segment():
    """The kernel's running sums restart exactly at segment boundaries."""
    rng = np.random.RandomState(3)
    n, d, k, bn = 200, 4, 6, 8
    x = jnp.asarray(rng.randn(n, d).astype(np.float32))
    a = jnp.asarray(rng.randint(0, k, n).astype(np.int32))
    perm, b2s = group_by_cluster_device(a, k, bn)
    xg = x[jnp.maximum(perm, 0)]
    w = (perm >= 0).astype(jnp.float32)
    cs, _, cc = segmented_scan(xg, w, b2s, bn=bn, interpret=True)
    row_seg = np.repeat(np.asarray(b2s), bn)
    xgn, wn = np.asarray(xg), np.asarray(w)
    for seg in np.unique(row_seg):
        rows = np.where(row_seg == seg)[0]
        np.testing.assert_allclose(
            np.asarray(cs)[rows],
            np.cumsum(xgn[rows] * wn[rows, None], axis=0), atol=1e-4)
        np.testing.assert_allclose(np.asarray(cc)[rows],
                                   np.cumsum(wn[rows]))


def test_sweep_pallas_impl_agrees_with_xla(blobs):
    """The Pallas scan and the XLA segment formulation drive the sweep to
    the same splits."""
    k = 8
    a = jax.random.randint(jax.random.PRNGKey(5), (blobs.shape[0],), 0, k,
                           jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    c_a = jax.random.normal(ks[0], (k, blobs.shape[1]))
    c_b = jax.random.normal(ks[1], (k, blobs.shape[1]))
    out = segmented_split_sweep(blobs, a, c_a, c_b, k=k, bn=16,
                                impl="pallas", interpret=True)
    ref_out = segmented_split_sweep(blobs, a, c_a, c_b, k=k, bn=16,
                                    impl="xla", interpret=True)
    for got, want in zip(out, ref_out):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-2)


def test_round_step_invariants(blobs):
    """One round from scratch: the state arrays stay mutually consistent
    (assignment partition, sizes, leaf means, stored energies)."""
    x = blobs
    n, d = x.shape
    k = 16
    state = _device_state(x, k)
    for r in range(3):
        state, _ = gdi_round_step(x, *state, jax.random.PRNGKey(r), k=k,
                                  bn=8, split_iters=2, impl="xla",
                                  interpret=True)
    a, centers, energies, sizes, nleaf = map(np.asarray, state)
    nleaf = int(nleaf)
    assert 1 < nleaf <= k
    assert a.min() >= 0 and a.max() < nleaf
    counts = np.bincount(a, minlength=k)
    np.testing.assert_array_equal(counts, sizes)
    assert (counts[:nleaf] > 0).all() and (counts[nleaf:] == 0).all()
    xs = np.asarray(x)
    for j in range(nleaf):
        mu = xs[a == j].mean(0)
        np.testing.assert_allclose(centers[j], mu, atol=2e-3)
        np.testing.assert_allclose(energies[j],
                                   ((xs[a == j] - mu) ** 2).sum(),
                                   rtol=1e-3, atol=0.5)


@functools.partial(jax.jit, static_argnames=("k", "bn"))
def _full_layout_round(x, a, centers, energies, sizes, nleaf, key, *,
                       k, bn):
    """Reference round over the full grouped layout: every leaf's rows
    are laid out and swept (by the segmented-scan kernel, interpreted),
    totals and random members come from all n points, and the unflagged
    leaves are masked out afterwards."""
    n, d = x.shape
    flag = _frontier_flags(energies, sizes, nleaf, k=k, frontier=0.125)
    x_sq = jnp.sum(x * x, -1)
    tot_s = jax.ops.segment_sum(x, a, num_segments=k)
    tot_q = jax.ops.segment_sum(x_sq, a, num_segments=k)
    tot_c = jax.ops.segment_sum(jnp.ones((n,), x.dtype), a, num_segments=k)
    ids = jnp.arange(n, dtype=jnp.int32)

    def member(g):       # per-leaf argmax of g, the earliest row on ties
        m = jax.ops.segment_max(g, a, num_segments=k)
        idx = jnp.where(g >= m[a], ids, n)
        return jnp.minimum(jax.ops.segment_min(idx, a, num_segments=k), n)

    k1, k2 = jax.random.split(key)
    i_a = member(jax.random.uniform(k1, (n,)))
    i_b = member(jax.random.uniform(k2, (n,)).at[i_a].set(-1.0,
                                                           mode="drop"))
    c_a, c_b = x[jnp.minimum(i_a, n - 1)], x[jnp.minimum(i_b, n - 1)]
    perm, b2s = group_by_cluster_device(a, k, bn)
    r = perm.shape[0]
    rows = jnp.arange(r, dtype=jnp.int32)
    row_seg = jnp.repeat(b2s, bn)
    for _ in range(2):
        proj_pt = jnp.sum(x * (c_a - c_b)[a], -1)
        proj = jnp.where(perm >= 0, proj_pt[jnp.maximum(perm, 0)], jnp.inf)
        _, _, order = jax.lax.sort((row_seg, proj, rows), num_keys=2,
                                   is_stable=True)
        perm2 = perm[order]
        ws = (perm2 >= 0).astype(x.dtype)
        xgs = x[jnp.maximum(perm2, 0)]
        csum, qsum, cnt = segmented_scan(xgs, ws, b2s, bn=bn,
                                         interpret=True)
        rem = tot_c[row_seg] - cnt
        phi_p = qsum - jnp.sum(csum * csum, -1) / jnp.maximum(cnt, 1.0)
        sfx = tot_s[row_seg] - csum
        phi_s = tot_q[row_seg] - qsum \
            - jnp.sum(sfx * sfx, -1) / jnp.maximum(rem, 1.0)
        ok = (ws > 0) & (cnt >= 1) & (rem >= 1) & flag[row_seg]
        score = jnp.where(ok, phi_p + phi_s, jnp.inf)
        smin = jax.ops.segment_min(score, row_seg, num_segments=k)
        rmin = jnp.minimum(jax.ops.segment_min(
            jnp.where(ok & (score <= smin[row_seg]), rows, r), row_seg,
            num_segments=k), r)
        success = flag & (rmin < r)
        at = jnp.minimum(rmin, r - 1)
        cnt_a = cnt[at]
        c_a = jnp.where(success[:, None],
                        csum[at] / jnp.maximum(cnt_a, 1.0)[:, None], c_a)
        c_b = jnp.where(success[:, None], (tot_s - csum[at])
                        / jnp.maximum(tot_c - cnt_a, 1.0)[:, None], c_b)
    child = nleaf + jnp.cumsum(success.astype(jnp.int32)) - 1
    slot = jnp.where(success, child, k)
    in_b = (rows > rmin[row_seg]) & success[row_seg]
    a = a.at[jnp.where(perm2 >= 0, perm2, n)].set(
        jnp.where(in_b, child[row_seg], row_seg), mode="drop")
    size_a = cnt_a.astype(jnp.int32)
    centers = jnp.where(success[:, None], c_a, centers)
    centers = centers.at[slot].set(c_b, mode="drop")
    energies = jnp.where(success, jnp.maximum(phi_p[at], 0.0), energies)
    energies = energies.at[slot].set(jnp.maximum(phi_s[at], 0.0),
                                     mode="drop")
    sizes = jnp.where(success, size_a, sizes).at[slot].set(
        sizes - size_a, mode="drop")
    return a, centers, energies, sizes, nleaf + jnp.sum(success)


IMPLS = [("xla", 8), ("pallas", 16)]


@pytest.mark.parametrize("impl,bn", IMPLS)
def test_a_round_at_its_rung_is_the_round_at_the_full_rung(impl, bn):
    """Every round of a small init, run at the rung its flagged rows
    chose and again over the full layout: the same state, bit for bit,
    and the returned row count always fits the rung it picks."""
    n, d, k = 1024, 8, 24
    x = gmm_blobs(jax.random.PRNGKey(7), n, d, true_k=k)
    ladder = rung_ladder(n, k, bn)
    run = functools.partial(gdi_round_step, x, k=k, bn=bn, impl=impl,
                            interpret=True)
    for seed in range(3):
        state, need, used = _device_state(x, k), -(-n // bn) * bn, set()
        keys = jax.random.split(jax.random.PRNGKey(seed), 64)
        for key in keys:
            rows = pick_rung(ladder, need)
            used.add(rows)
            got, counts = run(*state, key, rows=rows)
            full, full_counts = run(*state, key)
            for g, f in zip(got + (counts,), full + (full_counts,)):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(f))
            state = got
            nleaf, need = (int(v) for v in np.asarray(counts))
            sizes = np.asarray(state[3])
            assert need <= rows or need <= ladder[-1]
            assert need == sum(-(-s // bn) * bn for s, f in zip(
                sizes, np.asarray(_frontier_flags(
                    state[2], state[3], state[4], k=k, frontier=0.125)))
                if f)
            if nleaf == k:
                break
        assert nleaf == k
        assert len(used) > 1


@pytest.mark.parametrize("impl,bn", IMPLS)
def test_device_init_matches_the_full_layout_reference(impl, bn):
    """A whole init over layouts sized to the flagged leaves lands on the
    assignment of the reference that sweeps every leaf's rows."""
    n, d, k = 1024, 8, 24
    x = gmm_blobs(jax.random.PRNGKey(8), n, d, true_k=k)
    for seed in (1, 2, 3):
        key = jax.random.PRNGKey(seed)
        centers, a = gdi_device_init(x, k, key, bn=bn, impl=impl,
                                     interpret=True)
        state = _device_state(x, k)
        while int(state[4]) < k:
            key, sub = jax.random.split(key)
            state = _full_layout_round(x, *state, sub, k=k, bn=bn)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(state[0]))
        np.testing.assert_allclose(np.asarray(centers),
                                   np.asarray(state[1]),
                                   rtol=1e-5, atol=1e-5)


def test_rung_ladder_holds_every_round():
    """On random leaf sizes and flagged sets, the flagged leaves' padded
    rows (need) never exceed the rung they pick: the top rung is the full
    layout, which holds any set of leaves, so a round whose one leaf
    holds all n rows always finds a rung. Every rung is whole blocks."""
    rng = np.random.RandomState(0)
    for _ in range(500):
        k = int(rng.randint(1, 5000))
        bn = int(2 ** rng.randint(3, 8))
        nleaf = int(rng.randint(1, k + 1))
        sizes = np.zeros(k, np.int64)
        sizes[:nleaf] = rng.geometric(rng.uniform(1e-4, 0.9), nleaf)
        n = int(sizes.sum())
        ladder = rung_ladder(n, k, bn)
        assert ladder[-1] == grouped_capacity(n, k, bn) * bn
        assert len(ladder) <= 7 and all(r % bn == 0 for r in ladder)
        assert list(ladder) == sorted(set(ladder))
        assert pick_rung(ladder, _padded(n, bn)) >= n
        for share in (rng.uniform(), 1.0):
            flag = rng.uniform(size=k) < share
            need = int(np.sum(np.where(flag, _padded(sizes, bn), 0)))
            assert need <= ladder[-1]
            rung = pick_rung(ladder, need)
            assert need <= rung
            assert rung == ladder[0] or need > ladder[ladder.index(rung) - 1]


@pytest.mark.slow
def test_device_gdi_parity_with_host(blobs):
    """The pinned device-vs-host-loop parity: same data, same keys, the
    frontier-batched rounds must land on the greedy host loop's clustering
    quality (fixed keys make this deterministic) with the same structural
    guarantees."""
    x = blobs
    k = 32
    ratios = []
    for seed in (1, 2):
        key = jax.random.PRNGKey(seed)
        c_h, a_h = gdi_init(x, k, key)
        c_d, a_d = gdi_device_init(x, k, key)
        a_dn = np.asarray(a_d)
        # same partition structure: exactly k non-empty leaves
        assert a_dn.min() >= 0 and a_dn.max() == k - 1
        assert (np.bincount(a_dn, minlength=k) > 0).all()
        # centers are the leaf means, like the host loop's
        xs = np.asarray(x)
        for j in range(k):
            np.testing.assert_allclose(np.asarray(c_d)[j],
                                       xs[a_dn == j].mean(0), atol=2e-3)
        e_h = float(clustering_energy(x, c_h, a_h))
        e_d = float(clustering_energy(x, c_d, a_d))
        ratios.append(e_d / e_h)
    # batched frontier vs sequential greedy: same energy up to schedule
    # noise, pinned from both sides (BENCH_init.json tracks the <=1%
    # criterion at benchmark scale)
    assert 0.85 < np.mean(ratios) < 1.10, ratios


def test_gdi_parallel_round_step_port(blobs):
    """gdi_parallel_init on the shared round step: valid output for
    power-of-two and non-power-of-two k."""
    for k in (16, 12):
        c, a = gdi_parallel_init(blobs, k, jax.random.PRNGKey(1))
        an = np.asarray(a)
        assert c.shape == (k, blobs.shape[1])
        assert an.min() >= 0 and an.max() < k
        assert np.isfinite(np.asarray(c)).all()
        assert np.isfinite(float(clustering_energy(blobs, c, a)))


@pytest.mark.slow
def test_fit_pallas_chains_device_gdi(blobs):
    """fit(init="gdi", backend="pallas") runs init through convergence on
    the device path and matches the host-init xla run's quality."""
    r_dev = fit(blobs, 24, method="k2means", init="gdi", backend="pallas",
                kn=6, max_iters=12, key=KEY)
    r_ref = fit(blobs, 24, method="k2means", init="gdi", kn=6,
                max_iters=12, key=KEY)
    assert np.isfinite(r_dev.energy)
    assert r_dev.energy < 1.15 * r_ref.energy


def test_distributed_gdi_seeding(blobs):
    """init="gdi" on the distributed driver: the divisive assignment seeds
    the sharded iterations directly (single-device debug mesh)."""
    from repro.core.distributed import fit_distributed_k2means
    mesh = jax.make_mesh((1,), ("data",))
    r = fit_distributed_k2means(blobs, 16, 6, mesh, jax.random.PRNGKey(0),
                                max_iters=8, init="gdi")
    hist = [e for _, e in r.history]
    assert r.centers.shape == (16, blobs.shape[1])
    assert np.asarray(r.assignment).shape == (blobs.shape[0],)
    assert all(b <= a_ + 1e-2 for a_, b in zip(hist, hist[1:]))
