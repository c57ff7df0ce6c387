"""Query-time subsystem (DESIGN.md §10): bounded predict correctness,
backend parity, streaming partial_fit through the resident arena, and
checkpoint round-trips of the served model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OpCounter, fit
from repro.core.distance import chunked_argmin_sqdist
from repro.core.model import KMeansModel
from repro.data import gmm_blobs

from test_resident_layout import check_layout

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def fitted():
    """A converged fit over blobs + held-out queries from the same GMM
    (same key => same component means)."""
    allx = gmm_blobs(KEY, 4096 + 2048, 16, true_k=48)
    x, q = allx[:4096], allx[4096:]
    res, model = fit(x, 48, kn=8, max_iters=25, key=KEY,
                     return_model=True)
    return x, q, res, model


def test_predict_exact_in_neighborhood_and_recall(fitted):
    """Where the route lands a neighborhood containing the true nearest
    center the bounded predict must equal the brute-force argmin exactly;
    overall recall@1 on blobs must be >= 0.99."""
    _, q, _, model = fitted
    a_pred = np.asarray(model.predict(q))
    a_true = np.asarray(chunked_argmin_sqdist(q, model.centers)[0])
    routed = np.asarray(model.route(q))
    nb = np.asarray(model.neighbors)
    in_nb = (nb[routed] == a_true[:, None]).any(axis=1)
    assert in_nb.any()
    assert (a_pred[in_nb] == a_true[in_nb]).all()
    assert (a_pred == a_true).mean() >= 0.99


def test_predict_counted_distances(fitted):
    """The predict charge is the measured bounded count: at least the
    group scan + anchors per query, at most the dense budget, identical
    across repeat calls, and batch-size independent."""
    _, q, _, model = fitted
    nq = q.shape[0]
    c = OpCounter()
    model.predict(q, counter=c)
    dense = nq * model.dense_distances_per_query()
    floor = nq * (model.route_groups + model.route_probes)
    assert floor <= c.total <= dense
    c2 = OpCounter()
    model.predict(q, batch_size=700, counter=c2)
    assert c2.total == c.total


def test_predict_backend_parity(fitted):
    """The Pallas tiled-kernel resolution and the XLA gather fallback
    produce identical assignments and distances."""
    _, q, _, model = fitted
    a_x, d_x = model.predict(q, return_sqdist=True)
    model.backend = "pallas"
    try:
        a_p, d_p = model.predict(q, return_sqdist=True)
    finally:
        model.backend = "xla"
    assert (np.asarray(a_x) == np.asarray(a_p)).all()
    # distances agree up to f32 reduction-order noise (DESIGN.md §3.1)
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_p),
                               rtol=1e-4, atol=1e-4)


def test_predict_batching_invariant(fitted):
    """Chopping the query stream into batches cannot change the result
    (the tail batch is padded, padding rows dropped)."""
    _, q, _, model = fitted
    a1 = np.asarray(model.predict(q))
    a2 = np.asarray(model.predict(q, batch_size=700))
    assert (a1 == a2).all()


def test_predict_low_precision_queries_upcast_once(fitted):
    """bf16/f16 query batches are accepted with ONE explicit upcast at
    the predict boundary: the result is exactly the f32 predict of the
    rounded values, and non-float dtypes are rejected with a typed
    error (no silent int->float casts)."""
    _, q, _, model = fitted
    for dt in (jnp.bfloat16, jnp.float16):
        q_low = jnp.asarray(q, dt)
        a_low = np.asarray(model.predict(q_low))
        a_ref = np.asarray(model.predict(q_low.astype(jnp.float32)))
        assert (a_low == a_ref).all()
    with pytest.raises(TypeError, match="floating"):
        model.predict(jnp.zeros((4, model.d), jnp.int32))


def test_fit_return_model_shapes(fitted):
    x, _, res, model = fitted
    k, d = res.centers.shape
    assert model.k == k and model.d == d
    assert model.neighbors.shape == (k, model.kn)
    assert model.capacity == 2 * x.shape[0]
    assert model.n_rows == x.shape[0]
    # per-cluster stats seeded from the fit assignment
    counts = np.bincount(np.asarray(res.assignment), minlength=k)
    np.testing.assert_array_equal(np.asarray(model.counts), counts)
    # the arena holds exactly the training rows, invariants intact
    check_layout(model.state.pid, model.state.b2c, model.state.fill,
                 model.state.openb, model.a_pts, model.bn)
    assert float(model.state.wg.sum()) == x.shape[0]


def test_partial_fit_keeps_layout_invariants():
    """Streaming through sparse repairs AND forced re-sorts keeps the
    §9.1 slot-ownership invariants green after every batch."""
    allx = gmm_blobs(jax.random.PRNGKey(1), 1200 + 1000, 12, true_k=16)
    x, stream = allx[:1200], allx[1200:]
    _, model = fit(x, 16, kn=6, max_iters=15, key=KEY, return_model=True,
                   model_capacity=2300)
    counter = OpCounter()
    for i in range(10):
        xb = stream[i * 100:(i + 1) * 100]
        ab = model.partial_fit(xb, counter=counter)
        assert ab.shape == (100,)
        check_layout(model.state.pid, model.state.b2c, model.state.fill,
                     model.state.openb, model.a_pts, model.bn,
                     context=f"batch {i}")
        # streamed rows live in the arena under their predicted cluster
        assert model.n_rows == 1200 + (i + 1) * 100
    assert float(model.state.wg.sum()) == model.n_rows
    # layout maintenance was charged to the memory-traffic lane
    assert counter.bytes_moved > 0
    # arena full -> the next batch must refuse, not corrupt
    with pytest.raises(ValueError):
        model.partial_fit(stream[:200])


def test_partial_fit_updates_are_running_means():
    """Without decay, partial_fit's incremental delta keeps
    centers == sums / counts == the exact running member mean."""
    x = gmm_blobs(jax.random.PRNGKey(2), 800, 8, true_k=8)
    _, model = fit(x[:600], 8, kn=4, max_iters=10, key=KEY,
                   return_model=True)
    a1 = model.partial_fit(x[600:700])
    a2 = model.partial_fit(x[700:])
    a_all = np.concatenate([np.asarray(model.assignment()[:600]),
                            np.asarray(a1), np.asarray(a2)])
    k = model.k
    counts = np.bincount(a_all, minlength=k)
    np.testing.assert_allclose(np.asarray(model.counts), counts, rtol=1e-6)
    c = np.asarray(model.centers)
    s = np.asarray(model.sums)
    nz = counts > 0
    np.testing.assert_allclose(c[nz], s[nz] / counts[nz, None], rtol=1e-5)


def test_partial_fit_tracks_drifting_distribution():
    """With forgetting, a streamed distribution shift pulls the centers
    onto the shifted modes: the center-to-current-mean error decays
    monotonically across stream checkpoints."""
    key = jax.random.PRNGKey(3)
    k, d = 6, 8
    mus = jax.random.normal(key, (k, d)) * 4.0

    def draw(key, m, shift):
        comp = jax.random.randint(key, (m,), 0, k)
        noise = 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                        (m, d))
        return (mus[comp] + shift) + noise, comp

    x0, _ = draw(jax.random.PRNGKey(10), 900, 0.0)
    _, model = fit(x0, k, init="kmeanspp", kn=4, max_iters=20, key=KEY,
                   return_model=True, model_capacity=6000)
    model.decay = 0.8
    model.refresh_every = 2
    shift = jnp.ones((d,)) * 3.0          # one abrupt distribution shift

    def err():
        c = np.asarray(model.centers)
        target = np.asarray(mus + shift)
        d2 = ((c[:, None] - target[None, :]) ** 2).sum(-1)
        return float(np.sqrt(d2.min(axis=0)).mean())

    errs = [err()]
    for i in range(12):
        xb, _ = draw(jax.random.PRNGKey(20 + i), 256, 3.0)
        model.partial_fit(xb)
        if (i + 1) % 4 == 0:
            errs.append(err())
    assert all(e2 < e1 for e1, e2 in zip(errs, errs[1:])), errs
    assert errs[-1] < 0.25 * errs[0], errs


def test_model_checkpoint_roundtrip(tmp_path, fitted):
    """save -> restore preserves every array, the static config, and the
    streaming position; the restored model predicts identically and can
    continue partial_fit."""
    _, q, _, model = fitted
    ckpt = str(tmp_path / "model_ckpt")
    model.save(ckpt, step=5)
    m2 = KMeansModel.restore(ckpt)
    assert m2.n_rows == model.n_rows
    assert m2.batches_seen == model.batches_seen
    assert m2.kn == model.kn and m2.bn == model.bn
    for f in model.state._fields:
        np.testing.assert_array_equal(np.asarray(getattr(model.state, f)),
                                      np.asarray(getattr(m2.state, f)), f)
    np.testing.assert_array_equal(np.asarray(model.router.members),
                                  np.asarray(m2.router.members))
    np.testing.assert_array_equal(np.asarray(model.nb_dist),
                                  np.asarray(m2.nb_dist))
    a1 = np.asarray(model.predict(q[:512]))
    a2 = np.asarray(m2.predict(q[:512]))
    assert (a1 == a2).all()
    xb = q[:64]
    ab1 = np.asarray(model.predict(xb))
    ab2 = np.asarray(m2.partial_fit(xb))
    assert (ab1 == ab2).all()
    check_layout(m2.state.pid, m2.state.b2c, m2.state.fill,
                 m2.state.openb, m2.a_pts, m2.bn)


def test_predict_only_model_without_arena():
    """from_result without x: predict works, partial_fit updates the
    stats but streams no rows."""
    x = gmm_blobs(jax.random.PRNGKey(4), 600, 8, true_k=8)
    res = fit(x, 8, kn=4, max_iters=10, key=KEY)
    model = KMeansModel.from_result(res, kn=4)
    assert not model.has_arena
    a = np.asarray(model.predict(x[:100]))
    a_true = np.asarray(chunked_argmin_sqdist(x[:100], model.centers)[0])
    assert (a == a_true).mean() >= 0.99
    before = float(model.counts.sum())
    model.partial_fit(x[:50])
    assert float(model.counts.sum()) == before + 50
    assert model.n_rows == 0


def test_router_lists_every_center_of_a_dense_region():
    """Centers piled into one region make one router group far larger
    than the mean: the default cap grows to hold it, so every center is
    in some member list and predicting at the centers themselves finds
    each of them."""
    from repro.core.lloyd import KMeansResult
    from repro.core.model import _default_cap, _default_groups
    k, d = 256, 16
    kd, ks = jax.random.split(jax.random.PRNGKey(5))
    dense = 0.05 * jax.random.normal(kd, (192, d))
    spread = 4.0 * jax.random.normal(ks, (k - 192, d))
    c = jnp.concatenate([dense, spread])
    res = KMeansResult(c, jnp.arange(k, dtype=jnp.int32), 0.0, 0, 0.0, [])
    model = KMeansModel.from_result(res, kn=8)
    g = _default_groups(k)
    assert model.route_cap > _default_cap(k, g, 8)
    listed = np.zeros(k, bool)
    listed[np.asarray(model.router.members).ravel()] = True
    assert listed.all()
    assert (np.asarray(model.predict(c)) == np.arange(k)).all()


def test_router_lists_every_center_after_drift_refresh():
    """partial_fit pulls three quarters of the centers into one dense
    region; the routers rebuilt at the refreshes widen their lists past
    the width the model was built with, so every center is still listed
    and predicting at each center finds it."""
    from repro.core.lloyd import KMeansResult
    k, d, rows = 256, 16, 8
    c = 4.0 * jax.random.normal(jax.random.PRNGKey(7), (k, d))
    res = KMeansResult(c, jnp.arange(k, dtype=jnp.int32), 0.0, 0, 0.0, [])
    model = KMeansModel.from_result(res, kn=8, refresh_every=1, decay=0.01)
    cap0 = model.route_cap
    pile = np.arange(192)
    for _ in range(6):
        # rows at half of each piled center's position pull it halfway in
        target = 0.5 * np.asarray(model.centers)[pile]
        model.partial_fit(jnp.asarray(np.repeat(target, rows, axis=0)))
    listed = np.zeros(k, bool)
    listed[np.asarray(model.router.members).ravel()] = True
    assert listed.all()
    assert model.route_cap > cap0
    assert np.median(np.linalg.norm(np.asarray(model.centers)[pile],
                                    axis=1)) < 1.0
    assert (np.asarray(model.predict(model.centers)) == np.arange(k)).all()


def test_kv_partial_fit_folds_ring():
    """The KV-domain partial_fit absorbs live ring rows into the
    cluster-major tables with running-mean centroid updates and resets
    the ring (serve-loop integration, launch/serve.py)."""
    from repro.models.kv_cluster import build_cluster_major, kv_partial_fit
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 4)
    B, H, S, dh, kc, cap, R = 2, 2, 32, 16, 4, 24, 8
    keys = jax.random.normal(ks[0], (B, H, S, dh))
    vals = jax.random.normal(ks[1], (B, H, S, dh))
    kt, vt, cent, sizes = build_cluster_major(keys, vals, kc, cap)
    counts = sizes.astype(jnp.float32)
    ring_k = jax.random.normal(ks[2], (B, H, R, dh))
    ring_v = jax.random.normal(ks[3], (B, H, R, dh))
    fill = jnp.int32(5)                       # 5 live rows of R
    kt2, vt2, cent2, sizes2, counts2, rk2, rv2, fill2 = kv_partial_fit(
        kt, vt, cent, sizes, counts, ring_k, ring_v, fill)
    assert int(sizes2.sum()) == int(sizes.sum()) + 5 * B * H
    assert float(counts2.sum()) == float(counts.sum()) + 5 * B * H
    assert int(fill2) == 0 and float(jnp.abs(rk2).sum()) == 0.0
    # each folded row landed in its nearest centroid's table
    moved = np.asarray(sizes2 - sizes)
    assert (moved >= 0).all() and moved.sum() == 5 * B * H
    # centroids moved (running mean absorbed the rows), tables differ
    assert not np.allclose(np.asarray(cent2), np.asarray(cent))
    assert not np.array_equal(np.asarray(kt2), np.asarray(kt))
