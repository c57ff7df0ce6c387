"""The yardstick's plain references against NumPy at a tiny size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as ref


def _np_nearest(x, c):
    d = ((x[:, None, :].astype(np.float64) - c[None, :, :]) ** 2).sum(-1)
    return d.argmin(1), d.min(1)


@pytest.fixture(scope="module")
def data():
    x = ref.gmm_rows(ref.seed_key(3), n=600, d=8, true_k=6)
    c = ref.forgy(x, 12, jax.random.PRNGKey(4))
    return np.asarray(x), np.asarray(c)


def test_gmm_rows_seeded_and_fresh_rows_share_the_mixture():
    key = ref.seed_key(9)
    a = ref.gmm_rows(key, n=2000, d=4, true_k=3, spread=50.0)
    b = ref.gmm_rows(key, n=2000, d=4, true_k=3, spread=50.0)
    fresh = ref.gmm_rows(key, n=2000, d=4, true_k=3, spread=50.0,
                         row_key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(a), np.asarray(fresh))
    # widely spread components: every fresh row sits within noise of
    # some row of the first draw's components
    gap = _np_nearest(np.asarray(fresh), np.asarray(a)[:200])[1]
    assert np.median(gap) < 4 * 4.0


def test_seed_key_keeps_bits_above_32():
    assert not np.array_equal(np.asarray(ref.seed_key(7)),
                              np.asarray(ref.seed_key(7 + 2**33)))


def test_nearest_matches_numpy(data):
    x, c = data
    a, sq = ref.nearest(jnp.asarray(x), jnp.asarray(c), chunk=256)
    a_np, sq_np = _np_nearest(x, c)
    np.testing.assert_array_equal(np.asarray(a), a_np)
    np.testing.assert_allclose(np.asarray(sq), sq_np, rtol=1e-4, atol=1e-3)


def test_lloyd_matches_numpy(data):
    x, c0 = data
    c, a = ref.lloyd(jnp.asarray(x), jnp.asarray(c0), 6)
    cn = c0.astype(np.float64)
    for _ in range(6):
        an, _ = _np_nearest(x, cn)
        for j in range(cn.shape[0]):
            if (an == j).any():
                cn[j] = x[an == j].mean(0)
    np.testing.assert_array_equal(np.asarray(a), an)
    np.testing.assert_allclose(np.asarray(c), cn, rtol=1e-5, atol=1e-5)
    e = float(ref.energy(jnp.asarray(x), c, a))
    assert e == pytest.approx(((x - cn[an]) ** 2).sum(), rel=1e-5)


def test_center_gap(data):
    x, c0 = data
    c, a = ref.lloyd(jnp.asarray(x), jnp.asarray(c0), 6)
    xj = jnp.asarray(x)
    assert float(ref.center_gap(xj, c, a)) < 1e-6
    moved = c.at[0].add(0.5)
    assert float(ref.center_gap(xj, moved, a)) > 1e-2
    assert float(ref.center_gap(xj, c, a.at[0].set(99))) == 1.0


def test_lloyd_gain_is_small_at_a_minimum_and_large_off_it(data):
    x, c0 = data
    xj = jnp.asarray(x)
    c, a = ref.lloyd(xj, jnp.asarray(c0), 30)
    assert 0.0 <= float(ref.lloyd_gain(xj, c, a, 5)) < 1e-6
    start = ref.nearest(xj, jnp.asarray(c0))[0]
    assert float(ref.lloyd_gain(xj, jnp.asarray(c0), start, 5)) > 0.05


def test_truth_energy_is_that_of_the_component_means():
    x, comp = ref.gmm_rows(ref.seed_key(5), n=500, d=3, true_k=4,
                           components=True)
    xn, cn = np.asarray(x, np.float64), np.asarray(comp)
    want = sum(((xn[cn == j] - xn[cn == j].mean(0)) ** 2).sum()
               for j in np.unique(cn))
    assert float(ref.truth_energy(x, comp, 4)) == pytest.approx(want,
                                                                rel=1e-5)
    np.testing.assert_array_equal(
        np.asarray(x), np.asarray(ref.gmm_rows(ref.seed_key(5), n=500, d=3,
                                               true_k=4)))


def test_lower_precision_control_moves_the_centers(data):
    x, c0 = data
    c, _ = ref.lloyd(jnp.asarray(x), jnp.asarray(c0), 6)
    cb, _ = ref.lloyd(jnp.asarray(x), jnp.asarray(c0), 6,
                      dtype=jnp.bfloat16)
    rel = np.abs(np.asarray(cb) - np.asarray(c)).max() / \
        np.abs(np.asarray(c)).max()
    assert rel > 1e-4
