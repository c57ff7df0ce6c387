"""The measured window covers whole passes of the session's panel, and
the fit driver's panel of keys is the same in every run: the run's seed
draws only the control's start."""
import types

import jax
import numpy as np
import pytest

from bench import harness
from conftest import run_tiny, tiny_cell

FIT = "fit_sift1m_k4096"


class _Clock:
    """A stand-in for ``time.perf_counter`` that only units advance."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Session:
    """Units of ``unit_s`` seconds on the fake clock; ``pass_units`` is
    declared only where given."""

    def __init__(self, clock, unit_s, pass_units=None):
        self.clock, self.unit_s = clock, unit_s
        if pass_units is not None:
            self.pass_units = pass_units

    def unit(self, i):
        self.clock.t += self.unit_s
        return i


def _window(monkeypatch, seconds, unit_s, pass_units=None):
    clock = _Clock()
    monkeypatch.setattr(harness.time, "perf_counter", clock)
    counter = types.SimpleNamespace(active=False)
    outputs, elapsed, failed = harness.window(
        _Session(clock, unit_s, pass_units), seconds, counter)
    assert failed == 0 and not counter.active
    assert outputs == list(range(len(outputs)))
    return outputs, elapsed


def test_zero_seconds_take_one_whole_pass(monkeypatch):
    outputs, elapsed = _window(monkeypatch, 0.0, 1.0, pass_units=3)
    assert len(outputs) == 3 and elapsed == 3.0


def test_a_pass_cut_by_the_deadline_is_finished(monkeypatch):
    # units start at 0, 1, 2, 3, 4 inside 4.5 s; the sixth ends the pass
    outputs, elapsed = _window(monkeypatch, 4.5, 1.0, pass_units=3)
    assert len(outputs) == 6 and elapsed == 6.0
    # fits shorter than a third of the window: two whole passes
    outputs, _ = _window(monkeypatch, 10.0, 3.0, pass_units=3)
    assert len(outputs) == 6


def test_no_pass_length_keeps_the_deadline_rule(monkeypatch):
    outputs, elapsed = _window(monkeypatch, 4.5, 1.0)
    assert len(outputs) == 5 and elapsed == 5.0
    outputs, _ = _window(monkeypatch, 4.5, 1.0, pass_units=1)
    assert len(outputs) == 5


def _keys_fitted(session, units):
    seen = []

    def fit(x, k, key, **kw):
        seen.append(np.asarray(key))
        return types.SimpleNamespace(centers=x[:k], assignment=x[:, 0])
    session._fit = fit
    for i in units:
        session.unit(i)
    return seen


def test_the_panel_not_the_seed_sets_the_fit_keys():
    from bench.drivers import fit_loop
    cell = tiny_cell(FIT)
    a = fit_loop.setup(cell, 2**31 + 101, warm=False)
    b = fit_loop.setup(cell, 7, warm=False)
    assert a.fit_seeds == b.fit_seeds == cell.config["fit_seeds"]
    assert a.pass_units == b.pass_units == 3
    ka, kb = _keys_fitted(a, range(6)), _keys_fitted(b, range(6))
    for x, y in zip(ka, kb):
        np.testing.assert_array_equal(x, y)
    # units 0, 1, 2 fit three different keys, then the pass repeats
    assert not np.array_equal(ka[0], ka[1])
    assert not np.array_equal(ka[1], ka[2])
    for i in range(3):
        np.testing.assert_array_equal(ka[i], ka[i + 3])
    assert not np.array_equal(np.asarray(a.k_control),
                              np.asarray(b.k_control))
    # the panel's override: a single key, the one that seed gives
    c = fit_loop.setup(cell, 7, warm=False, fit_seeds=[5])
    assert c.pass_units == 1
    np.testing.assert_array_equal(
        _keys_fitted(c, [0])[0],
        np.asarray(fit_loop.fit_key(5)))


@pytest.mark.parametrize("name", [FIT])
def test_tiny_run_fits_whole_passes_and_is_correct(name):
    r = run_tiny(tiny_cell(name))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["attempted"] >= 3 and r["attempted"] % 3 == 0
