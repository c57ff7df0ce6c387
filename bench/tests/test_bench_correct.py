"""``correct`` on the CPU at a tiny size: a sound run passes; the
control (the plain reference one precision down, in the program's place)
and each fault of ``bench/faults.py``, planted under the timed path,
fail."""
import jax
import pytest

from bench import faults
from conftest import run_tiny, tiny_cell

FIT = "fit_sift1m_k4096"


@pytest.fixture
def fresh_jit_caches():
    """Traces made with a planted fault must not outlive the test."""
    yield
    jax.clear_caches()


@pytest.mark.parametrize("name", [FIT])
def test_sound_run_is_correct(name):
    r = run_tiny(tiny_cell(name))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    for m in r["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("name", [FIT])
def test_control_fails(name, monkeypatch):
    from bench import harness
    real = harness.driver_of

    def with_control(cell):
        mod = real(cell)
        setup = mod.setup

        def setup_control(cell, seed, warm=True):
            s = setup(cell, seed, warm=False)
            s.unit = lambda i: s.control()
            return s
        monkeypatch.setattr(mod, "setup", setup_control)
        return mod
    monkeypatch.setattr(harness, "driver_of", with_control)
    r = run_tiny(tiny_cell(name))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name,fault", [(FIT, f) for f in faults.FAULTS])
def test_fault_fails(name, fault, monkeypatch, fresh_jit_caches):
    # the faults go in after set-up's warm-up, under the window's units
    from bench import harness
    real = harness.driver_of

    def faulty(cell):
        mod = real(cell)
        setup = mod.setup

        def setup_then_break(cell, seed, warm=True):
            s = setup(cell, seed, warm)
            faults.plant(fault, monkeypatch.setattr)
            return s
        monkeypatch.setattr(mod, "setup", setup_then_break)
        return mod
    monkeypatch.setattr(harness, "driver_of", faulty)
    r = run_tiny(tiny_cell(name))
    assert not r["correct"], (fault, r["checks"])
