"""Faults planted under the fit's timed path.

Each breaks one layer that ``correct`` has to cover, inside the program
rather than on its output, so that the comparison has to find it:

- ``gdi_random``: the GDI start replaced by a Forgy start (k rows drawn
  at random), the iteration left as it is;
- ``state_unchanged``: the bounded step returns the state it was given;
- ``half_batch``: the step leaves out the second half of the rows (their
  weight set to 0), so the centers are the means of the rest;
- ``answer_altered``: the assignment kernel's answer for every row moved
  from the winning center to that center's nearest neighbour.

``plant(name, set_attr)`` patches the program with ``set_attr`` (pytest's
``monkeypatch.setattr`` undoes it; plain ``setattr`` by default) and
clears JAX's in-memory caches so that the next fit traces the broken
path. ``readings.py --fault`` reads the compared numbers of a fault at
the cell's own size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _gdi_random(set_attr):
    from repro.core import api
    real = api.initialize

    def initialize(x, k, init, key, counter, backend=None):
        return real(x, k, "random", key, counter, backend=backend)
    set_attr(api, "initialize", initialize)


def _state_unchanged(set_attr):
    from repro.core import engine
    real = engine._resident_single_step

    def step(x, w, state, **kw):
        return state, real(x, w, state, **kw)[1]
    set_attr(engine, "_resident_single_step", step)


def _half(w):
    return w.at[w.shape[0] // 2:].set(0)


def _half_batch(set_attr):
    from repro.core import engine
    real_step = engine._resident_single_step
    real_init = engine.K2Step.init_resident

    def step(x, w, state, **kw):
        return real_step(x, _half(w), state, **kw)

    def init_resident(self, x, w, centers, assignment):
        return real_init(self, x, _half(w), centers, assignment)
    set_attr(engine, "_resident_single_step", step)
    set_attr(engine.K2Step, "init_resident", init_resident)


def _answer_altered(set_attr):
    from repro.kernels import candidate_assign as ca
    real = ca.candidate_assign_tiled

    def kernel(x, ctab, csqtab, cidx, *args, **kw):
        a, d1, d2 = real(x, ctab, csqtab, cidx, *args, **kw)
        # cidx row j lists center j's neighbours, nearest first (j itself)
        return cidx[jnp.clip(a, 0, cidx.shape[0] - 1), 1], d1, d2
    set_attr(ca, "candidate_assign_tiled", kernel)


FAULTS = {"gdi_random": _gdi_random, "state_unchanged": _state_unchanged,
          "half_batch": _half_batch, "answer_altered": _answer_altered}


def plant(name: str, set_attr=setattr) -> None:
    FAULTS[name](set_attr)
    jax.clear_caches()
