"""Traffic driver: whole fits back to back on the configuration's rows.

A unit is one ``repro.core.api.fit`` call with the configuration's fit
settings, from the initialization through its iterations, waited for.
The configuration fixes the rows (``mixture_seed``, ``rows_seed``) and a
panel of fit keys (``fit_seeds``): unit ``i`` fits with the key of
``fit_seeds[i % len(fit_seeds)]``, and the session's ``pass_units``, the
panel's size, makes the harness's window cover whole passes of it. Which
leaves GDI sweeps, and so a fit's time, depends on its key; with the
panel fixed every run does the same work. The run's seed draws only the
control's Forgy start. Set-up makes the rows on the device and runs one
fit to warm every program. Each unit's key, seconds and energy ratio
are logged after the window.

Each fit's (centers, assignment) is held to the plain references of
``bench/reference.py``:

- ``center_gap``: the centers are the means of the rows assigned to them
  (the iteration's update);
- ``lloyd_gain``: plain HIGHEST Lloyd, run from the fit's own centers,
  finds little left to gain (the iteration's assignment: a fit that
  assigns rows wrongly or stops early leaves a large gain);
- ``truth_excess``: the fit's energy over that of the mixture's own
  partition, less 1 (the initialization: on this mixture a start that
  leaves small components bare ends in a minimum far above GDI's).

The end-to-end ``fit_energy_ratio`` is the mean over the window's fits
of the fit's energy over that of plain HIGHEST Lloyd from the
configuration's fixed Forgy start.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from bench import reference as ref
from bench.harness import log


def make_rows(cell):
    """The configuration's rows, with each row's mixture component."""
    data = cell.config["data"]
    x, comp = ref.gmm_rows(ref.seed_key(data["mixture_seed"]), n=data["n"],
                           d=data["d"], true_k=data["true_k"],
                           spread=data["spread"], noise=data["noise"],
                           row_key=ref.seed_key(data["rows_seed"]),
                           components=True)
    return jax.block_until_ready((x, comp))


def fit_key(seed: int) -> jax.Array:
    """The fit key that ``seed`` gives: the first half of its split (the
    second is the control's)."""
    return jax.random.split(ref.seed_key(seed))[0]


class FitSession:
    span = "fit"

    def __init__(self, cell, seed: int, fit_seeds=None):
        """``fit_seeds`` (default: the configuration's panel) replaces
        the panel of fit keys; only ``bench/readings.py`` sets it."""
        from repro.core import api
        self.cell = cell
        self.fit_kw = dict(cell.config["fit"])
        self.k = self.fit_kw.pop("k")
        self.iters = self.fit_kw["max_iters"]
        self.x, self.comp = make_rows(cell)
        self.fit_seeds = list(cell.config["fit_seeds"] if fit_seeds is None
                              else fit_seeds)
        self.pass_units = len(self.fit_seeds)
        self.fit_keys = [fit_key(s) for s in self.fit_seeds]
        self.k_control = jax.random.split(ref.seed_key(seed))[1]
        self.seconds = []               # of units 0, 1, ...
        self._fit = api.fit

    def unit(self, i: int):
        """Unit ``i`` (-1: set-up's warm-up) fits with the panel's key
        ``i`` modulo its size."""
        t0 = time.perf_counter()
        res = self._fit(self.x, self.k, key=self.fit_keys[i % self.pass_units],
                        **self.fit_kw)
        out = jax.block_until_ready((res.centers, res.assignment))
        if i >= 0:
            self.seconds.append(time.perf_counter() - t0)
        return out

    def release(self) -> None:
        self._fit = None

    def yardstick_energy(self) -> float:
        """Plain HIGHEST Lloyd from the configuration's Forgy start, for
        as many iterations as the fit may run."""
        c0 = ref.forgy(self.x, self.k, ref.seed_key(
            self.cell.config["yardstick"]["forgy_seed"]))
        return float(ref.energy(self.x, *ref.lloyd(self.x, c0, self.iters)))

    def control(self):
        """The reference in the program's place, one precision down: plain
        Lloyd in bfloat16 from a Forgy start drawn by the run's seed."""
        c0 = ref.forgy(self.x, self.k, self.k_control)
        return jax.block_until_ready(
            ref.lloyd(self.x, c0, self.iters, dtype=jnp.bfloat16))

    def compare(self, outputs) -> dict:
        e_yard = self.yardstick_energy()
        e_truth = float(ref.truth_energy(self.x, self.comp,
                                         self.cell.config["data"]["true_k"]))
        gap, gain, e = [], [], []
        for c, a in outputs:
            gap.append(float(ref.center_gap(self.x, c, a)))
            gain.append(float(ref.lloyd_gain(self.x, c, a, self.iters)))
            e.append(float(ref.energy(self.x, c, a)))
        self.ratios = [v / e_yard for v in e]
        log(f"energies: yardstick {e_yard!r}, mixture partition "
            f"{e_truth!r}, fits {e!r}")
        for i, r in enumerate(self.ratios):
            secs = self.seconds[i] if i < len(self.seconds) else None
            log(f"unit {i}: fit key {self.fit_seeds[i % self.pass_units]}, "
                f"{secs!r} s, energy ratio {r!r}")
        return {"center_gap": max(gap), "lloyd_gain": max(gain),
                "truth_excess": max(e) / e_truth - 1.0}

    def end_to_end(self, outputs, elapsed: float) -> dict:
        """After ``compare``, which reads the energies."""
        return {"fit_s": elapsed / len(outputs),
                "fit_energy_ratio": sum(self.ratios) / len(self.ratios)}


def setup(cell, seed: int, warm: bool = True,
          fit_seeds=None) -> FitSession:
    session = FitSession(cell, seed, fit_seeds)
    if warm:
        session.unit(-1)                  # every program compiled
    return session
