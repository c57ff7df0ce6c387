"""Shared benchmark helpers: dataset stand-ins scaled for the CPU budget,
speedup accounting (counted ops to reach a reference energy), CSV output.

The paper's metric is machine-independent (counted vector ops, §3), so the
speedup *ratios* transfer from these reduced-scale runs; shapes are scaled
stand-ins of the paper's datasets (see repro.data.synthetic).
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

from repro.core import OpCounter, fit
from repro.data import dataset_like

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already names the cache (JAX
    reads it) and is left alone. Otherwise the cache goes to the fixed
    directory ``<repo>/.jax_cache``: the path is part of what later runs
    look up, so it never moves. Returns the directory in use. Entry
    points call this; importing the library never does."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# reduced-scale grid for the CPU-only CI budget
BENCH_DATASETS = ("mnist50", "usps", "tinygist10k", "covtype")
BENCH_SCALE = {"mnist50": 0.08, "usps": 0.5, "tinygist10k": 0.35,
               "covtype": 0.03}
BENCH_K = (50, 100)
SEEDS = (0, 1)


def load(name: str):
    key = jax.random.fold_in(jax.random.PRNGKey(42), hash(name) % 2 ** 16)
    return dataset_like(name, key, scale=BENCH_SCALE.get(name, 0.1))


def ops_to_reach(history, target: float):
    """First cumulative op count whose energy is <= target, else None."""
    for ops, energy in history:
        if energy <= target:
            return ops
    return None


def run_method(x, k, method, init, seed, **kw):
    counter = OpCounter()
    r = fit(x, k, method=method, init=init, key=jax.random.PRNGKey(seed),
            counter=counter, **kw)
    return r


def emit(rows, header):
    print(",".join(header))
    for row in rows:
        print(",".join(str(v) for v in row))
    return rows
