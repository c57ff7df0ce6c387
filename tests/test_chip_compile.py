"""Chip-legality without a chip: the main-path Pallas kernels and the
resident k²-means step, compiled for a described TPU v5e (``v5e:2x2``)
with ``interpret=False``.

Interpret mode (every other kernel test) accepts any block layout; the
TPU compiler refuses blocks off the (8, 128) tiling, rank-1 blocks that
are not lane multiples and primitives Mosaic cannot lower (``cumsum``).
These compiles run the real Mosaic and XLA:TPU passes on the host, so a
refusal shows up here instead of on the chip. Shapes are the IVF
coarse-quantizer fit: d=128, k=4096, kn=32 (the resident step also at
the smoke's kn=128), with the point block at every size
``ops.choose_group_bn`` returns: bn=128 at
n/k = 256, down to the paper's small-n/k regime (bn=16) and the serving
batches (bn=8).

The topology is described inside a module fixture, never at import, so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D, K, KN, BKN = 128, 4096, 32, 8
N_SMOKE = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _tiled_args(sh, n, bn, dtype):
    nb = n // bn
    i32 = jnp.int32
    return [_spec(sh, (n, D), dtype), _spec(sh, (K, KN, D), dtype),
            _spec(sh, (K, KN)), _spec(sh, (K, KN), i32),
            _spec(sh, (nb,), i32), _spec(sh, (nb,), i32)]


def _candidate_assign_tiled(sh, n, bn):
    from repro.kernels.candidate_assign import candidate_assign_tiled
    args = _tiled_args(sh, n, bn, jnp.float32)
    args += [_spec(sh, (n,), jnp.int32), _spec(sh, (n,)), _spec(sh, (n,))]
    return functools.partial(candidate_assign_tiled, bn=bn, bkn=BKN,
                             interpret=False), args


def _candidate_assign_int8_tiled(sh, n, bn):
    from repro.kernels.candidate_assign import candidate_assign_int8_tiled
    xq, qtab, csq, _, rowsel, skip = _tiled_args(sh, n, bn, jnp.int8)
    args = [xq, _spec(sh, (n,)), _spec(sh, (n,)), qtab, csq, csq, csq,
            rowsel, skip]
    return functools.partial(candidate_assign_int8_tiled, bn=bn, bkn=BKN,
                             r=8, interpret=False), args


def _segmented_scan(sh, n, bn):
    from repro.kernels.segmented_scan import segmented_scan
    args = [_spec(sh, (n, D)), _spec(sh, (n,)),
            _spec(sh, (n // bn,), jnp.int32)]
    return functools.partial(segmented_scan, bn=bn, interpret=False), args


def _distance_argmin(sh, n, bn):
    from repro.kernels.distance_argmin import distance_argmin
    return (functools.partial(distance_argmin, bn=bn, bk=128,
                              interpret=False),
            [_spec(sh, (n, D)), _spec(sh, (K, D))])


@pytest.mark.parametrize("bn", [8, 16, 32, 64, 128])
@pytest.mark.parametrize("build", [_candidate_assign_tiled,
                                   _candidate_assign_int8_tiled,
                                   _segmented_scan, _distance_argmin],
                         ids=lambda f: f.__name__.strip("_"))
def test_gridded_kernel_compiles_for_v5e(one_chip, build, bn):
    """Every gridded kernel at every point-block regime (n = bn * k, the
    grouped layout's n/k = bn)."""
    fn, args = build(one_chip, bn * K, bn)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_center_sqdist_compiles_for_v5e(one_chip):
    from repro.kernels.center_knn import center_sqdist
    fn = functools.partial(center_sqdist, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, _spec(one_chip, (K, D)))


@pytest.mark.parametrize("kn", [KN, 128])
def test_resident_step_compiles_for_v5e(one_chip, kn):
    """The single-device resident K2Step at the smoke's fit shape: the
    whole iteration (kNN graph, bounded tiled assignment, repair or
    re-sort, update) as one TPU program."""
    from repro.core.engine import K2Step
    sb = K2Step(k=K, kn=kn, backend="pallas", residency="resident",
                interpret=False)
    x = _spec(one_chip, (N_SMOKE, D))
    w = _spec(one_chip, (N_SMOKE,))
    state = jax.eval_shape(sb.init_resident, x, w, _spec(one_chip, (K, D)),
                           _spec(one_chip, (N_SMOKE,), jnp.int32))
    state = jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), state)
    text = _compiled_text(sb.build(N_SMOKE, D), x, w, state)
    assert "tpu_custom_call" in text

