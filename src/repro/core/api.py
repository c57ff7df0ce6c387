"""Single entry point: ``fit(x, k, method=..., init=...)``.

This is the public clustering API used by the examples, the benchmark
harness and the LM integration (clustered-KV attention, MoE router init).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .akm import fit_akm
from .elkan import fit_elkan
from .gdi import gdi_device_init, gdi_init, gdi_parallel_init
from .k2means import fit_k2means
from .kmeanspp import assign_nearest, kmeanspp_init, random_init
from .lloyd import KMeansResult, fit_lloyd
from .minibatch import fit_minibatch
from .opcount import OpCounter

METHODS = ("lloyd", "elkan", "k2means", "minibatch", "akm")
INITS = ("random", "kmeanspp", "gdi", "gdi_host", "gdi_device",
         "gdi_parallel")


def initialize(x: jax.Array, k: int, init: str, key: jax.Array,
               counter: OpCounter, backend: str | None = None) -> jax.Array:
    """Initial centers, (k, d), under the profiler span ``kmeans.init``
    (its ``rounds`` and ``leaves`` set by the divisive inits).

    ``init="gdi"`` resolves to the frontier-batched device GDI when the
    fit runs on the Pallas fast path (``backend="pallas"``) so the whole
    program — init through convergence — stays on device, and to the
    host-loop reference otherwise. ``"gdi_host"`` / ``"gdi_device"`` pin
    one explicitly. The divisive inits' leaf assignments are dropped:
    k²-means starts from the exact assignment (:func:`fit_k2means`).
    """
    if init == "gdi":
        init = "gdi_device" if backend == "pallas" else "gdi_host"
    with jax.profiler.TraceAnnotation("kmeans.init") as span:
        if init == "random":
            return random_init(x, k, key, counter)
        if init == "kmeanspp":
            return kmeanspp_init(x, k, key, counter)
        divisive = {"gdi_host": gdi_init, "gdi_device": gdi_device_init,
                    "gdi_parallel": gdi_parallel_init}.get(init)
        if divisive is None:
            raise ValueError(f"unknown init {init!r}; expected one of "
                             f"{INITS}")
        info = {}
        centers = divisive(x, k, key, counter=counter, info=info)[0]
        if span.is_enabled():
            span.set_metadata(**info)
        return centers


def fit(x: jax.Array, k: int, *, method: str = "k2means", init: str = "gdi",
        key: jax.Array | None = None, max_iters: int = 100,
        kn: int = 30, m: int = 30, batch: int = 100,
        minibatch_iters: int | None = None,
        counter: OpCounter | None = None,
        mesh: Any = None, profile: bool = False,
        return_model: bool = False,
        model_capacity: int | None = None,
        validate: str = "raise", **kw: Any):
    """Cluster ``x`` into ``k`` clusters -> :class:`KMeansResult` (or
    ``(result, model)`` with ``return_model=True``). The paper's method
    is the default.

    Extra keywords flow to the method's fit function — notably
    ``backend="pallas"`` selects the fused k²-means device step
    (kernels + DESIGN.md §3), ``residency="resident"|"rebuild"`` picks
    between the persistent sparsely-repaired cluster-grouped layout and
    the per-iteration rebuild (DESIGN.md §9; resident is the pallas
    default) and ``monitor_every=<m>`` defers the energy/op-count host
    reads. With ``backend="pallas"`` and the default ``init="gdi"`` the
    initialization also runs device-resident (the frontier round step,
    DESIGN.md §4), so init -> kNN graph -> grouped assignment -> update
    chain as one device program with no host round trips besides the
    per-round leaf count and the ``monitor_every`` telemetry reads.

    ``profile=True`` attaches the counter's full op + memory-traffic
    breakdown (distances / additions / sort equivalents and the layout
    bytes gathered / scattered / sorted, ``OpCounter.profile()``) to the
    result's ``profile`` field — the residency win is directly readable
    from ``bytes_moved``.

    ``return_model=True`` returns ``(result, model)`` where ``model`` is
    a :class:`core.model.KMeansModel` built over the fit (centers +
    center kNN graph + per-cluster stats + resident member arena with
    ``model_capacity`` total rows, default 2n) — the query-time subsystem
    behind ``model.predict`` / ``model.partial_fit`` (DESIGN.md §10).

    ``mesh=<jax Mesh>`` places the same engine iteration sharded
    (core.distributed / DESIGN.md §7-8): points row-sharded over the
    mesh's data axes, centers replicated, convergence via the psum'd
    changed count — supported for ``method="k2means"`` with
    ``init`` in ("random", "kmeanspp", "gdi", "gdi_replicated") (the
    "gdi" seeding runs the frontier rounds per shard-group). The same
    extra keywords apply (``backend`` defaults to "pallas" there).

    ``validate``: "raise" (default) rejects inputs carrying non-finite
    rows with an error naming them; "sanitize" zeroes those rows before
    fitting (quarantine, counted on ``counter.sanitized_rows``); "none"
    skips the check (DESIGN.md §11.5).
    """
    counter = counter or OpCounter()
    reads0 = counter.host_reads
    with jax.profiler.TraceAnnotation("kmeans.fit") as span:
        key = key if key is not None else jax.random.PRNGKey(0)
        x = jnp.asarray(x, jnp.float32)
        if x.ndim != 2:
            raise ValueError(f"x must be 2-D (n, d), got shape {x.shape}")
        if validate not in ("raise", "sanitize", "none"):
            raise ValueError(f"validate must be 'raise' | 'sanitize' | "
                             f"'none', got {validate!r}")
        if validate != "none":
            x = _validated(x, validate, counter)
        result = _fit_placed(x, k, method, init, key, counter, mesh,
                             max_iters=max_iters, kn=kn, m=m, batch=batch,
                             minibatch_iters=minibatch_iters, **kw)
        if span.is_enabled():
            span.set_metadata(method=method, init=init, n=x.shape[0],
                              d=x.shape[1], k=k, kn=kn,
                              host_reads=counter.host_reads - reads0)
        if profile:
            result.profile = counter.profile()
        if not return_model:
            return result
        from .model import KMeansModel
        # the mesh placement defaults backend to "pallas"; the served model
        # follows the backend the fit actually ran on
        backend = kw.get("backend") or \
            ("pallas" if mesh is not None else "xla")
        return result, KMeansModel.from_result(
            result, x, kn=min(kn, k), capacity=model_capacity,
            backend=backend, interpret=kw.get("interpret"))


def _validated(x: jax.Array, validate: str,
               counter: OpCounter) -> jax.Array:
    """``x`` checked for non-finite rows (one host read, under the span
    ``kmeans.validate``): "raise" rejects them, "sanitize" zeroes them."""
    with jax.profiler.TraceAnnotation("kmeans.validate") as span:
        bad = ~jnp.isfinite(x).all(axis=1)
        n_bad = int(jnp.sum(bad))
        counter.host_reads += 1
        if span.is_enabled():
            span.set_metadata(bad_rows=n_bad)
    if not n_bad:
        return x
    if validate == "raise":
        import numpy as np
        idx = np.flatnonzero(np.asarray(bad))[:8]
        raise ValueError(
            f"fit input: {n_bad} non-finite rows (first at "
            f"{idx.tolist()}); pass validate='sanitize' to zero them")
    counter.count_sanitized_rows(n_bad)
    return jnp.where(bad[:, None], 0.0, x)


def _fit_placed(x, k, method, init, key, counter, mesh, *, max_iters, kn,
                m, batch, minibatch_iters, **kw) -> KMeansResult:
    """The fit itself, on one device or on ``mesh`` (see :func:`fit`)."""
    k_init, k_fit = jax.random.split(key)
    if mesh is not None:
        if method != "k2means":
            raise ValueError(
                f"mesh placement supports method='k2means' only, got "
                f"{method!r}")
        from .distributed import fit_distributed_k2means
        # k_init, as on the single-device path: init="random" from the
        # same seed samples the same centers under either placement
        return fit_distributed_k2means(x, k, kn, mesh, k_init,
                                       max_iters=max_iters, init=init,
                                       counter=counter, **kw)

    centers = initialize(x, k, init, k_init, counter,
                         backend=kw.get("backend"))

    if method == "lloyd":
        return fit_lloyd(x, centers, max_iters=max_iters, counter=counter,
                         **kw)
    if method == "elkan":
        return fit_elkan(x, centers, max_iters=max_iters, counter=counter,
                         **kw)
    if method == "k2means":
        # the exact start fit_k2means requires (its docstring)
        with jax.profiler.TraceAnnotation("kmeans.exact_start"):
            assignment = assign_nearest(x, centers, counter)
        return fit_k2means(x, centers, assignment, kn=kn,
                           max_iters=max_iters, counter=counter, **kw)
    if method == "minibatch":
        return fit_minibatch(x, centers, k_fit, batch=batch,
                             iters=minibatch_iters, counter=counter, **kw)
    if method == "akm":
        return fit_akm(x, centers, k_fit, m=m, max_iters=max_iters,
                       counter=counter, **kw)
    raise ValueError(f"unknown method {method!r}; expected one of {METHODS}")
