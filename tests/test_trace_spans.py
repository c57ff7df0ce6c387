"""The fit's profiler spans and its host-read counter.

``api.fit`` names its phases with ``jax.profiler.TraceAnnotation`` spans
(``kmeans.*``) whose attributes are counts already on the host, and
counts every blocking device-to-host read on ``OpCounter.host_reads``.
Here a tiny fit (the benchmark's CPU shape) runs under the profiler and
its host events are read back with ``ProfileData``.
"""
import glob

import jax
import numpy as np
import pytest

from repro.core import OpCounter, api
from repro.data import gmm_blobs

N, D, K, KN, ITERS = 4096, 32, 64, 16, 5
FIT = dict(method="k2means", init="gdi", backend="pallas", kn=KN,
           max_iters=ITERS)


def _rows():
    return gmm_blobs(jax.random.PRNGKey(0), N, D, true_k=K)


def _fit(x, counter):
    r = api.fit(x, K, key=jax.random.PRNGKey(1), counter=counter, **FIT)
    return jax.block_until_ready(r)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(result, counter, spans) of one fit under the profiler; spans are
    (name, start, end, stats) of the fit's thread, by start."""
    from jax.profiler import ProfileData
    x = _rows()
    _fit(x, OpCounter())                    # compile outside the trace
    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    counter = OpCounter()
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        result = _fit(x, counter)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("kmeans.")]
    return result, counter, sorted(spans, key=lambda s: (s[1], -s[2]))


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_span_tree(traced):
    _, _, spans = traced
    fit, = _named(spans, "kmeans.fit")
    phases = [_named(spans, n)[0] for n in
              ("kmeans.validate", "kmeans.init", "kmeans.exact_start",
               "kmeans.iterate")]
    for p in phases:
        assert _inside(p, fit)
    assert [p[1] for p in phases] == sorted(p[1] for p in phases)
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    init, iterate = phases[1], phases[3]
    for s in spans:
        if s[0].startswith("kmeans.init."):
            assert _inside(s, init)
        if s[0].startswith("kmeans.iterate."):
            assert _inside(s, iterate)
    children = [s[0] for s in spans if s[0].startswith("kmeans.iterate.")]
    assert children[0] == "kmeans.iterate.build"
    assert children[-1] == "kmeans.iterate.final"
    assert children[1:-1] == ["kmeans.iterate.step",
                              "kmeans.iterate.flush"] * ITERS
    assert fit[3] == {"method": "k2means", "init": "gdi", "n": N, "d": D,
                      "k": K, "kn": KN, "host_reads": fit[3]["host_reads"]}
    assert phases[0][3] == {"bad_rows": 0}


def test_one_round_span_per_gdi_round(traced):
    _, _, spans = traced
    init, = _named(spans, "kmeans.init")
    rounds = _named(spans, "kmeans.init.round")
    assert [s[3]["round"] for s in rounds] == list(range(len(rounds)))
    leaves = [s[3]["leaves"] for s in rounds]
    assert all(a < b for a, b in zip(leaves, leaves[1:]))
    assert leaves[-1] == K
    assert init[3] == {"rounds": len(rounds), "leaves": K,
                       "rows_swept": init[3]["rows_swept"],
                       "rows_full": init[3]["rows_full"]}


def test_init_span_counts_the_rows_its_rounds_swept(traced):
    _, _, spans = traced
    init, = _named(spans, "kmeans.init")
    rows = [s[3]["rows"] for s in _named(spans, "kmeans.init.round")]
    assert init[3]["rows_swept"] == sum(rows)
    assert 0 < init[3]["rows_swept"] <= init[3]["rows_full"]
    full, rest = divmod(init[3]["rows_full"], len(rows))
    assert rest == 0 and max(rows) <= full


def test_one_flush_per_iteration(traced):
    result, counter, spans = traced
    steps = _named(spans, "kmeans.iterate.step")
    flushes = _named(spans, "kmeans.iterate.flush")
    assert [s[3]["it"] for s in steps] == list(range(1, ITERS + 1))
    assert [f[3]["iterations"] for f in flushes] == [1] * ITERS
    assert result.iterations == ITERS
    profile = counter.profile()
    assert sum(f[3]["moved"] for f in flushes) == profile["rows_moved"]
    assert sum(f[3]["resorted"] for f in flushes) == profile["resorts"]
    iterate, = _named(spans, "kmeans.iterate")
    assert iterate[3]["iterations"] == ITERS
    last_changed = flushes[-1][3]["changed"]
    assert bool(iterate[3]["converged"]) == (last_changed == 0)
    assert all(f[3]["changed"] >= 0 for f in flushes)
    assert iterate[3]["rows_recomputed"] == \
        sum(f[3]["n_need"] for f in flushes)
    assert 0 < iterate[3]["rows_recomputed"] <= N * ITERS


def test_host_reads_are_counted_and_carried_by_the_fit_span(traced):
    _, counter, spans = traced
    fit, = _named(spans, "kmeans.fit")
    rounds = len(_named(spans, "kmeans.init.round"))
    flushes = len(_named(spans, "kmeans.iterate.flush"))
    assert fit[3]["host_reads"] == 1 + rounds + flushes
    assert counter.profile()["host_reads"] == fit[3]["host_reads"]


def test_an_unprofiled_fit_is_the_same_fit(traced):
    result, counter, _ = traced
    plain = OpCounter()
    r = _fit(_rows(), plain)
    np.testing.assert_array_equal(np.asarray(r.centers),
                                  np.asarray(result.centers))
    np.testing.assert_array_equal(np.asarray(r.assignment),
                                  np.asarray(result.assignment))
    assert plain.host_reads == counter.host_reads


@pytest.mark.parametrize("init, reads", [
    ("gdi_init", lambda info: 1 + 3 * info["rounds"]),
    ("gdi_device_init", lambda info: info["rounds"]),
    ("gdi_parallel_init", lambda info: info["rounds"]),
])
def test_host_reads_of_each_gdi(init, reads):
    from repro.core import gdi
    x = gmm_blobs(jax.random.PRNGKey(2), 512, 8, true_k=8)
    counter, info = OpCounter(), {}
    getattr(gdi, init)(x, 8, jax.random.PRNGKey(3), counter=counter,
                       info=info)
    assert info["leaves"] == 8 and info["rounds"] >= 3
    assert counter.host_reads == reads(info)


def test_host_reads_of_a_fit_without_gdi():
    x = gmm_blobs(jax.random.PRNGKey(2), 512, 8, true_k=8)
    counter = OpCounter()
    r = api.fit(x, 8, init="kmeanspp", kn=4, max_iters=3, counter=counter,
                key=jax.random.PRNGKey(4))
    assert counter.host_reads == 1 + r.iterations     # validate, flushes


def test_guards_add_one_read_per_flush():
    x = gmm_blobs(jax.random.PRNGKey(2), 512, 8, true_k=8)
    reads = []
    for guards in (False, True):
        counter = OpCounter()
        r = api.fit(x, 8, init="random", kn=4, max_iters=3,
                    counter=counter, guards=guards,
                    key=jax.random.PRNGKey(4))
        reads.append((counter.host_reads, r.iterations))
    (plain, it0), (guarded, it1) = reads
    assert it0 == it1 and guarded == plain + it1
