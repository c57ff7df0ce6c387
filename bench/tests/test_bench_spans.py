"""The reduction of the program's own spans (bench/span_reduce.py) and
the metrics read from it: on a hand-built trace with known answers, on a
small trace recorded on a TPU v5e through the harness's traced path (one
``fit`` span around the CPU tests' tiny fit: n=4096, d=32, k=64, kn=16,
5 iterations, pallas, device GDI; kept are the device's ``XLA Ops`` and
``XLA Modules`` lines and the host thread of the ``fit`` span, what the
reductions read), and on the recorded predict trace, which holds no
program span."""
import os

import pytest

from bench import harness
from bench import span_reduce as sr
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# trace_reduce.reduce of tiny_fit.xplane.pb.gz, as the parent commit of
# the program spans gave it
RECORDED_WINDOW_BUSY = (0.333020833, 0.062993413)
RECORDED_GROUPS = {"other": 0.000155968, "init": 0.059992401,
                   "iterate": 0.002845044}
LEAVES = {"kmeans.validate", "kmeans.init.round", "kmeans.exact_start",
          "kmeans.iterate.build", "kmeans.iterate.step",
          "kmeans.iterate.flush", "kmeans.iterate.final"}
METRICS = os.path.join(harness.BENCH, "metrics")
READERS = ["init_idle_ms.fit", "iterate_idle_ms.fit", "entry_idle_ms.fit",
           "host_reads.fit", "recompute_share.fit"]
IDLE = READERS[:3]


def _read(name, table):
    reader = harness.load_module(os.path.join(METRICS, name + ".py"))
    return reader.read({sr.CONTEXT_KEY: table})


def _hand_built():
    ops = {"/device:TPU:0": [tr.Op(f"op{i}", "jit_x", s, e) for i, (s, e)
                             in enumerate([(5, 30), (120, 200), (250, 320),
                                           (620, 650), (720, 750),
                                           (950, 1200)])]}
    spans = [sr.Span("kmeans.fit", 10, 990, {"n": 100, "host_reads": 7}),
             sr.Span("kmeans.validate", 20, 60, {"bad_rows": 0}),
             sr.Span("kmeans.init", 100, 500, {}),
             sr.Span("kmeans.init.round", 150, 300, {}),
             sr.Span("kmeans.init.round", 300, 450, {}),
             sr.Span("kmeans.iterate", 600, 900,
                     {"iterations": 4, "rows_recomputed": 100}),
             sr.Span("kmeans.iterate.flush", 700, 800, {})]
    host = [("fit", 0, 1000)] + [(s.name, s.start, s.end) for s in spans]
    return tr.Trace(ops, sorted(host, key=lambda h: h[1])), spans


def test_idle_goes_to_the_innermost_span():
    trace, spans = _hand_built()
    t = sr.reduce_spans(trace, spans, ["fit"])
    idle = {n: round(r.idle_s * 1e9) for n, r in t.rows.items()}
    assert idle == {"(none)": 5, "kmeans.fit": 190, "kmeans.validate": 30,
                    "kmeans.init": 70, "kmeans.init.round": 180,
                    "kmeans.iterate": 170, "kmeans.iterate.flush": 70}
    s = tr.reduce(trace, ["fit"], {})
    assert t.idle_s == pytest.approx(s.window_s - s.busy_s)
    assert t.windows == 1
    # each gap named by the span holding most of it
    assert [(n, round(sec * 1e9)) for n, sec in t.gaps] == [
        ("kmeans.init.round", 300), ("kmeans.iterate", 200),
        ("kmeans.fit", 90), ("kmeans.iterate", 70),
        ("kmeans.init.round", 50), ("(none)", 5)]


def test_count_wall_and_busy_inside_each_span():
    trace, spans = _hand_built()
    rows = sr.reduce_spans(trace, spans, ["fit"]).rows
    rounds = rows["kmeans.init.round"]
    assert rounds.count == 2
    assert rounds.wall_s == pytest.approx(300e-9)
    assert rounds.busy_s == pytest.approx(120e-9)   # 50 + 50 + 20
    assert rows["kmeans.fit"].busy_s == pytest.approx(270e-9)
    assert rows["(none)"].count == 0


def test_the_five_metrics_on_the_hand_built_trace():
    trace, spans = _hand_built()
    t = sr.reduce_spans(trace, spans, ["fit"])
    got = {name: _read(name, t) for name in READERS}
    assert got == pytest.approx({
        "init_idle_ms.fit": 250e-6, "iterate_idle_ms.fit": 240e-6,
        "entry_idle_ms.fit": 225e-6, "host_reads.fit": 7,
        "recompute_share.fit": 25.0})
    assert sum(got[n] for n in IDLE) == pytest.approx(1e3 * t.idle_s)


def test_readers_find_nothing_without_program_spans():
    trace, _ = _hand_built()
    empty = sr.reduce_spans(trace, [], ["fit"])
    for name in READERS:
        assert _read(name, empty) is None
    table = sr.table_of(os.path.join(DATA, "tiny_predict.xplane.pb.gz"),
                        ["predict_call"])
    assert table.spans == [] and table.idle_s > 0
    for name in READERS:
        assert _read(name, table) is None


def test_recorded_tpu_fit_trace():
    path = os.path.join(DATA, "tiny_fit.xplane.pb.gz")
    trace = tr.load(path, ["fit"])
    groups = tr.load_groups(os.path.join(harness.BENCH, "program_groups"))
    s = tr.reduce(trace, ["fit"], groups)
    assert (s.window_s, s.busy_s) == RECORDED_WINDOW_BUSY
    assert s.group_s == RECORDED_GROUPS
    table = sr.table_of(path, ["fit"])
    idle = sum(_read(name, table) for name in IDLE)
    assert abs(idle - 1e3 * (s.window_s - s.busy_s)) < 1e-3     # 1 us
    assert {sp.name for sp in table.spans} == LEAVES | {
        "kmeans.fit", "kmeans.init", "kmeans.iterate"}
    # every gap of 10 ms or more lies in a leaf span: the longest is the
    # re-lowering of K2Step's layout-build partial
    assert table.gaps[0][0] == "kmeans.iterate.build"
    assert all(name in LEAVES for name, sec in table.gaps if sec >= 0.01)
    rounds = table.rows["kmeans.init.round"].count
    flushes = table.rows["kmeans.iterate.flush"].count
    assert _read("host_reads.fit", table) == 1 + rounds + flushes
    assert 0 < _read("recompute_share.fit", table) <= 100


def test_every_reader_reads_alike_from_the_harness_single_parse():
    """The harness parses the trace once for the device summary and the
    span table; each reader gives what it gives from separate reads."""
    path = os.path.join(DATA, "tiny_fit.xplane.pb.gz")
    summary, table = harness.reduce_trace(path, "fit")
    once = {"summary": summary, sr.CONTEXT_KEY: table}
    groups = tr.load_groups(os.path.join(harness.BENCH, "program_groups"))
    apart = {"summary": tr.reduce(tr.load(path, ["fit"]), ["fit"], groups),
             sr.CONTEXT_KEY: sr.table_of(path, ["fit"])}
    names = sorted(f[:-3] for f in os.listdir(METRICS) if f.endswith(".py"))
    assert set(READERS) < set(names)
    for name in names:
        reader = harness.load_module(os.path.join(METRICS, name + ".py"))
        assert reader.read(once) == reader.read(apart), name
    for name in READERS:
        assert _read(name, table) is not None


def test_readers_give_the_mean_over_a_pass_of_three_fits():
    """A traced pass of three fits alike reads what one fit reads: the
    per-fit readers divide by the number of ``fit`` spans."""
    one, spans = _hand_built()

    def shift(t, j):                # fit j of the pass, 2000 ns apart
        return t + 2000 * j
    ops = {dev: [tr.Op(o.name, o.module, shift(o.start, j), shift(o.end, j))
                 for j in range(3) for o in lst]
           for dev, lst in one.ops.items()}
    spans3 = [sr.Span(s.name, shift(s.start, j), shift(s.end, j), s.stats)
              for j in range(3) for s in spans]
    host = sorted([(n, shift(s, j), shift(e, j)) for j in range(3)
                   for n, s, e in one.host], key=lambda h: h[1])
    three = tr.Trace(ops, host)
    t1 = sr.reduce_spans(one, spans, ["fit"])
    t3 = sr.reduce_spans(three, spans3, ["fit"])
    assert t3.windows == 3
    for name in READERS:
        assert _read(name, t3) == pytest.approx(_read(name, t1)), name
