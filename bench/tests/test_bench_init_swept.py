"""``init_swept_share.fit``: the share of the full grouped layout's rows
that the GDI rounds swept, read from the ``rows_swept`` / ``rows_full``
attributes of the ``kmeans.init`` spans; None where no span carries
them, as in a trace of a program that sweeps every round at full size."""
import os

import pytest

from bench import harness
from bench import span_reduce as sr
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = "init_swept_share.fit"


def _read(table):
    reader = harness.load_module(os.path.join(harness.BENCH, "metrics",
                                              NAME + ".py"))
    return reader.read({sr.CONTEXT_KEY: table})


def _table(*inits):
    """A span table of one ``fit`` window holding the given
    ``kmeans.init`` attribute dicts, one init span each."""
    ops = {"/device:TPU:0": [tr.Op("op", "jit_x", 10, 20)]}
    spans = [sr.Span("kmeans.fit", 0, 1000, {"n": 100})]
    spans += [sr.Span("kmeans.init", 100 + 200 * i, 200 + 200 * i, stats)
              for i, stats in enumerate(inits)]
    host = [("fit", 0, 1000)] + [(s.name, s.start, s.end) for s in spans]
    return sr.reduce_spans(tr.Trace(ops, host), spans, ["fit"])


def test_share_of_the_rows_swept():
    one = {"rounds": 4, "leaves": 8, "rows_swept": 250, "rows_full": 1000}
    assert _read(_table(one)) == pytest.approx(25.0)
    two = {"rounds": 2, "leaves": 8, "rows_swept": 450, "rows_full": 500}
    assert _read(_table(one, two)) == pytest.approx(100 * 700 / 1500)


def test_none_without_the_attributes():
    assert _read(_table({"rounds": 4, "leaves": 8})) is None
    assert _read(_table()) is None
    assert _read(_table({"rows_swept": 0, "rows_full": 0})) is None


def test_none_on_the_recorded_fit_trace():
    """The recorded v5e trace comes from a program whose init span has no
    row counts: the metric is absent there, not zero."""
    table = sr.table_of(os.path.join(DATA, "tiny_fit.xplane.pb.gz"),
                        ["fit"])
    assert any(s.name == "kmeans.init" for s in table.spans)
    assert _read(table) is None
