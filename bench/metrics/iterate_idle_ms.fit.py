"""Device-idle milliseconds per fit whose innermost program span is
``kmeans.exact_start``, ``kmeans.iterate`` or one of its children
(``build``, ``step``, ``flush``, ``final``): the bounded iteration's
dispatches, re-lowerings and monitor reads (bench/span_reduce.py)."""
from bench import span_reduce


def read(ctx):
    t = span_reduce.of(ctx)
    return None if t is None else t.idle_ms_per_unit("bounded iteration")
