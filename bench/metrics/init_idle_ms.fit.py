"""Device-idle milliseconds per fit whose innermost program span is
``kmeans.init`` or one of its ``kmeans.init.round`` children: the GDI
rounds' dispatches and leaf-count reads (bench/span_reduce.py)."""
from bench import span_reduce


def read(ctx):
    t = span_reduce.of(ctx)
    return None if t is None else t.idle_ms_per_unit("init")
