"""Device-idle milliseconds per fit inside the benchmark's ``fit`` span
that neither the init nor the bounded iteration owns: ``kmeans.validate``,
``kmeans.fit``'s own time and time in no program span (bench/
span_reduce.py). With init_idle_ms.fit and iterate_idle_ms.fit it sums
to idle_share.fit of the fit span's length."""
from bench import span_reduce


def read(ctx):
    t = span_reduce.of(ctx)
    return None if t is None else t.idle_ms_per_unit(None)
