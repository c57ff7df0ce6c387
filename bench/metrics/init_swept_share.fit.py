"""Share, in %, of the full grouped layout's rows that the fit's GDI
rounds swept: 100 * rows_swept / rows_full, summed over the
``kmeans.init`` spans that carry both attributes (each round runs at the
smallest static capacity that holds its flagged leaves' rows;
bench/span_reduce.py)."""
from bench import span_reduce


def read(ctx):
    t = span_reduce.of(ctx)
    if t is None:
        return None
    inits = [s.stats for s in t.spans if s.name == "kmeans.init"
             and "rows_swept" in s.stats and "rows_full" in s.stats]
    full = sum(s["rows_full"] for s in inits)
    return 100.0 * sum(s["rows_swept"] for s in inits) / full if full \
        else None
