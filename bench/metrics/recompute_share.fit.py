"""Share, in %, of the bounded iteration's row visits in which a row
recomputed its kn candidate distances because its bounds did not spare
it: 100 * rows_recomputed / (n * iterations), from each ``kmeans.iterate``
span and the ``n`` of the ``kmeans.fit`` span around it
(bench/span_reduce.py)."""
from bench import span_reduce


def read(ctx):
    t = span_reduce.of(ctx)
    if t is None:
        return None
    fits = [s for s in t.spans if s.name == "kmeans.fit" and "n" in s.stats]
    done = visits = 0
    for it in t.spans:
        if it.name != "kmeans.iterate" or "rows_recomputed" not in it.stats:
            continue
        n = next((f.stats["n"] for f in fits
                  if f.start <= it.start and it.end <= f.end), None)
        if n is None:
            continue
        done += it.stats["rows_recomputed"]
        visits += n * it.stats["iterations"]
    return 100.0 * done / visits if visits else None
