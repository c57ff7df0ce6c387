"""Blocking device-to-host reads per fit: the ``host_reads`` attribute of
the program's ``kmeans.fit`` span, which carries that fit's count of
``OpCounter.host_reads`` (bench/span_reduce.py)."""
from bench import span_reduce


def read(ctx):
    t = span_reduce.of(ctx)
    if t is None:
        return None
    reads = [s.stats["host_reads"] for s in t.spans
             if s.name == "kmeans.fit" and "host_reads" in s.stats]
    return sum(reads) / len(reads) if reads else None
