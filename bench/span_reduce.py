"""Reduce the program's own spans in a profiler trace to per-layer numbers.

The program opens ``jax.profiler.TraceAnnotation`` spans named
``kmeans.*`` (``kmeans.fit``, ``kmeans.init.round``,
``kmeans.iterate.flush``, ...) on the thread that runs the fit, inside
the benchmark's own span; their attributes ride on them as statistics.
:func:`reduce_spans` gives, for each span name: how often it ran, its
wall time, the device's busy time inside it, and the device's idle time
that falls to it. Each idle nanosecond inside the benchmark's spans goes
to the innermost program span open at that instant, or to ``(none)``,
so the idle times of all names sum to the idle time that
``trace_reduce.reduce`` gives (its ``window_s - busy_s``). It also names
the first device's longest idle gaps by the span that holds most of
each.

The benchmark's metric readers share one :class:`SpanTable` per run
through :func:`of`: the harness reduces it from the same parse of the
trace as its device numbers, logs it and keeps it in the metric context
under ``"program_spans"``.
"""
from __future__ import annotations

import bisect
import dataclasses

from bench import trace_reduce

PREFIX = "kmeans."
NONE = "(none)"
CONTEXT_KEY = "program_spans"
# the span names whose idle time each layer owns (a name and its
# children); the rest of the fit's idle time is the entry layer's
LAYER_SPANS = {
    "init": ("kmeans.init",),
    "bounded iteration": ("kmeans.exact_start", "kmeans.iterate"),
}


@dataclasses.dataclass
class Span:
    name: str
    start: int          # ns, on the trace's common clock
    end: int
    stats: dict


@dataclasses.dataclass
class Row:
    count: int = 0
    wall_s: float = 0.0
    busy_s: float = 0.0     # device busy inside the span, children included
    idle_s: float = 0.0     # device idle whose innermost span this is


@dataclasses.dataclass
class SpanTable:
    windows: int                 # the benchmark's spans (units) reduced
    idle_s: float                # device idle inside them
    rows: dict[str, Row]         # span name (or NONE) -> its row
    spans: list[Span]            # the program spans, by start
    gaps: list[tuple[str, float]]  # longest idle gaps (span, s), longest first

    def idle_ms_per_unit(self, layer: str | None) -> float:
        """Idle ms per unit owned by ``layer`` of :data:`LAYER_SPANS`, or,
        with ``None``, by no layer there (the entry layer)."""
        owned = {n for n in self.rows if _layer_of(n) == layer}
        return 1e3 * sum(self.rows[n].idle_s for n in owned) / self.windows

    def lines(self) -> list[str]:
        out = [f"program spans over {self.windows} unit(s): name, count, "
               "wall ms, device busy ms, device idle ms (innermost)"]
        for name, r in sorted(self.rows.items(),
                              key=lambda kv: -kv[1].idle_s):
            out.append(f"  {name:24s} {r.count:6d} {1e3 * r.wall_s:12.3f} "
                       f"{1e3 * r.busy_s:12.3f} {1e3 * r.idle_s:10.3f}")
        out.append("longest idle gaps (ms) by program span: " + ", ".join(
            f"{name} {1e3 * sec:.3f}" for name, sec in self.gaps))
        return out


def _layer_of(name: str) -> str | None:
    for layer, roots in LAYER_SPANS.items():
        if any(name == r or name.startswith(r + ".") for r in roots):
            return layer
    return None


def load_spans(pd, bench_names) -> list[Span]:
    """The program spans, with their statistics, of the host thread that
    holds any of the benchmark's spans ``bench_names`` (the thread that
    ``trace_reduce.load`` keeps), from the ``ProfileData`` that
    ``trace_reduce.profile_data`` parsed."""
    bench_names = set(bench_names)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(e.name in bench_names for e in events):
                out.extend(Span(e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats))
                           for e in events if e.name.startswith(PREFIX))
    return sorted(out, key=lambda s: (s.start, -s.end))


def _innermost(spans: list[Span]):
    """Piecewise (start, end, name) of the innermost span open, ``NONE``
    where none is, from the first start to the last end."""
    events = sorted([(s.end, 0, i) for i, s in enumerate(spans)]
                    + [(s.start, 1, i) for i, s in enumerate(spans)])
    out, open_, last = [], [], None
    for t, is_start, i in events:
        if last is not None and t > last:
            out.append((last, t, spans[open_[-1]].name if open_ else NONE))
        if is_start:
            open_.append(i)
        else:
            open_.remove(i)
        last = t
    return out


def _attribute(gaps, segments) -> dict[str, int]:
    """Nanoseconds of the sorted, disjoint ``gaps`` per segment name;
    time outside every segment goes to ``NONE``."""
    out: dict[str, int] = {}
    j = 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        t, i = a, j
        while t < b:
            if i < len(segments) and segments[i][0] <= t:
                end, name = min(b, segments[i][1]), segments[i][2]
                i += 1
            else:
                end = min(b, segments[i][0]) if i < len(segments) else b
                name = NONE
            out[name] = out.get(name, 0) + (end - t)
            t = end
    return out


def _busy_until(busy):
    """t -> nanoseconds of the merged ``busy`` intervals before t."""
    starts = [s for s, _ in busy]
    cum = [0]
    for s, e in busy:
        cum.append(cum[-1] + e - s)

    def until(t: int) -> int:
        i = bisect.bisect_right(starts, t) - 1
        return 0 if i < 0 else cum[i] + min(t, busy[i][1]) - busy[i][0]
    return until


def reduce_spans(trace: trace_reduce.Trace, spans: list[Span],
                 bench_names, top_gaps: int = 10) -> SpanTable:
    """The per-name table of the program ``spans`` inside the benchmark's
    spans named ``bench_names`` (see the module docstring). Busy and idle
    time are averaged over the devices, as ``trace_reduce.reduce`` does."""
    bench_names = set(bench_names)
    windows = trace_reduce._merge([(s, e) for n, s, e in trace.host
                                   if n in bench_names])
    if not windows:
        raise ValueError(f"no span named {sorted(bench_names)} in the trace")
    if not trace.ops:
        raise ValueError("no device operations in the trace")
    spans = [s for s in spans
             if any(s.start < b and s.end > a for a, b in windows)]
    segments = _innermost(spans)
    ndev = len(trace.ops)
    idle_ns: dict[str, int] = {}
    busy_ns = [0] * len(spans)
    named = None
    for _, ops in sorted(trace.ops.items()):
        busy = trace_reduce._merge(
            [(s, e) for op in ops
             for s, e in trace_reduce._clip(op.start, op.end, windows)])
        until = _busy_until(busy)
        for i, sp in enumerate(spans):
            busy_ns[i] += until(sp.end) - until(sp.start)
        gaps = trace_reduce._gaps(busy, windows)
        for name, ns in _attribute(gaps, segments).items():
            idle_ns[name] = idle_ns.get(name, 0) + ns
        if named is None:
            longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top_gaps]
            named = [(max(_attribute([g], segments).items(),
                          key=lambda kv: kv[1])[0], (g[1] - g[0]) / 1e9)
                     for g in longest]
    rows: dict[str, Row] = {}
    for sp, b in zip(spans, busy_ns):
        r = rows.setdefault(sp.name, Row())
        r.count += 1
        r.wall_s += (sp.end - sp.start) / 1e9
        r.busy_s += b / ndev / 1e9
    for name, ns in idle_ns.items():
        rows.setdefault(name, Row()).idle_s = ns / ndev / 1e9
    return SpanTable(windows=sum(n in bench_names for n, _, _ in trace.host),
                     idle_s=sum(idle_ns.values()) / ndev / 1e9,
                     rows=rows, spans=spans, gaps=named)


def table_of(path: str, bench_names) -> SpanTable:
    """:func:`reduce_spans` of the xplane file ``path``, parsed once."""
    pd = trace_reduce.profile_data(path)
    trace = trace_reduce.load(pd, bench_names)
    return reduce_spans(trace, load_spans(pd, bench_names), bench_names)


def of(ctx: dict) -> SpanTable | None:
    """The run's span table, which the harness's traced run reduced from
    the trace it read once and put in the metric context under
    ``"program_spans"``. None where the trace holds no program span."""
    table = ctx.get(CONTEXT_KEY)
    return table if table is not None and table.spans else None
