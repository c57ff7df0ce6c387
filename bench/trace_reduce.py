"""Reduce a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
:class:`Trace`: the operations each device ran (with the XLA module that
ran them) and the host events of the thread that opened the benchmark's
own ``TraceAnnotation`` spans. ``reduce`` then clips everything to those
spans and gives the device's busy time (the union of its operation
intervals, averaged over the devices), its time per program group (the
name-to-layer maps of ``program_groups/<group>.json``; an instant in
which ops of several groups ran counts once, for the first of them), the
operations that took most time and the longest idle gaps, each named by
the innermost host event open across it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re

# device lines of a TPU plane, and the statistic that names an op's module
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MODULE_STAT = "hlo_module"
# host events that say nothing about what the host was doing
_HOST_NOISE = re.compile(r"^(\$|ThreadpoolListener|Wait for)")


@dataclasses.dataclass
class Op:
    name: str
    module: str
    start: int          # ns, on the trace's common clock
    end: int


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Op]]             # device plane -> ops, by start
    host: list[tuple[str, int, int]]     # (name, start, end), by start


@dataclasses.dataclass
class Summary:
    spans: list[tuple[str, int, int]]    # the benchmark's spans reduced
    window_s: float                      # their total length
    busy_s: float                        # device busy inside them
    group_s: dict[str, float]            # device time per program group
    op_s: dict[str, float]               # device time per "module:op"
    gaps: list[tuple[str, float]]        # idle gaps, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _module_of(ev, modules, starts):
    """The op's module: its own statistic, else the module event that
    spans it on the same device."""
    stats = dict(ev.stats)
    if MODULE_STAT in stats:
        return str(stats[MODULE_STAT])
    mid = ev.start_ns + ev.duration_ns / 2
    i = bisect.bisect_right(starts, mid) - 1
    if i >= 0 and modules[i][2] >= mid:
        return modules[i][0]
    return "?"


def op_name(hlo: str) -> str:
    """An op's name and result type from its HLO text:
    ``%fusion.7 = f32[13631488]{0:T(1024)} fusion(...)`` reads
    ``fusion.7 f32[13631488]``."""
    lhs, _, rhs = hlo.partition(" = ")
    if not rhs:
        return hlo[:80]
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)          # layouts
    typ = rhs[:rhs.index(")") + 1] if rhs.startswith("(") \
        else rhs.split(" ", 1)[0]
    return f"{lhs.lstrip('%')} {typ}"


def profile_data(path: str):
    """The parsed ``jax.profiler.ProfileData`` of one xplane file
    (gzipped where the name ends in ``.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(source, span_names) -> Trace:
    """Read one xplane file: its path, or the ``ProfileData`` that
    :func:`profile_data` parsed from it. Device planes are those named
    ``/device:`` with an ``XLA Ops`` line; the host events kept are those
    of the thread that holds any of ``span_names``."""
    pd = profile_data(source) if isinstance(source, str) else source
    ops: dict[str, list[Op]] = {}
    host: list[tuple[str, int, int]] = []
    span_names = set(span_names)
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            modules = []
            if MODULES_LINE in lines:
                modules = sorted(((re.sub(r"\(\d+\)$", "", e.name),
                                   e.start_ns, e.start_ns + e.duration_ns)
                                  for e in lines[MODULES_LINE].events),
                                 key=lambda m: m[1])
            starts = [m[1] for m in modules]
            ops[plane.name] = sorted(
                (Op(op_name(e.name), _module_of(e, modules, starts),
                    int(e.start_ns),
                    int(e.start_ns + e.duration_ns))
                 for e in lines[OPS_LINE].events), key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                       for e in line.events]
                if any(n in span_names for n, _, _ in evs):
                    host.extend(evs)
    host.sort(key=lambda h: h[1])
    return Trace(ops, host)


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, spans):
    """Parts of [s, e) inside the spans."""
    for a, b in spans:
        lo, hi = max(s, a), min(e, b)
        if hi > lo:
            yield lo, hi


def _host_name(t, host):
    """The innermost informative host event open at time t."""
    best = None
    for name, s, e in host:
        if s > t:
            break
        if e >= t and not _HOST_NOISE.match(name) and \
                (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "?"


def group_of(module: str, groups: dict[str, list[str]]) -> str:
    """The first group whose patterns match the module name, else
    "other"."""
    for group, patterns in groups.items():
        if any(re.search(p, module) for p in patterns):
            return group
    return "other"


def reduce(trace: Trace, span_names, groups: dict[str, list[str]],
           top_gaps: int = 10) -> Summary:
    """Clip the trace to the benchmark's spans named ``span_names`` and
    reduce it (see the module docstring)."""
    span_names = set(span_names)
    spans = [(n, s, e) for n, s, e in trace.host if n in span_names]
    if not spans:
        raise ValueError(f"no span named {sorted(span_names)} in the trace")
    if not trace.ops:
        raise ValueError("no device operations in the trace")
    windows = _merge([(s, e) for _, s, e in spans])
    window_ns = sum(e - s for s, e in windows)
    order = list(groups) + ["other"]
    busy_ns, group_ns, op_ns, module_group = 0, {}, {}, {}
    gaps = []
    for plane, ops in sorted(trace.ops.items()):
        inside = []
        for op in ops:
            if op.module not in module_group:
                module_group[op.module] = order.index(
                    group_of(op.module, groups))
            for s, e in _clip(op.start, op.end, windows):
                inside.append((s, e, module_group[op.module]))
                key = f"{op.module}:{op.name}"
                op_ns[key] = op_ns.get(key, 0) + (e - s)
        busy = _merge([(s, e) for s, e, _ in inside])
        busy_ns += sum(e - s for s, e in busy)
        for g, ns in _split_by_group(inside, len(order)).items():
            group_ns[order[g]] = group_ns.get(order[g], 0) + ns
        if not gaps:            # the idle gaps of the first device
            gaps = _gaps(busy, windows)
    ndev = len(trace.ops)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top_gaps]
    named = [(_host_name((a + b) // 2, trace.host), (b - a) / 1e9)
             for a, b in longest]
    return Summary(spans=spans, window_s=window_ns / 1e9,
                   busy_s=busy_ns / ndev / 1e9,
                   group_s={g: v / ndev / 1e9 for g, v in group_ns.items()},
                   op_s={k: v / ndev / 1e9 for k, v in op_ns.items()},
                   gaps=named)


def _split_by_group(intervals, n_groups: int) -> dict[int, int]:
    """Nanoseconds per group index over (start, end, group) intervals,
    each instant counted once, for the lowest group index running then:
    the per-group times sum to the union of the intervals."""
    events = sorted([(s, 1, g) for s, _, g in intervals]
                    + [(e, -1, g) for _, e, g in intervals])
    running = [0] * n_groups
    out: dict[int, int] = {}
    last = None
    for t, step, g in events:
        if last is not None and t > last:
            top = next((i for i, c in enumerate(running) if c), None)
            if top is not None:
                out[top] = out.get(top, 0) + (t - last)
        running[g] += step
        last = t
    return out


def _gaps(busy, windows):
    """Idle intervals inside the windows, between busy intervals."""
    out = []
    for a, b in windows:
        t = a
        for s, e in busy:
            if e <= a or s >= b:
                continue
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if b > t:
            out.append((t, b))
    return out


def load_groups(directory: str) -> dict[str, list[str]]:
    """Every ``<group>.json`` in ``directory``: group -> its module name
    patterns, in the order of the file names."""
    out = {}
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            out[os.path.basename(path)[:-5]] = json.load(f)["patterns"]
    return out
