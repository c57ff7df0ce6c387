"""The benchmark harness: one cell, one seed, one run.

Everything that belongs to one cell is found by name. ``BENCHMARK.json``
names the cell's configuration and traffic mix; the configuration is the
file that entry names, the traffic mix is ``traffic/<traffic>.json`` (its
``driver`` key names ``drivers/<driver>.py``), the cell's correctness
limits are ``workloads/<cell>.json``, each per-layer metric is read by
``metrics/<metric>.py``, and each program group of the trace is
``program_groups/<group>.json``. Adding a cell, a configuration, a
traffic mix, a metric or a program group adds files and entries; no file
here changes.

A run: the driver's ``setup`` (data made on the device, the program
built and warmed on every shape the window uses) is ``setup_s``. Then
either the measured window, a closed loop of the driver's units that
covers whole passes of the session's ``pass_units`` units (every unit
that starts inside ``seconds`` counts, the last finishes, and units go
on until the count is a whole number of passes), or, with ``trace``,
the traffic's ``trace_units`` units under the profiler. Then the
device's peak memory is read, the program's state is freed, and the
units' outputs are compared with the plain reference.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict                 # number -> {"max": v} or {"min": v}
    end_to_end: list[dict]       # the manifest's metrics this cell reports
    per_layer: list[dict]


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, fallback: bool) -> bool:
    return cell in metric["workloads"] if "workloads" in metric \
        else fallback


def load_cell(name: str, manifest: dict | None = None,
              root: str = ROOT) -> Cell:
    """The cell ``name`` with everything the manifest names for it."""
    manifest = manifest or load_manifest(root)
    entry = {w["name"]: w for w in manifest["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    config = {c["name"]: c for c in manifest["configs"]}[entry["config"]]
    e2e = [m for m in manifest["end_to_end"]
           if _reports(m, name, fallback=True)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if _reports(m, name, fallback=m["moves"] in reported)]
    return Cell(name=name, chips=entry["chips"],
                config=_json(root, config["file"]),
                traffic=_json(BENCH, "traffic", entry["traffic"] + ".json"),
                limits=_json(BENCH, "workloads", name + ".json")["limits"],
                end_to_end=e2e, per_layer=per_layer)


def load_module(path: str):
    """Import a file by path (metric files carry dots in their names)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_of(cell: Cell):
    return load_module(os.path.join(BENCH, "drivers",
                                    cell.traffic["driver"] + ".py"))


def use_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``. Every program is
    cached, however fast it compiled, so that a warm set-up compiles
    nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts the programs built while ``active``: each is either
    compiled or loaded from the persistent cache (``loads``)."""

    def __init__(self):
        import jax
        self.active = False
        self.built = 0
        self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_built)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_built(self, event: str, _secs: float, **_kw) -> None:
        if self.active and event == BACKEND_COMPILE:
            self.built += 1

    def _on_event(self, event: str, **_kw) -> None:
        if self.active and event == CACHE_HIT:
            self.loads += 1

    @property
    def compiles(self) -> int:
        return self.built - self.loads


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no statistics)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def window(session, seconds: float, counter: CompileCounter):
    """The closed loop: units back to back in whole passes of the
    session's ``pass_units`` (1 where it declares none). Every unit that
    starts before ``seconds`` have passed counts and runs to its end, and
    units go on starting until their count is a whole number of passes,
    at least one. Returns (outputs, elapsed seconds from the first start
    to the last end, units failed)."""
    per_pass = getattr(session, "pass_units", 1)
    outputs, failed = [], 0
    counter.active = True
    t0 = time.perf_counter()
    try:
        while not outputs or len(outputs) % per_pass or \
                time.perf_counter() - t0 < seconds:
            try:
                outputs.append(session.unit(len(outputs)))
            except Exception as e:          # a failed unit ends the window
                log(f"unit {len(outputs)} failed: {e!r}")
                failed += 1
                break
    finally:
        elapsed = time.perf_counter() - t0
        counter.active = False
    return outputs, elapsed, failed


def reduce_trace(path: str, span: str):
    """The xplane file ``path``, parsed once, clipped to the benchmark's
    spans named ``span``: (the device summary of ``trace_reduce``, the
    program span table of ``span_reduce``)."""
    from bench import span_reduce, trace_reduce
    pd = trace_reduce.profile_data(path)
    tr = trace_reduce.load(pd, [span])
    groups = trace_reduce.load_groups(os.path.join(BENCH, "program_groups"))
    summary = trace_reduce.reduce(tr, [span], groups)
    table = span_reduce.reduce_spans(
        tr, span_reduce.load_spans(pd, [span]), [span])
    return summary, table


def traced(session, cell: Cell, counter: CompileCounter, trace_dir: str):
    """The traffic's ``trace_units`` units, each in a span named by the
    driver, under the profiler. Returns (outputs, trace summary, program
    span table, units failed)."""
    import jax
    from bench import trace_reduce
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # Python calls are not traced
    outputs, failed = [], 0
    counter.active = True
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for i in range(cell.traffic["trace_units"]):
            try:
                with jax.profiler.TraceAnnotation(session.span):
                    outputs.append(session.unit(i))
            except Exception as e:
                log(f"traced unit {i} failed: {e!r}")
                failed += 1
                break
    finally:
        jax.profiler.stop_trace()
        counter.active = False
    t0 = time.perf_counter()
    path = trace_reduce.find_xplane(trace_dir)
    summary, table = reduce_trace(path, session.span)
    log(f"trace of {len(summary.spans)} unit(s), {os.path.getsize(path)} "
        f"bytes, reduced in {time.perf_counter() - t0:.1f} s")
    for line in table.lines():
        log(line)
    return outputs, summary, table, failed


def judge(numbers: dict, limits: dict) -> dict:
    """Each compared number beside its limit, with its verdict. A number
    without a limit, or a limit without a number, fails."""
    out = {}
    for name in sorted(set(numbers) | set(limits)):
        value, lim = numbers.get(name), limits.get(name, {})
        rule = "max" if "max" in lim else "min"
        bound = lim.get(rule)
        ok = value is not None and bound is not None and \
            math.isfinite(value) and \
            (value <= bound if rule == "max" else value >= bound)
        out[name] = {"value": value, "limit": bound, "ok": ok, "rule": rule}
    return out


def _finite(v):
    """A number as JSON can carry it: None for a missing or non-finite
    reading."""
    return v if v is not None and math.isfinite(v) else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices) -> dict:
    """One run of one cell on ``devices`` (already checked by the
    caller). Returns the result object; ``checks`` comes last."""
    counter = CompileCounter()
    driver = driver_of(cell)
    session = driver.setup(cell, seed)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")
    summary = table = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
        outputs, summary, table, failed = traced(session, cell, counter,
                                                 trace_dir)
        elapsed = None
    else:
        outputs, elapsed, failed = window(session, seconds, counter)
        log(f"window: {len(outputs)} units in {elapsed:.3f} s")
    log(f"programs compiled inside the window: {counter.compiles}; "
        f"loaded from the persistent cache: {counter.loads}")
    peak = memory_peak(devices)
    session.release()
    t_ref = time.perf_counter()
    numbers = session.compare(outputs) if outputs else {}
    log(f"reference and comparison {time.perf_counter() - t_ref:.1f} s")
    checks = judge(numbers, cell.limits)
    attempted = len(outputs) + failed
    correct = failed == 0 and attempted > 0 and \
        all(c["ok"] for c in checks.values())

    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        from bench import span_reduce
        ctx = {"summary": summary, span_reduce.CONTEXT_KEY: table,
               "cell": cell, "session": session,
               "device_kind": devices[0].device_kind}
        for m in cell.per_layer:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        values = session.end_to_end(outputs, elapsed) if outputs else {}
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["compiles_in_window"] = counter.compiles
    result["cache_loads_in_window"] = counter.loads
    result["checks"] = {n: {"value": _finite(c["value"]),
                            "limit": c["limit"]}
                        for n, c in checks.items()}
    for name, c in checks.items():
        op = "<=" if c["rule"] == "max" else ">="
        log(f"check {name} = {c['value']!r} {op} {c['limit']!r}: "
            f"{'ok' if c['ok'] else 'FAIL'}")
    return result
