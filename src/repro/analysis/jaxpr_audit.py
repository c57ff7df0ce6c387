"""Pass 1 — the hot-path auditor (DESIGN.md §15.3, rules K2L10x).

Each registered entry point (``analysis.registry.audit_entries``) is
abstract-evaluated with ``jax.make_jaxpr`` — nothing executes — and the
closed jaxpr is walked recursively (through ``pjit``, ``scan``,
``while``, ``cond``, ``shard_map``, ``custom_*`` and ``pallas_call``
sub-jaxprs) checking:

``K2L100``  the entry failed to trace at all (a registry rot guard —
            a renamed entry or changed signature must fail loudly, not
            silently shrink coverage).
``K2L101``  host callbacks / infeed / outfeed anywhere in a hot entry —
            the §3 deferred-host-read contract. Every registered entry
            IS a hot loop body (drivers call it every iteration), so a
            callback anywhere in it is a per-iteration host sync; the
            finding notes when it is additionally nested in scan/while.
``K2L102``  dtype discipline: any f64 value or convert to f64 (the
            engine is an f32 design; f64 halves MXU throughput and
            doubles every byte lane), and — in ``int8_region`` entries —
            more int8→float dequantizations than the entry's
            ``sanctioned_dequants`` (§13 sanctions exactly the residual
            -norm pass; an extra dequant means quantized rows leaked
            into f32 math before the re-rank).
``K2L103``  recompile hazards: the entry is traced twice from identical
            builds — any difference in the jaxprs means a Python-side
            value (RNG, clock, id()) leaked into the trace, which under
            ``jit`` shows up as silent constant-staleness or retrace
            churn. Entries with a ``build_alt`` are additionally traced
            at a second abstract signature; a trace *failure* there
            means a dimension leaked as a Python scalar (shape
            specialization beyond the declared static args).
``K2L104``  collective placement: collectives in ``collective_free``
            entries (single-device hot paths must not hide a psum), and
            collectives nested inside scan/while/cond in sharded
            entries — the §7.1 hierarchical update psums sit at the top
            level of the shard_map body, unconditionally.
"""
from __future__ import annotations

import os

from .report import Finding
from .registry import EntryPoint, audit_entries

HOST_PRIM_EXACT = frozenset({"infeed", "outfeed", "debug_print",
                             "outside_call"})
COLLECTIVES = frozenset({"psum", "psum2", "psum_invariant", "pmax", "pmin",
                         "pmean", "all_gather", "all_gather_invariant",
                         "all_to_all", "ppermute", "pgather",
                         "reduce_scatter", "psum_scatter", "pbroadcast"})
LOOP_PRIMS = frozenset({"scan", "while"})
REGION_PRIMS = frozenset({"scan", "while", "cond"})


def _is_host_prim(name: str) -> bool:
    return name in HOST_PRIM_EXACT or "callback" in name


def _subjaxprs(params):
    """Every Jaxpr/ClosedJaxpr reachable from an eqn's params (pjit's
    ``jaxpr``, scan's ``jaxpr``, while's ``cond_jaxpr``/``body_jaxpr``,
    cond's ``branches``, pallas_call's kernel jaxpr, ...)."""
    import jax.extend.core as core
    stack = list(params.values())
    while stack:
        v = stack.pop()
        if isinstance(v, core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            stack.extend(v)
        elif isinstance(v, dict):
            stack.extend(v.values())


def walk_eqns(jaxpr, path=()):
    """Yield ``(eqn, path)`` for every equation, ``path`` being the tuple
    of enclosing primitive names (innermost last)."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        sub_path = path + (eqn.primitive.name,)
        for sub in _subjaxprs(eqn.params):
            yield from walk_eqns(sub, sub_path)


def _eqn_location(eqn, repo_root):
    """Best-effort (file, line) of the user code that emitted an eqn."""
    try:
        import jax._src.source_info_util as siu
        frame = siu.user_frame(eqn.source_info)
        if frame is not None:
            fname = frame.file_name
            if repo_root and fname.startswith(repo_root):
                fname = os.path.relpath(fname, repo_root)
            line = getattr(frame, "start_line", 0) or \
                getattr(frame, "line_num", 0) or 0
            return fname, int(line)
    except Exception:
        pass
    return None, 0


def _trace(entry: EntryPoint, alt: bool = False):
    import jax
    fn, args = (entry.build_alt if alt else entry.build)()
    return jax.make_jaxpr(fn)(*args)


def _is_f64(dtype) -> bool:
    import numpy as np
    return dtype == np.float64


def audit_entry(entry: EntryPoint, repo_root: str = "") -> list[Finding]:
    import numpy as np
    findings: list[Finding] = []

    def add(rule, site, message, file=None, line=0, severity="error"):
        findings.append(Finding(rule=rule, severity=severity,
                                file=file or entry.file, line=line,
                                entry=entry.name, site=site,
                                message=message))

    try:
        closed = _trace(entry)
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        add("K2L100", "trace",
            f"entry failed to trace: {type(e).__name__}: {e}")
        return findings

    dequants = 0
    for eqn, path in walk_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        file, line = _eqn_location(eqn, repo_root)
        in_loop = any(p in LOOP_PRIMS for p in path)
        in_region = any(p in REGION_PRIMS for p in path)

        # K2L101 — deferred-host-read contract (§3)
        if _is_host_prim(prim):
            where = (f"nested inside {'/'.join(path)}" if in_loop
                     else "in the hot entry body")
            add("K2L101", f"{prim}@{'/'.join(path)}",
                f"host callback primitive '{prim}' {where}: the §3 "
                "contract defers all host reads to monitor_every "
                "boundaries", file=file, line=line)

        # K2L102 — dtype discipline
        new_dtype = eqn.params.get("new_dtype")
        if new_dtype is not None and _is_f64(np.dtype(new_dtype)):
            add("K2L102", f"convert-f64@{'/'.join(path)}",
                "convert_element_type to float64 in an f32 engine",
                file=file, line=line)
        else:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None and _is_f64(dt):
                    add("K2L102", f"f64:{prim}@{'/'.join(path)}",
                        f"primitive '{prim}' materializes a float64 "
                        "value in an f32 engine", file=file, line=line)
                    break
        if entry.int8_region and prim == "convert_element_type":
            src_dt = getattr(getattr(eqn.invars[0], "aval", None),
                             "dtype", None)
            if (src_dt == np.int8
                    and np.issubdtype(np.dtype(new_dtype), np.floating)):
                dequants += 1

        # K2L104 — collective placement
        if prim in COLLECTIVES:
            if entry.collective_free:
                add("K2L104", f"{prim}@{'/'.join(path)}",
                    f"collective '{prim}' in a collective-free entry",
                    file=file, line=line)
            elif in_region:
                add("K2L104", f"{prim}-nested@{'/'.join(path)}",
                    f"collective '{prim}' nested inside "
                    f"{'/'.join(path)}: §7.1 hierarchical-update "
                    "collectives must sit at the top level of the "
                    "shard_map body", file=file, line=line)

    if entry.int8_region and dequants > entry.sanctioned_dequants:
        add("K2L102", "dequant-budget",
            f"{dequants} int8→float dequantizations, "
            f"{entry.sanctioned_dequants} sanctioned (§13: only the "
            "residual-norm pass may dequantize before the exact "
            "re-rank)")

    # K2L103 — recompile hazards
    try:
        closed2 = _trace(entry)
        if str(closed.jaxpr) != str(closed2.jaxpr):
            add("K2L103", "retrace",
                "two traces from identical builds differ: a Python-side "
                "value leaks into the trace (recompile/staleness hazard)")
    except Exception as e:  # noqa: BLE001
        add("K2L103", "retrace",
            f"re-trace failed: {type(e).__name__}: {e}")
    if entry.build_alt is not None:
        try:
            _trace(entry, alt=True)
        except Exception as e:  # noqa: BLE001
            add("K2L103", "alt-signature",
                "entry does not trace at a second abstract signature "
                f"(leaked Python-scalar dimension?): "
                f"{type(e).__name__}: {e}")

    return findings


def run(entries: list[EntryPoint] | None = None,
        repo_root: str = "") -> tuple[list[Finding], dict]:
    entries = audit_entries() if entries is None else entries
    findings: list[Finding] = []
    for entry in entries:
        findings.extend(audit_entry(entry, repo_root))
    stats = {"entries": len(entries),
             "findings": len(findings)}
    return findings, stats
