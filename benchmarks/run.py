"""Benchmark entry point — one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--fast] [--smoke] [--perf-out P]``
Prints CSV blocks (name,value columns per table) plus summary lines, and
writes a machine-readable BENCH_perf.json (per-section wall-clock + each
section's summary payload + the run's counted-op totals) so future PRs can
compare against this baseline.

``--smoke`` runs every section (plus the standalone assign bench) at tiny
shapes with all BENCH_*.json outputs redirected to a temp directory, then
asserts each file exists and keeps its schema — the bit-rot canary the
full test suite invokes (tests/test_benchmarks_smoke.py). It never touches
the committed acceptance baselines.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

# required top-level keys per benchmark artifact — the smoke-mode schema
# contract; extend when a bench grows a new output file
BENCH_SCHEMAS = {
    "BENCH_assign.json": ("backend", "interpret_mode", "repeats", "results"),
    "BENCH_init.json": ("fast", "runs", "summary"),
    "BENCH_dist.json": ("fast", "runs", "summary"),
    "BENCH_iter.json": ("fast", "runs", "summary"),
    "BENCH_predict.json": ("fast", "runs", "summary"),
    "BENCH_ft.json": ("fast", "runs", "summary"),
    "BENCH_serve.json": ("fast", "runs", "summary"),
    "BENCH_quant.json": ("fast", "runs", "summary"),
    "BENCH_drift.json": ("fast", "runs", "summary"),
    "BENCH_perf.json": ("fast", "sections", "summary_ok", "total_wall_s"),
    "k2lint_report.json": ("schema", "version", "passes", "counts",
                           "findings", "ok"),
}


def _k2lint_section(out_path: str):
    """Run the k2lint static analyzer end to end and validate the report
    it writes — the smoke-mode guarantee that the CI lint tier's tooling
    itself has not rotted (gating happens in scripts/lint.sh)."""
    from repro.analysis import cli, report as _rep
    rc = cli.run(out=out_path, quiet=True)
    if not os.path.isabs(out_path):      # cli.run writes repo-root-relative
        out_path = os.path.join(cli._repo_root(), out_path)
    with open(out_path) as fh:
        rep = json.load(fh)
    _rep.validate_report(rep)
    print(f"# k2lint summary: exit={rc} counts={rep['counts']}")
    return {"exit": rc, "counts": rep["counts"], "ok": rep["ok"]}


def _jsonable(v):
    """Best-effort coercion of section return values for the perf report.
    Numpy scalars become numbers (not strings) so the baselines stay
    machine-comparable."""
    try:
        json.dumps(v)
        return v
    except TypeError:
        if isinstance(v, dict):
            return {str(k): _jsonable(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return [_jsonable(x) for x in v]
        if hasattr(v, "item"):
            try:
                return _jsonable(v.item())
            except (TypeError, ValueError):
                pass
        return str(v)


def _sections(args, outdir=None):
    """The section list; ``outdir`` (smoke mode) redirects every artifact
    and shrinks every shape to schema-check scale."""
    from . import (assign_bench, complexity, convergence_curves, dist_bench,
                   drift_bench, ft_bench, init_bench, iter_bench,
                   predict_bench, quant_bench, roofline, serve_bench,
                   table4_init, table5_speedup)

    if outdir is not None:
        out = lambda name: os.path.join(outdir, name)      # noqa: E731
        return [
            ("table2_complexity",
             "Table 2 (smoke): per-iteration complexity",
             lambda: complexity.run(k=20, kn=5, max_iters=3)),
            ("assign",
             "Assign kernel (smoke) -> BENCH_assign.json",
             lambda: assign_bench.run(fast=True, repeats=1,
                                      out=out("BENCH_assign.json"))),
            ("init",
             "Init (smoke) -> BENCH_init.json",
             lambda: init_bench.run(fast=True, out=out("BENCH_init.json"),
                                    n=1024, d=16, true_k=32,
                                    grid=((16, (0,)),))),
            ("table4_init",
             "Table 4/7 (smoke)",
             lambda: table4_init.run(max_iters=2, datasets=("usps",),
                                     ks=(8,), seeds=(0,))),
            ("table5_speedup_1pct",
             "Table 5 (smoke)",
             lambda: table5_speedup.run(eps=0.01, max_iters=3,
                                        datasets=("usps",), ks=(8,),
                                        seeds=(0,))),
            ("distributed",
             "Distributed (smoke) -> BENCH_dist.json",
             lambda: dist_bench.run(fast=True, out=out("BENCH_dist.json"),
                                    shape=(1024, 16, 16, 6, 6))),
            ("iter",
             "Iteration residency (smoke) -> BENCH_iter.json",
             lambda: iter_bench.run(fast=True, out=out("BENCH_iter.json"),
                                    n=1024, d=16, k=16, kn=8, iters=8,
                                    regroup_every=4)),
            ("predict",
             "Predict (smoke) -> BENCH_predict.json",
             lambda: predict_bench.run(fast=True,
                                       out=out("BENCH_predict.json"),
                                       n=2048, d=16, k=32, kn=8,
                                       n_queries=512, fit_iters=4)),
            ("ft",
             "Fault tolerance (smoke) -> BENCH_ft.json",
             lambda: ft_bench.run(fast=True, out=out("BENCH_ft.json"),
                                  shape=(2048, 16, 32, 8, 10))),
            ("serve",
             "Serving plane (smoke) -> BENCH_serve.json",
             lambda: serve_bench.run(fast=True,
                                     out=out("BENCH_serve.json"),
                                     n=2048, d=16, k=32, kn=8,
                                     n_queries=512, fit_iters=4,
                                     horizon=0.01, rows_per_request=32,
                                     ladder=(32, 64, 128),
                                     fracs=(0.25, 2.0), pf_every=10)),
            ("quant",
             "Quantized scan (smoke) -> BENCH_quant.json",
             lambda: quant_bench.run(fast=True,
                                     out=out("BENCH_quant.json"),
                                     n=2048, d=16, k=32, kn=8,
                                     n_queries=512, fit_iters=4)),
            ("drift",
             "Drift robustness (smoke) -> BENCH_drift.json",
             lambda: drift_bench.run(fast=True,
                                     out=out("BENCH_drift.json"),
                                     shape=(128, 8, 16, 8, 8, 4, 2, 3))),
            ("fig23_convergence",
             "Fig 2/3 (smoke)",
             lambda: convergence_curves.run(k=8, max_iters=3)),
            ("k2lint",
             "k2lint static analysis (smoke) -> k2lint_report.json",
             lambda: _k2lint_section(out("k2lint_report.json"))),
            ("roofline",
             "Roofline (from dry-run artifacts, if present)",
             lambda: roofline.run()),
        ]

    return [
        ("table2_complexity",
         "Table 2: per-iteration complexity (counted ops vs analytic)",
         lambda: complexity.run(max_iters=12 if args.fast else 25)),
        ("init",
         "Init: host-loop GDI vs device GDI vs k-means++ "
         "(-> BENCH_init.json)",
         lambda: init_bench.run(fast=args.fast)),
        ("table4_init",
         "Table 4/7: initialization comparison (random / ++ / GDI)",
         lambda: table4_init.run(max_iters=20 if args.fast else 40)),
        ("table5_speedup_1pct",
         "Table 5 (1% target): algorithmic speedup over Lloyd++",
         lambda: table5_speedup.run(
             eps=0.01, max_iters=25 if args.fast else 40,
             datasets=("mnist50", "usps") if args.fast else None)),
        ("table6_speedup_0pct",
         "Table 6 (0% target): speedup at exact Lloyd++ energy",
         lambda: table5_speedup.run(eps=0.0,
                                    max_iters=25 if args.fast else 40,
                                    datasets=("mnist50", "usps"))),
        ("distributed",
         "Distributed: bounded engine step vs legacy sharded step "
         "(4-device debug mesh -> BENCH_dist.json)",
         lambda: dist_bench.run(fast=args.fast)),
        ("iter",
         "Iteration residency: rebuild vs resident grouped layout "
         "(-> BENCH_iter.json)",
         lambda: iter_bench.run(fast=args.fast)),
        ("predict",
         "Predict: bounded route vs brute-force assignment "
         "(-> BENCH_predict.json)",
         lambda: predict_bench.run(fast=args.fast)),
        ("ft",
         "Fault tolerance: chaos vs fault-free self-healing "
         "(-> BENCH_ft.json)",
         lambda: ft_bench.run(fast=args.fast)),
        ("serve",
         "Serving plane: latency/recall vs offered QPS under overload "
         "(-> BENCH_serve.json)",
         lambda: serve_bench.run(fast=args.fast)),
        ("quant",
         "Quantized scan, exact re-rank: int8 vs f32 scan traffic "
         "(-> BENCH_quant.json)",
         lambda: quant_bench.run(fast=args.fast)),
        ("drift",
         "Drift robustness: windowed streaming vs periodic re-fit "
         "(-> BENCH_drift.json)",
         lambda: drift_bench.run(fast=args.fast)),
        ("fig23_convergence",
         "Fig 2/3: convergence curves (energy vs counted ops)",
         lambda: convergence_curves.run(max_iters=15 if args.fast else 30)),
        ("k2lint",
         "k2lint static analysis (-> k2lint_report.json)",
         lambda: _k2lint_section("k2lint_report.json")),
        ("roofline",
         "Roofline (from dry-run artifacts, if present)",
         lambda: roofline.run()),
    ]


def _check_schemas(outdir: str) -> list[str]:
    """Assert every redirected BENCH artifact exists with its schema keys
    (BENCH_perf.json is validated by the caller after it is written)."""
    problems = []
    for name, keys in BENCH_SCHEMAS.items():
        if name == "BENCH_perf.json":
            continue
        path = os.path.join(outdir, name)
        if not os.path.exists(path):
            problems.append(f"{name}: not written")
            continue
        try:
            payload = json.load(open(path))
        except json.JSONDecodeError as e:
            problems.append(f"{name}: invalid json ({e})")
            continue
        missing = [k for k in keys if k not in payload]
        if missing:
            problems.append(f"{name}: missing keys {missing}")
    return problems


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller grids (CI mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes; assert every section runs and every "
                         "BENCH_*.json keeps its schema (temp outputs)")
    ap.add_argument("--perf-out", default="BENCH_perf.json",
                    help="machine-readable per-section report path")
    args, _ = ap.parse_known_args()

    from .common import use_compile_cache
    print(f"# compilation cache: {use_compile_cache()}")
    outdir = None
    perf_out = args.perf_out
    if args.smoke:
        outdir = tempfile.mkdtemp(prefix="bench-smoke-")
        perf_out = os.path.join(outdir, "BENCH_perf.json")
        print(f"# smoke outputs -> {outdir}")

    sections = _sections(args, outdir)
    report = {"fast": args.fast, "sections": []}
    wall0 = time.time()
    ran = []
    for key, title, fn in sections:
        t0 = time.time()
        print(f"== {title} ==")
        result = fn()
        wall = time.time() - t0
        print(f"# section time {wall:.1f}s\n")
        ran.append(key)
        report["sections"].append({
            "section": key,
            "wall_s": round(wall, 3),
            "summary": _jsonable(result),
        })
    report["summary_ok"] = all(s["summary"] is not None or s["section"]
                               == "roofline"
                               for s in report["sections"])
    report["total_wall_s"] = round(time.time() - wall0, 3)

    with open(perf_out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {perf_out}")

    if args.smoke:
        problems = _check_schemas(outdir)
        payload = json.load(open(perf_out))
        missing = [k for k in BENCH_SCHEMAS["BENCH_perf.json"]
                   if k not in payload]
        if missing:
            problems.append(f"BENCH_perf.json: missing keys {missing}")
        expected = [k for k, _, _ in sections]
        if ran != expected:
            problems.append(f"sections ran {ran} != expected {expected}")
        if problems:
            raise SystemExit("SMOKE FAILED: " + "; ".join(problems))
        print(f"SMOKE OK: {len(ran)} sections, "
              f"{len(BENCH_SCHEMAS)} schemas intact")


if __name__ == "__main__":
    main()
