"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files that the harness finds by that name."""
import json
import os
import re

import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = harness.BENCH
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


@pytest.fixture(scope="module")
def manifest():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    return harness.load_manifest()


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "bench/run.py"]
    assert manifest["paths"] == ["bench"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51


def test_check_budget_fits_with_24_cells(manifest):
    runs = 2 + 14 * 24
    total = runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_lines(manifest):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert LINE.match(entry[key]), (entry["name"], key)
    metrics = [m["name"] for m in manifest["end_to_end"]
               + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_configs(manifest):
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16
        assert c["name"] in used


def test_every_cell_finds_its_files(manifest):
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = harness.load_cell(w["name"], manifest)
        driver = cell.traffic["driver"]
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           driver + ".py"))
        mod = harness.driver_of(cell)
        assert callable(mod.setup)
        assert cell.limits, "every cell states its correctness limits"
        assert cell.traffic["trace_units"] >= 1


def test_end_to_end_metrics(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_enough(manifest):
    for w in manifest["workloads"]:
        cell = harness.load_cell(w["name"], manifest)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_per_layer_metrics_have_readers_and_move_what_their_cells_report(
        manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        reader = harness.load_module(os.path.join(BENCH, "metrics",
                                                  m["name"] + ".py"))
        assert callable(reader.read)
        for cell in m.get("workloads", cells):
            assert cell in cells
            reported = {e["name"] for e in
                        harness.load_cell(cell, manifest).end_to_end}
            assert m["moves"] in reported, (m["name"], cell)


def test_layers_are_named_alike(manifest):
    layers = {m["layer"] for m in manifest["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"`{layer}`" in perf, f"PERF.md does not list layer {layer}"


def test_program_groups_are_the_layers_the_readers_use():
    from bench import trace_reduce
    groups = trace_reduce.load_groups(os.path.join(BENCH, "program_groups"))
    assert set(groups) >= {"init", "iterate"}
    for group, patterns in groups.items():
        assert NAME.match(group) and patterns
        for p in patterns:
            re.compile(p)


def test_four_chip_share(manifest):
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_run_without_a_tpu_fails_and_prints_no_result():
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "fit_sift1m_k4096", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""
    assert "TPU" in proc.stderr
