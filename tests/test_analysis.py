"""k2lint static-analysis tests (DESIGN.md §15).

Seeded-violation fixtures: each pass must flag a deliberately broken
construct (host read inside ``lax.scan``, a BlockSpec overflowing the
VMEM budget, an uncharged ``sqnorm`` distance site, an f64 leak in an
int8 region) with the documented rule id and a stable fingerprint —
and the committed tree itself must come back clean against the
committed baseline.
"""
import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import cli, jaxpr_audit, kernel_contracts, opcount_lint
from repro.analysis.registry import (EntryPoint, KernelEntry,
                                     audit_entries, kernel_entries)
from repro.analysis.report import (Finding, apply_baseline, finalize_findings,
                                   fingerprint, load_baseline, make_report,
                                   validate_report, write_baseline)

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# report / fingerprint / baseline mechanics
# ---------------------------------------------------------------------------


def test_fingerprint_is_line_independent_and_stable():
    fp = fingerprint("K2L101", "src/x.py", "e", "s")
    assert fp == fingerprint("K2L101", "src/x.py", "e", "s")
    assert len(fp) == 16
    # any identity component changes the fingerprint; the line does not
    assert fp != fingerprint("K2L102", "src/x.py", "e", "s")
    a = Finding(rule="K2L101", severity="error", file="src/x.py", line=3,
                entry="e", site="s", message="m")
    b = Finding(rule="K2L101", severity="error", file="src/x.py", line=99,
                entry="e", site="s", message="m")
    finalize_findings([a])
    finalize_findings([b])
    assert a.fingerprint == b.fingerprint == fp


def test_repeated_sites_get_distinct_fingerprints():
    fs = [Finding(rule="K2L301", severity="error", file="f.py", line=i,
                  entry="", site="g:call:pairwise_sqdist", message="m")
          for i in (1, 2, 3)]
    finalize_findings(fs)
    assert len({f.fingerprint for f in fs}) == 3


def test_baseline_roundtrip_suppresses_and_requires_justification(tmp_path):
    f = Finding(rule="K2L301", severity="error", file="f.py", line=1,
                entry="", site="g:call:pairwise_sqdist", message="m")
    finalize_findings([f])
    path = tmp_path / "baseline.json"
    write_baseline(str(path), [f], "audited: legacy driver charges this")
    base = load_baseline(str(path))
    assert f.fingerprint in base
    assert apply_baseline([f], base) == [] and f.baselined
    # a second, new finding still blocks
    g = Finding(rule="K2L301", severity="error", file="f.py", line=9,
                entry="", site="h:call:pairwise_sqdist", message="m")
    finalize_findings([g])
    assert apply_baseline([g], base) == [g]
    # entries without a justification are rejected outright
    raw = json.loads(path.read_text())
    raw["findings"][0]["justification"] = ""
    path.write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(str(path))


def test_report_schema(tmp_path):
    f = Finding(rule="K2L101", severity="error", file="f.py", line=1,
                entry="e", site="s", message="m")
    finalize_findings([f])
    rep = make_report([f], {"jaxpr_audit": {"entries": 1}}, [f])
    validate_report(rep)
    assert rep["ok"] is False and rep["counts"]["blocking"] == 1
    with pytest.raises(ValueError):
        validate_report({"schema": "nope"})


# ---------------------------------------------------------------------------
# pass 1 — seeded jaxpr violations
# ---------------------------------------------------------------------------


def _entry(fn, args, **kw):
    return EntryPoint(name=kw.pop("name", "seeded/entry"),
                      file="tests/test_analysis.py",
                      build=lambda: (fn, args), **kw)


def test_seeded_host_callback_in_scan_is_k2l101():
    def hot(x):
        def body(c, xi):
            jax.debug.print("host read {}", jnp.sum(xi))
            return c + jnp.sum(xi), c
        return jax.lax.scan(body, jnp.float32(0), x)

    fs = jaxpr_audit.audit_entry(_entry(hot, (jnp.ones((8, 4)),)))
    finalize_findings(fs)
    hits = [f for f in fs if f.rule == "K2L101"]
    assert hits, _rules(fs)
    assert "scan" in hits[0].site
    assert hits[0].fingerprint == fingerprint(
        "K2L101", hits[0].file, hits[0].entry, hits[0].site)


def test_seeded_f64_leak_in_int8_region_is_k2l102():
    def hot(xq):
        # dequantize straight to f64 — both prongs of the dtype rule
        return jnp.sum(xq.astype(jnp.float64))

    with jax.enable_x64(True):
        fs = jaxpr_audit.audit_entry(
            _entry(hot, (jnp.zeros((8, 4), jnp.int8),),
                   int8_region=True, sanctioned_dequants=0))
    sites = {f.site for f in fs if f.rule == "K2L102"}
    assert any(s.startswith("convert-f64") for s in sites), sites
    assert "dequant-budget" in sites


def test_seeded_dequant_over_budget_is_k2l102():
    def hot(xq, sc):
        a = xq.astype(jnp.float32) * sc          # sanctioned (residual)
        b = jnp.float32(0.5) * xq.astype(jnp.float32)   # leaked second one
        return jnp.sum(a) + jnp.sum(b)

    args = (jnp.zeros((8, 4), jnp.int8), jnp.ones((8, 4), jnp.float32))
    fs = jaxpr_audit.audit_entry(
        _entry(hot, args, int8_region=True, sanctioned_dequants=1))
    assert any(f.rule == "K2L102" and f.site == "dequant-budget"
               for f in fs), _rules(fs)
    # with both sanctioned the same trace is clean
    fs2 = jaxpr_audit.audit_entry(
        _entry(hot, args, int8_region=True, sanctioned_dequants=2))
    assert not [f for f in fs2 if f.rule == "K2L102"]


def test_seeded_trace_failure_is_k2l100_and_alt_signature_k2l103():
    fs = jaxpr_audit.audit_entry(
        _entry(lambda x: jnp.sum(x), ("not-an-array",)))
    assert any(f.rule == "K2L100" for f in fs)

    def leaky(x):          # shape leaked as a Python scalar: alt trace dies
        assert x.shape[0] == 8
        return jnp.sum(x)

    e = EntryPoint(name="seeded/leaky", file="tests/test_analysis.py",
                   build=lambda: (leaky, (jnp.ones((8,)),)),
                   build_alt=lambda: (leaky, (jnp.ones((16,)),)))
    fs = jaxpr_audit.audit_entry(e)
    assert any(f.rule == "K2L103" and f.site == "alt-signature" for f in fs)


def test_seeded_collective_in_collective_free_entry_is_k2l104():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))

    def hot(x):
        return shard_map(lambda s: jax.lax.psum(s, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P())(x)

    fs = jaxpr_audit.audit_entry(_entry(hot, (jnp.ones((8,)),)))
    assert any(f.rule == "K2L104" for f in fs), _rules(fs)
    # the same trace is sanctioned when the entry declares collectives
    fs2 = jaxpr_audit.audit_entry(
        _entry(hot, (jnp.ones((8,)),), collective_free=False))
    assert not [f for f in fs2 if f.rule == "K2L104"]


# ---------------------------------------------------------------------------
# pass 2 — seeded kernel-contract violations
# ---------------------------------------------------------------------------


def _copy_kernel_entry(shape, block, grid, index_map, name="seeded/kernel",
                       **kw):
    import jax.experimental.pallas as pl

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def build():
        x = jnp.zeros(shape, jnp.float32)
        spec = pl.BlockSpec(block, index_map)

        def fn(x):
            return pl.pallas_call(
                body, grid=grid, in_specs=[spec], out_specs=spec,
                out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
                interpret=True)(x)
        return fn, (x,)

    return KernelEntry(name=name, file="tests/test_analysis.py",
                       build=build, **kw)


def test_seeded_vmem_overflow_is_k2l203():
    # (2048, 2048) f32 blocks, double-buffered in+out = 64 MiB > budget
    e = _copy_kernel_entry((2048, 2048), (2048, 2048), (1,),
                           lambda i: (0, 0))
    fs = kernel_contracts.check_kernel(e)
    assert any(f.rule == "K2L203" for f in fs), _rules(fs)


def test_seeded_indivisible_block_is_k2l201_unless_pad_ok():
    e = _copy_kernel_entry((96, 128), (64, 128), (2,), lambda i: (i, 0))
    fs = kernel_contracts.check_kernel(e)
    assert any(f.rule == "K2L201" for f in fs), _rules(fs)
    e2 = _copy_kernel_entry((96, 128), (64, 128), (2,), lambda i: (i, 0),
                            pad_ok=True)
    assert not [f for f in kernel_contracts.check_kernel(e2)
                if f.rule == "K2L201"]


def test_seeded_coverage_gap_and_revisit_are_k2l204():
    # 4 row blocks, but the index map only ever visits rows 0 and 1,
    # revisiting them in non-contiguous runs
    e = _copy_kernel_entry((512, 128), (128, 128), (4,),
                           lambda i: (i % 2, 0))
    sites = {f.site for f in kernel_contracts.check_kernel(e)
             if f.rule == "K2L204"}
    assert any(s.endswith("coverage") for s in sites), sites
    assert any(s.endswith("revisit") for s in sites), sites


def test_clean_kernel_has_no_blocking_findings():
    e = _copy_kernel_entry((512, 128), (128, 128), (4,), lambda i: (i, 0))
    fs = kernel_contracts.check_kernel(e)
    assert not [f for f in fs if f.severity == "error"], _rules(fs)


# ---------------------------------------------------------------------------
# pass 3 — seeded opcount-lint violations (pure source, no tracing)
# ---------------------------------------------------------------------------

_UNCHARGED = """
import jax.numpy as jnp
from repro.core.distance import pairwise_sqdist, sqnorm

def assign(x, c):
    d = pairwise_sqdist(x, c)
    return jnp.argmin(d, axis=1)

def energy(x, c, a):
    return jnp.sum(sqnorm(x - c[a]))
"""


def test_seeded_uncharged_sqdist_is_k2l301():
    fs = opcount_lint.lint_source(_UNCHARGED, "src/repro/seeded.py",
                                  charging_map={})
    sites = {f.site for f in fs}
    assert "assign:call:pairwise_sqdist" in sites
    assert "energy:residual-norm:sqnorm" in sites
    f = next(f for f in fs if f.site.startswith("assign"))
    finalize_findings(fs)
    assert f.fingerprint == fingerprint("K2L301", "src/repro/seeded.py",
                                        "", "assign:call:pairwise_sqdist")


def test_charge_map_pragma_and_infunction_charge_all_pass():
    charged = _UNCHARGED.replace(
        "    d = pairwise_sqdist(x, c)",
        "    counter.add_distances(x.shape[0] * c.shape[0])\n"
        "    d = pairwise_sqdist(x, c)").replace(
        "def energy(x, c, a):",
        "def energy(x, c, a):  # k2lint: charged-by(driver)")
    assert opcount_lint.lint_source(charged, "src/repro/seeded.py",
                                    charging_map={}) == []
    # a CHARGING_MAP entry (function- or module-scoped) also passes
    fs = opcount_lint.lint_source(
        _UNCHARGED, "src/repro/seeded.py",
        charging_map={"src/repro/seeded.py::assign": "driver charges n*k"})
    assert {f.site for f in fs} == {"energy:residual-norm:sqnorm"}
    assert opcount_lint.lint_source(
        _UNCHARGED, "src/repro/seeded.py",
        charging_map={"src/repro/seeded.py::*": "driver charges all"}) == []


def test_expansion_idiom_is_detected():
    src = ("def d2(x, c, xn, cn):\n"
           "    return xn + cn - 2.0 * (x @ c.T)\n")
    fs = opcount_lint.lint_source(src, "src/repro/seeded.py",
                                  charging_map={})
    assert [f.site for f in fs] == ["d2:expansion:2*contraction"]


def test_unparseable_module_is_k2l300():
    fs = opcount_lint.lint_source("def broken(:\n", "src/repro/bad.py")
    assert [f.rule for f in fs] == ["K2L300"]


# ---------------------------------------------------------------------------
# registry coverage + the committed tree is clean
# ---------------------------------------------------------------------------


def test_registry_meets_coverage_floor():
    ents = audit_entries()
    assert len(ents) >= 10
    assert len({e.name for e in ents}) == len(ents)
    # every Pallas kernel file with a grid/BlockSpec has a contract entry
    kfiles = {os.path.relpath(p, REPO).replace(os.sep, "/")
              for p in glob.glob(os.path.join(REPO, "src/repro/kernels",
                                              "*.py"))
              if "pl.pallas_call(" in open(p).read()}
    covered = {k.file for k in kernel_entries()}
    assert kfiles <= covered, kfiles - covered


def test_seeded_fixtures_block_through_the_gate(tmp_path):
    """Each seeded violation survives finalize + empty-baseline apply —
    i.e. would make the CLI gate exit non-zero — and a justified
    baseline entry is the only way to suppress it."""
    # a seeded tree under opcount_lint.run's own directory walk
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "seeded.py").write_text(_UNCHARGED)
    fs, stats = opcount_lint.run(root="src/repro", charging_map={},
                                 repo_root=str(tmp_path))
    assert stats["files"] == 1 and fs

    def hot(x):
        def body(c, xi):
            jax.debug.print("leak {}", jnp.sum(xi))
            return c, c
        return jax.lax.scan(body, jnp.float32(0), x)

    fs += jaxpr_audit.audit_entry(_entry(hot, (jnp.ones((4, 2)),)))
    fs += kernel_contracts.check_kernel(
        _copy_kernel_entry((2048, 2048), (2048, 2048), (1,),
                           lambda i: (0, 0)))
    fs += kernel_contracts.check_kernel(
        _copy_kernel_entry((96, 128), (64, 128), (2,), lambda i: (i, 0),
                           name="seeded/indivisible"))
    finalize_findings(fs)
    blocking = apply_baseline(fs, {})
    assert {f.rule for f in blocking} >= {"K2L301", "K2L101", "K2L203",
                                          "K2L201"}
    # baselining every blocking fingerprint (with justification) clears it
    path = tmp_path / "baseline.json"
    write_baseline(str(path), blocking, "seeded fixtures, audited")
    assert apply_baseline(fs, load_baseline(str(path))) == []


def test_clean_tree_has_no_new_blocking_findings(tmp_path):
    out = tmp_path / "k2lint_report.json"
    assert cli.run(out=str(out), quiet=True, repo_root=REPO) == 0
    rep = json.loads(out.read_text())
    validate_report(rep)
    assert rep["ok"] is True and rep["counts"]["blocking"] == 0
    assert rep["passes"]["jaxpr_audit"]["entries"] >= 10
    assert rep["passes"]["kernel_contracts"]["kernels"] >= 6
