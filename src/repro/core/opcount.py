"""Vector-operation accounting following the paper's experimental methodology.

The paper (§3) measures runtime complexity as the number of *vector operations*
(distances, inner products, additions — all O(d)), counting sorts as
``|X_j| * log2(|X_j|) / d`` vector-op equivalents so that comparisons are
charged fairly. We reproduce that accounting exactly so that the speedup
tables are machine-independent.

Alongside the paper's op metric the counter tracks a *memory-traffic* metric
(bytes gathered / scattered / sorted by layout maintenance, DESIGN.md §9):
the resident-layout engine's whole point is that steady-state iterations
stop paying the O(n log n + nd) grouping traffic, and these byte counters
are what make that win measurable (``benchmarks/iter_bench.py``,
``fit(..., profile=True)``). Bytes are reported separately and never mix
into ``total`` — the paper's op metric is unchanged.

A third lane makes the self-healing execution layer observable
(DESIGN.md §11): layout-event totals (``rows_moved``/``resorts`` from the
engine's :class:`StepStats`) and repair counters, one per rung of the
repair lattice (``bound_reset`` < ``regroup`` < ``split`` < ``restore``)
plus the serving-side ``degraded_folds`` (arena-full ``partial_fit``
falling back to the Sculley-sums-only fold), ``retries`` (transient
predict/serve failures absorbed by backoff) and ``sanitized_rows``
(non-finite inputs quarantined at weight 0). Healing is never silent:
every repair lands on the counter and surfaces through
``fit(..., profile=True)`` and the benchmark summary lines.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class OpCounter:
    """Host-side accumulator of the paper's vector-op metric."""
    distances: float = 0.0
    inner_products: float = 0.0
    additions: float = 0.0
    sort_equivalents: float = 0.0
    # quantized-scan lane (DESIGN.md §13): int8 approximate distances are
    # counted separately from the paper's f32 vector-op metric — an int8
    # scan op is neither free nor a full f32 distance, so mixing the two
    # into ``total`` would corrupt the speedup tables in either direction
    int8_ops: float = 0.0
    # memory-traffic lane (bytes): layout gathers/scatters and sort passes
    bytes_gathered: float = 0.0
    bytes_scattered: float = 0.0
    bytes_sorted: float = 0.0
    # scan-traffic lane (bytes): table bytes the distance scans read —
    # dtype-aware (int8 rows cost d + 4 scale bytes vs 4d for f32), so the
    # quantized-scan win is a counted claim (BENCH_quant.json)
    bytes_scanned: float = 0.0
    # robustness lane (DESIGN.md §11): layout events + repair lattice
    rows_moved: float = 0.0
    resorts: float = 0.0
    repairs: dict = dataclasses.field(
        default_factory=lambda: {"bound_reset": 0, "regroup": 0,
                                 "split": 0, "restore": 0})
    degraded_folds: float = 0.0
    retries: float = 0.0
    sanitized_rows: float = 0.0
    # streaming lane (DESIGN.md §14): rows retired by sliding-window
    # eviction (their subtraction deltas charge ``additions`` as usual)
    evicted_rows: float = 0.0
    # serving-plane graceful-degradation lane (DESIGN.md §12): one counter
    # per rung of the executor's degradation ladder — probe-shrunk routing,
    # route-only assignment, and load-shed requests (typed Overloaded)
    degrades: dict = dataclasses.field(
        default_factory=lambda: {"int8_scan": 0, "probe_shrink": 0,
                                 "route_only": 0, "shed": 0})
    # blocking device-to-host reads of the fit path: the input check, each
    # GDI round (or host-loop split), each monitor flush, guard, fallback
    # energy and checkpoint read; ``api.fit``'s ``kmeans.fit`` span
    # carries one fit's count
    host_reads: int = 0

    @property
    def total(self) -> float:
        return (self.distances + self.inner_products + self.additions
                + self.sort_equivalents)

    @property
    def bytes_moved(self) -> float:
        """Total layout memory traffic (gather + scatter + sort bytes)."""
        return self.bytes_gathered + self.bytes_scattered + self.bytes_sorted

    @staticmethod
    def _integral(n, kind: str) -> float:
        """Whole-op charges must be integral: a fractional distance count
        (e.g. a Python-float ``k * k / 2`` at odd k) silently corrupts
        ``total`` for the paper's speedup tables. Sort *equivalents* are
        the one legitimately fractional lane (``add_sort``)."""
        v = float(n)
        if v != int(v):
            raise ValueError(f"{kind} charge must be an integer op count, "
                             f"got {n!r}")
        return v

    def add_distances(self, n: float) -> None:
        self.distances += self._integral(n, "distances")

    def add_inner(self, n: float) -> None:
        self.inner_products += self._integral(n, "inner_products")

    def add_additions(self, n: float) -> None:
        self.additions += self._integral(n, "additions")

    def add_int8_ops(self, n: float) -> None:
        """Charge ``n`` int8 approximate-distance ops (the quantized scan
        stage). Kept off ``total`` — see the class docstring."""
        self.int8_ops += self._integral(n, "int8_ops")

    def add_scan_bytes(self, b: float) -> None:
        self.bytes_scanned += float(b)

    def add_sort(self, m: float, d: int) -> None:
        """Charge an m-element sort as m*log2(m)/d vector ops (paper §2.2)."""
        if m > 1:
            self.sort_equivalents += m * math.log2(m) / max(d, 1)

    def add_gather_bytes(self, b: float) -> None:
        self.bytes_gathered += float(b)

    def add_scatter_bytes(self, b: float) -> None:
        self.bytes_scattered += float(b)

    def add_sort_bytes(self, b: float) -> None:
        self.bytes_sorted += float(b)

    @property
    def total_repairs(self) -> int:
        return int(sum(self.repairs.values()))

    def count_repair(self, kind: str, n: int = 1) -> None:
        """Record ``n`` self-heal repairs of one lattice rung
        (``bound_reset`` | ``regroup`` | ``split`` | ``restore``)."""
        if kind not in self.repairs:
            raise ValueError(f"unknown repair kind {kind!r}; expected one "
                             f"of {sorted(self.repairs)}")
        self.repairs[kind] += int(n)

    def count_degraded_fold(self, n: int = 1) -> None:
        self.degraded_folds += int(n)

    @property
    def total_degrades(self) -> int:
        return int(sum(self.degrades.values()))

    def count_degrade(self, kind: str, n: int = 1) -> None:
        """Record ``n`` requests served on one degradation rung
        (``int8_scan`` | ``probe_shrink`` | ``route_only`` | ``shed``)."""
        if kind not in self.degrades:
            raise ValueError(f"unknown degrade kind {kind!r}; expected one "
                             f"of {sorted(self.degrades)}")
        self.degrades[kind] += int(n)

    def count_retry(self, n: int = 1) -> None:
        self.retries += int(n)

    def count_sanitized_rows(self, n: int) -> None:
        self.sanitized_rows += int(n)

    def count_evicted_rows(self, n: int) -> None:
        self.evicted_rows += int(n)

    def snapshot(self) -> float:
        return self.total

    def profile(self) -> dict:
        """Machine-readable counter state for ``fit(..., profile=True)``."""
        return {
            "distances": self.distances,
            "inner_products": self.inner_products,
            "additions": self.additions,
            "sort_equivalents": self.sort_equivalents,
            "total_ops": self.total,
            "int8_ops": self.int8_ops,
            "bytes_gathered": self.bytes_gathered,
            "bytes_scattered": self.bytes_scattered,
            "bytes_sorted": self.bytes_sorted,
            "bytes_moved": self.bytes_moved,
            "bytes_scanned": self.bytes_scanned,
            "rows_moved": self.rows_moved,
            "resorts": self.resorts,
            "repairs": dict(self.repairs),
            "total_repairs": self.total_repairs,
            "degraded_folds": self.degraded_folds,
            "degrades": dict(self.degrades),
            "total_degrades": self.total_degrades,
            "retries": self.retries,
            "sanitized_rows": self.sanitized_rows,
            "evicted_rows": self.evicted_rows,
            "host_reads": self.host_reads,
        }


# state lanes that ride along with a moved row besides its d features:
# (u, lo, w) — the point id travels inside the sort/scatter key charge
LAYOUT_STATE_LANES = 3


def charge_iteration(counter: OpCounter, *, n: int, d: int, k: int, kn: int,
                     stats, resident: bool = False,
                     precision: str = "f32") -> float:
    """Charge one k²-means iteration from its device ``StepStats``.

    Paper ops: the k²-NN graph build, k_n candidate distances per recomputed
    point, k movement norms, and the mean update's additions — ``n`` when the
    update re-reduced every row (rebuild engines and resident re-sort
    iterations), ``2*moved`` when the resident engine applied an incremental
    delta (each moved row is subtracted from its old center sum and added to
    its new one).

    Memory traffic: ``moved`` rows × (d + state lanes) gathered and
    scattered by layout maintenance, plus m·log2(m) key-passes over the
    same rows — the full argsort of a re-sort (``moved`` spans the whole
    re-sorted arena(s), so partial shard re-sorts charge only the shards
    that actually sorted) or the move-buffer compaction of a sparse
    repair. Both lanes are dtype-aware: under ``precision="int8"``
    (DESIGN.md §13) the k_n candidate scan charges int8 ops instead of
    f32 distances — only the exactly re-ranked survivors
    (``stats.reranked``) cost f32 distances — and a moved arena row
    carries d int8 feature bytes plus one f32 scale lane instead of d f32
    features. The scan-traffic lane counts the candidate-table bytes each
    recomputed point read (d+4 per int8 candidate vs 4d f32, plus the 4d
    f32 bytes of every re-ranked survivor). Returns the iteration's
    post-update energy.
    """
    n_need, changed, energy, moved, resorted = (float(s) for s in stats[:5])
    reranked = float(stats[5]) if len(stats) > 5 else 0.0
    if precision == "int8":
        counter.add_distances(k * k + k + reranked)
        counter.add_int8_ops(n_need * kn)
        counter.add_scan_bytes(n_need * kn * (d + 4) + reranked * 4 * d)
        row_bytes = d + (LAYOUT_STATE_LANES + 1) * 4
    else:
        counter.add_distances(k * k + n_need * kn + k)
        counter.add_scan_bytes(n_need * kn * 4 * d)
        row_bytes = (d + LAYOUT_STATE_LANES) * 4
    full_update = (not resident) or resorted > 0
    counter.add_additions(n if full_update else 2.0 * moved)
    counter.rows_moved += moved
    counter.resorts += resorted
    if moved > 0:
        counter.add_gather_bytes(moved * row_bytes)
        counter.add_scatter_bytes(moved * row_bytes)
        counter.add_sort_bytes(moved * 8
                               * max(1.0, math.log2(max(moved, 2.0))))
    return energy
