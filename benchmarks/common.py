"""Shared benchmark helpers.

Reporting goes through one telemetry-backed ``BenchReport``: every
``emit`` row still prints the legacy ``name,value,details`` CSV line
(line-oriented consumers and the CI logs key on it), but rows are also
mirrored into a ``MetricsRegistry`` as labeled gauges and flushed at
process exit as a human-readable table plus, when ``REPRO_BENCH_JSON``
names a path, a machine-readable JSON report (rows + the Prometheus
exposition of the registry).
"""
from __future__ import annotations

import atexit
import json
import os
import time

from repro.core.perf_model import opt_perf_model
from repro.core.router import make_baseline_cluster, make_slos_serve_cluster
from repro.launch.compile_cache import enable_compile_cache
from repro.telemetry import MetricsRegistry, prometheus_text

enable_compile_cache()

PERF = opt_perf_model(7e9)
PERF_SPEC = opt_perf_model(7e9, spec=True)


def system_factory(kind: str, n_replicas: int = 1, spec_alpha=0.7):
    if kind == "ours":
        return lambda: make_slos_serve_cluster(
            n_replicas, PERF_SPEC if spec_alpha else PERF,
            spec_alpha=spec_alpha)
    if kind == "ours-ar":
        return lambda: make_slos_serve_cluster(n_replicas, PERF,
                                               spec_alpha=None)
    if kind == "ours-nobe":
        from repro.core.simulator import SimConfig
        return lambda: make_slos_serve_cluster(
            n_replicas, PERF, spec_alpha=None,
            sim_cfg=SimConfig(best_effort=False))
    if kind == "distserve":
        def best_of_ratios():
            return make_baseline_cluster("distserve", max(n_replicas, 2),
                                         PERF, prefill_ratio=(1, 1))
        return best_of_ratios
    return lambda: make_baseline_cluster(kind, n_replicas, PERF)


SYSTEMS = ["ours", "ours-ar", "vllm", "vllm-spec", "sarathi"]


def timed(fn, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    return out, time.time() - t0


class BenchReport:
    """Accumulates benchmark rows; mirrors each into a metrics registry
    (``repro_benchmark_value{benchmark,metric}`` gauges) so benchmark
    output and serving telemetry share one exposition format."""

    def __init__(self, name: str = "benchmarks"):
        self.name = name
        self.rows: list[dict] = []
        self.registry = MetricsRegistry(enabled=True)
        self._gauge = self.registry.gauge(
            "repro_benchmark_value",
            "headline value per benchmark row",
            ("benchmark", "metric"))

    def add(self, metric: str, value: float, **details) -> dict:
        row = {"metric": metric, "value": float(value), **details}
        self.rows.append(row)
        self._gauge.labels(benchmark=self.name, metric=metric).set(
            float(value))
        return row

    # ------------------------------ output ----------------------------- #
    def table(self) -> str:
        if not self.rows:
            return ""
        w = max(len(r["metric"]) for r in self.rows)
        lines = [f"{'metric'.ljust(w)}  {'value':>12}  details",
                 f"{'-' * w}  {'-' * 12}  {'-' * 7}"]
        for r in self.rows:
            details = ";".join(f"{k}={v}" for k, v in r.items()
                               if k not in ("metric", "value"))
            lines.append(f"{r['metric'].ljust(w)}  {r['value']:>12.2f}  "
                         f"{details}")
        return "\n".join(lines)

    def to_json(self) -> str:
        return json.dumps({"name": self.name, "rows": self.rows,
                           "prometheus": prometheus_text(self.registry)},
                          indent=2, sort_keys=True)

    def flush(self) -> None:
        if not self.rows:
            return
        print(f"\n== {self.name} report ==\n{self.table()}", flush=True)
        path = os.environ.get("REPRO_BENCH_JSON")
        if path:
            with open(path, "w") as fh:
                fh.write(self.to_json() + "\n")
            print(f"json report -> {path}", flush=True)


_REPORT: BenchReport | None = None


def report() -> BenchReport:
    """The process-wide report, flushed at exit."""
    global _REPORT
    if _REPORT is None:
        _REPORT = BenchReport(os.path.basename(
            os.environ.get("REPRO_BENCH_NAME", "benchmarks")))
        atexit.register(_REPORT.flush)
    return _REPORT


def _parse_details(derived: str) -> dict:
    out = {}
    for part in derived.split(";"):
        if "=" in part:
            k, _, v = part.partition("=")
            try:
                out[k] = float(v)        # typed JSON where possible
            except ValueError:
                out[k] = v
    return out


def emit(name: str, us_per_call: float, derived: str):
    """Legacy row emitter: prints the historical CSV line AND records the
    row on the shared ``BenchReport``."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
    report().add(name, us_per_call, **_parse_details(derived))
