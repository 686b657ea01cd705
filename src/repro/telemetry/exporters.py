"""Telemetry exporters: Prometheus text exposition and an in-memory step
tracer with span-style timing hooks, serialised as JSON lines.

``prometheus_text`` renders a ``MetricsRegistry`` in the Prometheus text
exposition format (``# HELP`` / ``# TYPE`` headers, ``le``-labeled
histogram buckets with ``_sum``/``_count``); ``parse_prometheus`` is the
matching minimal parser used by tests and the dashboard tooling, so the
round trip is covered in-repo without a client-library dependency.

``StepTracer`` keeps ``step`` records (the per-step sampler row) and
``span`` records (host phases such as ``repro.driver.plan`` or
``repro.engine.decode.wait``, each with start, end, parent span and
request id on ``time.perf_counter_ns``) in memory, and writes them as
JSON lines at ``dump()`` / ``close()``.  While a tracer is enabled each
nested span also opens a ``jax.profiler.TraceAnnotation``, so a device
profile taken meanwhile carries the same labels.
"""
from __future__ import annotations

import contextlib
import json
import math
import time
import weakref
from typing import NamedTuple, Optional

from repro.telemetry.registry import Histogram, MetricsRegistry


def _fmt(v: float) -> str:
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _labels(kv: dict) -> str:
    if not kv:
        return ""
    inner = ",".join(f'{k}="{_escape(str(v))}"' for k, v in kv.items())
    return "{" + inner + "}"


def _escape(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def prometheus_text(registry: MetricsRegistry) -> str:
    """Render the registry in Prometheus text exposition format."""
    out: list[str] = []
    for m in registry.collect():
        out.append(f"# HELP {m.name} {_escape(m.help)}")
        out.append(f"# TYPE {m.name} {m.kind}")
        if isinstance(m, Histogram):
            for lv, child in m.samples():
                cum = 0
                for bound, c in zip(child.bounds, child.counts):
                    cum += c
                    out.append(f"{m.name}_bucket"
                               f"{_labels({**lv, 'le': _fmt(bound)})}"
                               f" {cum}")
                out.append(f"{m.name}_bucket"
                           f"{_labels({**lv, 'le': '+Inf'})} {child.count}")
                out.append(f"{m.name}_sum{_labels(lv)} {_fmt(child.sum)}")
                out.append(f"{m.name}_count{_labels(lv)} {child.count}")
        else:
            for lv, child in m.samples():
                out.append(f"{m.name}{_labels(lv)} {_fmt(child.value)}")
    return "\n".join(out) + "\n"


def timeseries_prometheus_text(sampler, name: str = "repro_step_series"
                               ) -> str:
    """Render the latest sample of every step time series as gauges:
    ``repro_step_series{series="..."}`` carries the value and
    ``repro_step_series_timestamp{series="..."}`` the timestamp of that
    sample in the sampler's exported time base — virtual seconds by
    default, wall-clock epoch seconds when the sampler was built with
    ``wall_clock=True`` (the serving-gateway mode).  Values are
    identical across the two modes by construction; only the timestamp
    series differs."""
    if not sampler.series:
        return ""
    out = [f"# HELP {name} latest value per step time series",
           f"# TYPE {name} gauge"]
    rows = []
    for sname in sorted(sampler.series):
        last = sampler.series[sname].last()
        if last is None:
            continue
        out.append(f"{name}{_labels({'series': sname})} {_fmt(last[1])}")
        t = sampler.last_time(sname) if hasattr(sampler, "last_time") \
            else last[0]
        rows.append((sname, t))
    out.append(f"# HELP {name}_timestamp sample time of the latest value "
               f"(virtual seconds, or wall-clock epoch in wall mode)")
    out.append(f"# TYPE {name}_timestamp gauge")
    for sname, t in rows:
        out.append(f"{name}_timestamp{_labels({'series': sname})} "
                   f"{_fmt(t if t is not None else math.nan)}")
    return "\n".join(out) + "\n"


def parse_prometheus(text: str) -> dict[tuple[str, tuple], float]:
    """Parse a text exposition back into ``{(name, ((label, value),
    ...)): value}``.  Minimal by design (no exemplars, no timestamps) —
    enough for the e2e consistency tests and the dashboard tooling."""
    out: dict[tuple[str, tuple], float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        if "{" in head:
            name, _, rest = head.partition("{")
            body = rest.rsplit("}", 1)[0]
            labels = []
            for part in _split_labels(body):
                k, _, v = part.partition("=")
                labels.append((k, v.strip('"')
                               .replace('\\"', '"')
                               .replace("\\n", "\n")
                               .replace("\\\\", "\\")))
            key = (name, tuple(sorted(labels)))
        else:
            key = (head, ())
        out[key] = float(val)
    return out


def _split_labels(body: str) -> list[str]:
    """Split 'a="x",b="y,z"' on commas outside quotes."""
    parts, cur, quoted, escaped = [], [], False, False
    for ch in body:
        if escaped:
            cur.append(ch)
            escaped = False
            continue
        if ch == "\\":
            cur.append(ch)
            escaped = True
            continue
        if ch == '"':
            quoted = not quoted
        if ch == "," and not quoted:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p for p in parts if p]


def quantile_from_exposition(samples: dict, name: str, q: float,
                             **labels) -> float:
    """``histogram_quantile`` over a parsed exposition: estimate the
    q-quantile of histogram ``name`` restricted to ``labels``."""
    want = tuple(sorted((k, str(v)) for k, v in labels.items()))
    buckets: list[tuple[float, float]] = []
    for (n, lv), v in samples.items():
        if n != name + "_bucket":
            continue
        d = dict(lv)
        le = d.pop("le")
        if tuple(sorted(d.items())) != want:
            continue
        buckets.append((math.inf if le == "+Inf" else float(le), v))
    if not buckets:
        return math.nan
    buckets.sort()
    total = buckets[-1][1]
    if total == 0:
        return math.nan
    rank = q * total
    prev_bound, prev_cum = 0.0, 0.0
    for bound, cum in buckets:
        if cum >= rank:
            if math.isinf(bound):
                return prev_bound
            if cum == prev_cum:
                return bound
            frac = (rank - prev_cum) / (cum - prev_cum)
            return prev_bound + (bound - prev_bound) * frac
        prev_bound, prev_cum = bound, cum
    return buckets[-1][0]




class Span(NamedTuple):
    """One timed host phase, on ``time.perf_counter_ns``."""
    name: str
    start_ns: int
    end_ns: Optional[int]     # None while an async span is still open
    id: int
    parent: int               # id of the enclosing span, -1 for none
    rid: Optional[int]        # the request it belongs to, if just one
    attrs: dict


class StepRecord(NamedTuple):
    step: int
    t: float                  # virtual seconds
    wall_ns: int              # perf_counter_ns when recorded
    row: dict


# Temporary: the benchmark's run record does not carry the cluster's
# tracer yet, so its metric readers find it here.  Delete ``_LIVE`` and
# ``live_tracers`` once ``bench/lib/record.py`` ``Run`` holds the tracer.
_LIVE: "weakref.WeakSet[StepTracer]" = weakref.WeakSet()


def live_tracers() -> list["StepTracer"]:
    """Every enabled tracer alive in this process (temporary, see
    ``_LIVE``)."""
    return list(_LIVE)


class StepTracer:
    """In-memory trace of a serving stack, serialised as JSON lines.

    Three record kinds: ``step`` rows snapshot the per-step sampler
    output (virtual time ``t`` plus the host clock ``wall_ns``), ``span``
    records time named host phases (start, end, parent span, request
    id), and free-form records come through ``emit``.  Records are kept
    as tuples (bounded by ``max_records``; ``dropped`` counts the rest)
    and turned into JSON only by ``records()``, ``dump()`` and
    ``close()``, which writes them to ``path``.  A disabled tracer
    records nothing.
    """

    def __init__(self, path: Optional[str] = None,
                 max_records: int = 100_000, enabled: bool = True):
        self.enabled = enabled
        self.path = path
        self.max_records = max_records
        self.dropped = 0
        self._recs: list = []
        self._stack: list[int] = []     # ids of the open context spans
        self._open: dict = {}           # (name, rid) -> start_ns
        self._next_id = 0
        if enabled:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
            _LIVE.add(self)

    def _add(self, rec) -> None:
        if len(self._recs) < self.max_records:
            self._recs.append(rec)
        else:
            self.dropped += 1

    def emit(self, record: dict) -> None:
        if self.enabled:
            self._add(dict(record))

    def step(self, step: int, now: float, row: dict) -> None:
        if self.enabled:
            self._add(StepRecord(step, now, time.perf_counter_ns(), row))

    @contextlib.contextmanager
    def span(self, name: str, rid: Optional[int] = None, **attrs):
        """Time a phase nested in the open spans.  The phase also runs
        under a ``jax.profiler.TraceAnnotation`` of the same name, so a
        device profile taken meanwhile holds a copy of the span on the
        device trace's clock."""
        if not self.enabled:
            yield
            return
        sid = self._next_id
        self._next_id += 1
        parent = self._stack[-1] if self._stack else -1
        self._stack.append(sid)
        t0 = time.perf_counter_ns()
        try:
            with self._annotation(name):
                yield
        finally:
            self._stack.pop()
            self._add(Span(name, t0, time.perf_counter_ns(), sid, parent,
                           rid, attrs))

    def begin(self, name: str, rid: int) -> None:
        """Open an async span of one request, closed by ``end`` from any
        later point of the program.  It does not nest in the call stack,
        so it has no parent and no profiler copy."""
        if self.enabled:
            self._open[name, rid] = time.perf_counter_ns()

    def end(self, name: str, rid: int, **attrs) -> None:
        """Close the async span ``begin`` opened; no-op if none is open."""
        if not self.enabled:
            return
        t0 = self._open.pop((name, rid), None)
        if t0 is None:
            return
        sid = self._next_id
        self._next_id += 1
        self._add(Span(name, t0, time.perf_counter_ns(), sid, -1, rid,
                       attrs))

    def _still_open(self) -> list[Span]:
        return [Span(name, t0, None, -1, -1, rid, {})
                for (name, rid), t0 in self._open.items()]

    def spans(self) -> list[Span]:
        """Closed spans in the order they closed, then the async spans
        still open (``end_ns`` None)."""
        return [r for r in self._recs if isinstance(r, Span)] \
            + self._still_open()

    def records(self, kind: Optional[str] = None) -> list[dict]:
        recs = []
        for r in self._recs + self._still_open():
            if isinstance(r, Span):
                d = {"kind": "span", "name": r.name,
                     "start_ns": r.start_ns, "end_ns": r.end_ns,
                     "id": r.id, "parent": r.parent, "rid": r.rid,
                     **r.attrs}
            elif isinstance(r, StepRecord):
                d = {"kind": "step", "step": r.step, "t": round(r.t, 6),
                     "wall_ns": r.wall_ns, **r.row}
            else:
                d = r
            if kind is None or d.get("kind") == kind:
                recs.append(d)
        return recs

    def dump(self) -> str:
        lines = [json.dumps(r, separators=(",", ":"), sort_keys=True)
                 for r in self.records()]
        return "\n".join(lines) + ("\n" if lines else "")

    def close(self) -> None:
        if self.path is not None and self.enabled:
            with open(self.path, "w") as f:
                f.write(self.dump())
