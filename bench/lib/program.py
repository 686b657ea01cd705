"""What the program's own telemetry recorded over a run's window.

In a traced run the cluster's telemetry is on, and its ``StepTracer``
keeps every ``repro.*`` span in memory on ``time.perf_counter_ns``, the
clock of the run's ``t0`` and ``t_end``.  The run record does not carry
the tracer yet, so the readers take the process's one live tracer
(``repro.telemetry.exporters.live_tracers``).  A program without that
registry, a run with telemetry off, more than one live tracer (whose
spans would be another stack's too), or a tracer that dropped records
(its spans would cover part of the window) gives None.

The spans the readers use, all from ``serving/frontend.py`` and
``serving/engine.py``: ``repro.driver.execute`` around each engine batch;
inside it ``repro.engine.<kind>.dispatch`` (the jitted call) and
``repro.engine.<kind>.wait`` (the host blocked on its result) per
prefill group and decode scan, the decode dispatch carrying the scan's
``lane_steps`` as an attribute; and one ``repro.request.queue`` per request, from its
submission to the first batch that prefills it.
"""
from __future__ import annotations

from typing import Optional

from bench.lib.trace import _union

EXECUTE = "repro.driver.execute"
DECODE_DISPATCH = "repro.engine.decode.dispatch"
QUEUE = "repro.request.queue"


def window_ns(run) -> tuple[int, int]:
    return round(run.t0 * 1e9), round(run.t_end * 1e9)


def spans(run) -> Optional[list]:
    """The spans of the one live tracer that overlap the run's window (an
    async span still open counts as open to the window's end), or None."""
    try:
        from repro.telemetry.exporters import live_tracers
    except ImportError:
        return None
    tracers = live_tracers()
    if len(tracers) != 1 or tracers[0].dropped:
        return None
    lo, hi = window_ns(run)
    out = [s for s in tracers[0].spans()
           if s.start_ns <= hi and (s.end_ns is None or s.end_ns >= lo)]
    return out or None


def is_phase(name: str, *phases: str) -> bool:
    """An engine span of one of ``phases`` (``wait``, ``dispatch``, ..),
    of a prefill group or a decode scan."""
    return name.startswith("repro.engine.") \
        and name.rsplit(".", 1)[-1] in phases


def union(spans, keep, lo: int, hi: int) -> list:
    """Union of the closed spans whose name ``keep`` accepts, clipped to
    [lo, hi]: sorted disjoint [start, end] intervals."""
    return _union([(max(s.start_ns, lo), min(s.end_ns, hi)) for s in spans
                   if s.end_ns is not None and keep(s.name)
                   and s.start_ns < hi and s.end_ns > lo])


def length(u) -> int:
    return sum(e - s for s, e in u)


def overlap(a, b) -> int:
    """Length of the intersection of two unions."""
    i = j = n = 0
    while i < len(a) and j < len(b):
        n += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return n


def device_wait_pct(spans, lo: int, hi: int) -> float:
    """Share of [lo, hi] in which the host waited on a device result."""
    return 100.0 * length(union(
        spans, lambda n: is_phase(n, "wait"), lo, hi)) / (hi - lo)


def engine_host_pct(spans, lo: int, hi: int) -> float:
    """Share of [lo, hi] inside an engine batch but outside its device
    calls' dispatch and wait: the engine's own host work."""
    ex = union(spans, lambda n: n == EXECUTE, lo, hi)
    dw = union(spans, lambda n: is_phase(n, "dispatch", "wait"), lo, hi)
    return 100.0 * (length(ex) - overlap(ex, dw)) / (hi - lo)


def lane_steps(spans, lo: int, hi: int) -> int:
    """Lane-steps of the decode scans dispatched in [lo, hi]."""
    return sum(s.attrs["lane_steps"] for s in spans
               if s.name == DECODE_DISPATCH and lo <= s.start_ns <= hi)


def queue_waits_s(spans, outs, lo: int, hi: int) -> list[float]:
    """Per request of ``outs`` that has a queue span: submission to the
    start of its first prefill batch, or to ``hi`` where that did not
    come by then (still queued, dropped or cancelled)."""
    by_rid = {s.rid: s for s in spans if s.name == QUEUE}
    out = []
    for o in outs:
        s = by_rid.get(o.rid)
        if s is None:
            continue
        started = s.end_ns is not None and s.end_ns <= hi \
            and "outcome" not in s.attrs
        out.append(((s.end_ns if started else hi) - s.start_ns) * 1e-9)
    return out
