"""Idle gaps of a traced window labelled by the program's own spans.

With telemetry on, each nested ``repro.*`` span of the program
(``StepTracer.span``) runs under a ``jax.profiler.TraceAnnotation`` of
its name, so the profile holds a copy of it on the host planes, on the
device trace's clock.  ``reduce`` takes the window as
``bench.lib.trace.reduce`` does, finds the device's idle gaps in it, and
labels each by the innermost ``repro.*`` span around its midpoint ("no
span" outside them); the idle seconds per span split a gap that runs
across several spans among them.  It also gives the share of the window
that the
union of the engine's ``*.dispatch`` and ``*.wait`` spans covers: where
the host waits on the device and nothing else, that share and the
device's busy share agree.
"""
from __future__ import annotations

from collections import defaultdict

from bench.lib import program, trace

PREFIX = "repro."


def load(path: str):
    """(``bench.*`` spans, ``repro.*`` spans, and per device plane its
    (module runs, op runs)), each run as (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    bench, spans, devices = [], [], []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    rec = (ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name)
                    if ev.name.startswith(trace.SPAN_PREFIX):
                        bench.append(rec)
                    elif ev.name.startswith(PREFIX):
                        spans.append(rec)
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                     trace.module_name(ev.name))
                    for ev in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                    trace.op_name(ev.name)) for ev in lines["XLA Ops"].events]
            if ops:
                devices.append((mods, ops))
    return bench, spans, devices


def window(bench) -> tuple[int, int]:
    """The traced window in ns, widened by ``trace.SKEW_NS`` on both
    sides, from the harness's spans as ``trace.reduce`` finds it."""
    marks = {s[2]: s for s in bench if s[2] in (trace.OPEN, trace.CLOSE)}
    win = [s for s in bench if s[2] == trace.WINDOW_SPAN]
    if trace.OPEN in marks and trace.CLOSE in marks:
        lo, hi = marks[trace.OPEN][0], marks[trace.CLOSE][1]
        lo = min((s[0] for s in bench
                  if s[2] == trace.STEP_SPAN and s[0] >= lo), default=lo)
    elif win:
        lo, hi = win[0][0], win[0][1]
    else:
        raise ValueError(f"no {trace.WINDOW_SPAN} span")
    return lo - trace.SKEW_NS, hi + trace.SKEW_NS


def _clipped_union(ivs, lo, hi):
    return trace._union([(max(s, lo), min(e, hi)) for s, e in ivs
                         if e > lo and s < hi])


def reduce(path: str, top: int = 10) -> dict:
    bench, spans, devices = load(path)
    lo, hi = window(bench)
    inner = sorted(spans, key=lambda s: s[1] - s[0])

    def label(a, b):
        mid = (a + b) / 2
        for s, e, name in inner:            # innermost first
            if s <= mid <= e:
                return name
        return "no span"

    gaps, busy = [], 0
    for _mods, ops in devices:
        u = _clipped_union([(s, e) for s, e, _ in ops], lo, hi)
        busy += sum(e - s for s, e in u)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps += [(b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                 if b > a]
    n = max(len(devices), 1)
    gaps.sort(reverse=True)
    by_label: dict = defaultdict(float)
    for _, a, b in gaps:
        # a gap can run across several spans (a host phase, then the
        # harness's sleep): each piece goes to its own innermost span
        cuts = sorted({a, b} | {x for s, e, _ in spans for x in (s, e)
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            by_label[label(x, y)] += (y - x) * 1e-9 / n
    calls = _clipped_union([(s, e) for s, e, name in spans
                            if program.is_phase(name, "dispatch", "wait")],
                           lo, hi)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9 / n,
        "dispatch_wait_pct": 100.0 * sum(e - s for s, e in calls)
        / (hi - lo),
        "idle_gaps": [[label(a, b), g * 1e-9] for g, a, b in gaps[:top]],
        "idle_by_program_span": dict(by_label),
    }
