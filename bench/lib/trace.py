"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics and the ``breakdown`` read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run, named by its HLO instruction
(``%paged_decode_attention.1 = f32[...] custom-call(...)``), and their
``XLA Modules`` line one event per program run.  Host spans are the
harness's own ``jax.profiler.TraceAnnotation`` names, all starting with
``bench.``.  The traced window runs from the first ``bench.step`` that
starts after the instant span ``bench.window.open`` (the profiler starts
mid-step; the step in flight is not counted) to the end of
``bench.window.close``; in a trace without them it is the span
``bench.window``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPEN, CLOSE = WINDOW_SPAN + ".open", WINDOW_SPAN + ".close"
STEP_SPAN = "bench.step"
# The device's clock reads up to about a millisecond off the host's in
# these traces (a kernel can appear to start before the host launched
# it), so the window reaches this far past the host span on both sides.
# The harness keeps the device idle there: the system is empty when the
# window opens and the trace stops when it closes.
SKEW_NS = 5_000_000
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] ...`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].lstrip("%").strip()
    return _SUFFIX.sub("", head)


def module_name(event_name: str) -> str:
    """``jit__decode_scan(1234)`` -> ``jit__decode_scan``."""
    return event_name.split("(", 1)[0]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops):
    """(start, end, name, self seconds) of each op: an op that encloses
    others (a ``while`` around a loop body) keeps only its own time."""
    out = []
    stack = []
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, name, e - s]
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        out.append(rec)
        stack.append(rec)
    return out


def reduce(path: str, top: int = 10) -> dict:
    """Busy and window seconds (averaged over the device planes), device
    seconds per operation name, the ``breakdown`` lists, and the count of
    device planes that ran anything."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    spans = []
    devices = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           module_name(ev.name))
                          for ev in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                    op_name(ev.name)) for ev in lines["XLA Ops"].events]
            if ops:
                devices.append((mods, ops))
    marks = {s[2]: s for s in spans if s[2] in (OPEN, CLOSE)}
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if OPEN in marks and CLOSE in marks:
        lo, hi = marks[OPEN][0], marks[CLOSE][1]
        lo = min((s[0] for s in spans
                  if s[2] == STEP_SPAN and s[0] >= lo), default=lo)
    elif win:
        lo, hi = win[0][0], win[0][1]
    else:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    lo, hi = lo - SKEW_NS, hi + SKEW_NS
    inner = sorted((s for s in spans
                    if s[2] not in (WINDOW_SPAN, OPEN, CLOSE)),
                   key=lambda s: s[1] - s[0])
    op_s: dict = defaultdict(float)
    mod_op_s: dict = defaultdict(float)
    busy = 0.0
    gaps = []
    for mods, ops in devices:
        starts = [m[0] for m in mods]
        clipped = []
        for s, e, name, own in self_times(ops):
            if e <= lo or s >= hi:
                continue
            clipped.append((max(s, lo), min(e, hi)))
            op_s[name] += own * 1e-9
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            mod_op_s[f"{mod}/{name}"] += own * 1e-9
        u = _union(clipped)
        busy += sum(e - s for s, e in u) * 1e-9
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    n = max(len(devices), 1)

    def label(a, b):
        mid = (a + b) / 2
        for s, e, name in inner:            # innermost first
            if s <= mid <= e:
                return name
        return "no span"

    gaps.sort(reverse=True)
    by_label: dict = defaultdict(float)
    for g, a, b in gaps:
        by_label[label(a, b)] += g * 1e-9
    return {
        "busy_s": busy / n,
        "window_s": (hi - lo) * 1e-9,
        "devices": len(devices),
        "op_s": {k: v / n for k, v in op_s.items()},
        "device_ops": sorted(([k, v / n] for k, v in mod_op_s.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label(a, b), g * 1e-9] for g, a, b in gaps[:top]],
        "idle_by_span": dict(by_label),
    }
