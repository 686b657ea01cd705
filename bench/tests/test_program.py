"""The readers of the program's own spans and counters: their arithmetic
on synthetic span lists, the readers on synthetic runs (and their
silence where the program records nothing), and the readers on a whole
traced run through ``ClusterFrontend`` at a tiny size on the CPU."""
import gc

import pytest

from bench.lib import program, spec
from bench.lib.judge import Outcome
from bench.lib.record import Run
from repro.telemetry import exporters
from repro.telemetry.exporters import Span

MS = 1_000_000
READERS = ("decode_lane_steps_useful_pct", "device_wait_pct",
           "engine_host_pct", "queue_wait_p90_ms")


def sp(name, s, e, rid=None, **attrs):
    return Span(name, s * MS, None if e is None else e * MS, 0, -1, rid,
                attrs)


# a window of 100 ms: two batches, each a decode scan, with host work
# around the device calls, and a plan span that is no engine work
SPANS = [
    sp("repro.driver.plan", 0, 5),
    sp("repro.driver.execute", 5, 45),
    sp("repro.engine.intake", 5, 8),
    sp("repro.engine.decode.prepare", 8, 10),
    sp("repro.engine.decode.dispatch", 10, 12, lane_steps=64),
    sp("repro.engine.decode.wait", 12, 40),
    sp("repro.engine.decode.post", 40, 45),
    sp("repro.driver.execute", 60, 110),
    sp("repro.engine.prefill.dispatch", 62, 63),
    sp("repro.engine.prefill.wait", 63, 70),
    sp("repro.engine.decode.dispatch", 70, 71, lane_steps=32),
    sp("repro.engine.decode.wait", 71, 105),       # past the window's end
    sp("repro.engine.decode.dispatch", 101, 102, lane_steps=999),
]


def test_device_wait_and_engine_host_shares():
    lo, hi = 0, 100 * MS
    # waits 12-40, 63-70 and 71-100 (clipped): 28 + 7 + 29 ms
    assert program.device_wait_pct(SPANS, lo, hi) == pytest.approx(64.0)
    # execute 5-45 and 60-100 (80 ms) less dispatch and wait (10-40,
    # 62-100: 68 ms) leaves 12 ms of the engine's own host work
    assert program.engine_host_pct(SPANS, lo, hi) == pytest.approx(12.0)
    assert program.device_wait_pct(SPANS, lo, hi) \
        + program.engine_host_pct(SPANS, lo, hi) <= 100.0


def test_lane_steps_of_the_scans_dispatched_in_the_window():
    assert program.lane_steps(SPANS, 0, 100 * MS) == 96
    assert program.lane_steps(SPANS, 0, 110 * MS) == 96 + 999


def test_overlap_of_unions():
    a = [[0, 10], [20, 30]]
    b = [[5, 25], [28, 40]]
    assert program.overlap(a, b) == 5 + 5 + 2
    assert program.overlap(a, []) == 0


def outcome(rid, due):
    return Outcome(rid=rid, cls="a", due=due, prompt_len=8, out_len=4,
                   ttft_limit=1.0, tpot_limit=0.1, submitted=due)


def test_queue_waits_count_up_to_the_window_end_not_the_drain():
    spans = [sp(program.QUEUE, 10, 30, rid=1),
             sp(program.QUEUE, 20, None, rid=2),                 # queued
             sp(program.QUEUE, 30, 60, rid=3, outcome="dropped"),
             sp(program.QUEUE, 40, 150, rid=4),     # started after t_end
             sp(program.QUEUE, 0, 5, rid=9)]               # not due here
    outs = [outcome(r, 0.0) for r in (1, 2, 3, 4, 5)]   # 5: no span
    w = program.queue_waits_s(spans, outs, 0, 100 * MS)
    assert w == pytest.approx([0.02, 0.08, 0.07, 0.06])
    assert program.queue_waits_s(spans, [outcome(5, 0.0)], 0, 100 * MS) \
        == []


class FakeTracer:
    def __init__(self, spans, dropped=0):
        self._spans, self.dropped = spans, dropped

    def spans(self):
        return self._spans


def run_of(t0_ms, t_end_ms, outs=(), decode_tokens=0):
    return Run(outs=list(outs), t0=t0_ms / 1e3, t_end=t_end_ms / 1e3,
               t_drain=t_end_ms / 1e3 + 100.0, setup_s=1.0,
               counters={"decode_tokens": decode_tokens}, plan=None,
               trace=None, family=None, dims=None, peaks={})


def read_all(run):
    return {m: spec.reader(m)(run) for m in READERS}


def test_readers_on_a_synthetic_run(monkeypatch):
    spans = SPANS + [sp(program.QUEUE, 1, 61, rid=1),
                     sp(program.QUEUE, 2, None, rid=2)]
    monkeypatch.setattr(exporters, "live_tracers",
                        lambda: [FakeTracer(spans)])
    got = read_all(run_of(0, 100, [outcome(1, 0.0), outcome(2, 0.0)],
                          decode_tokens=24))
    assert got["decode_lane_steps_useful_pct"] == pytest.approx(25.0)
    assert got["device_wait_pct"] == pytest.approx(64.0)
    assert got["engine_host_pct"] == pytest.approx(12.0)
    # waits 60 ms and 98 ms (to t_end): p90 by linear interpolation
    assert got["queue_wait_p90_ms"] == pytest.approx(60 + 0.9 * 38)


def test_readers_are_silent_without_program_spans(monkeypatch):
    run = run_of(0, 100, [outcome(1, 0.0)], decode_tokens=24)
    # a program with no live tracers (telemetry off)
    monkeypatch.setattr(exporters, "live_tracers", lambda: [])
    assert read_all(run) == dict.fromkeys(READERS)
    # spans from another window only
    monkeypatch.setattr(exporters, "live_tracers",
                        lambda: [FakeTracer([sp("repro.driver.execute",
                                                200, 300)])])
    assert read_all(run) == dict.fromkeys(READERS)
    # a tracer that dropped records covers part of the window
    monkeypatch.setattr(exporters, "live_tracers",
                        lambda: [FakeTracer(SPANS, dropped=1)])
    assert read_all(run) == dict.fromkeys(READERS)
    # two live tracers: the spans of one may be another stack's
    monkeypatch.setattr(exporters, "live_tracers",
                        lambda: [FakeTracer(SPANS), FakeTracer(SPANS)])
    assert read_all(run) == dict.fromkeys(READERS)
    # a program that predates the tracer registry
    monkeypatch.delattr(exporters, "live_tracers")
    assert read_all(run) == dict.fromkeys(READERS)


def test_readers_on_a_traced_run_through_the_cluster():
    """The harness's own traced path at a tiny size: telemetry on, the
    profiler over the window, then the readers as ``cell_run`` calls
    them."""
    from bench import run as bench_run
    from bench.tests.test_harness import FAM, SEED, TINY, TRAFFIC
    gc.collect()            # the tracers of earlier tests' clusters
    assert exporters.live_tracers() == []
    res = bench_run.serve(TINY, FAM, TRAFFIC, 2.0, 3.0, SEED, "tiny", 0,
                          True, full=False)
    run = Run(outs=res["outs"], t0=res["t0"], t_end=res["t_end"],
              t_drain=res["t_drain"], setup_s=1.0,
              counters=res["counters"], plan=res["plan"],
              trace=res["trace"], family=FAM, dims=res["dims"], peaks={},
              calls=res["calls"], t_trace=res["t_trace"])
    got = read_all(run)
    assert 0 < got["decode_lane_steps_useful_pct"] <= 100
    assert 0 < got["device_wait_pct"] < 100
    assert 0 < got["engine_host_pct"] < 100
    assert got["device_wait_pct"] + got["engine_host_pct"] <= 100
    window_ms = 1000 * (run.t_end - run.t0)
    assert 0 <= got["queue_wait_p90_ms"] <= window_ms
    spans = program.spans(run)
    queued = {s.rid for s in spans if s.name == program.QUEUE}
    assert queued >= {o.rid for o in run.outs}
