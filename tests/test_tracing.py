"""Program spans and counters of the serving path, on a tiny CPU cluster.

One deterministic trace runs through a real 2-replica ``ClusterFrontend``
with telemetry on and once more with it off.  With it on, every engine
phase span (``repro.engine.*``) nests in the ``repro.driver.execute``
span of its batch, each prefill group and decode scan shows exactly one
``dispatch`` and one ``wait``, the decode counters agree with the scans
the engine ran, and each request has one ``repro.request.queue`` span
that ends when its first prefill batch starts.  With it off nothing is
recorded, the counters still count and the token streams are the same.
"""
import time

import pytest

from repro.core.slo import StageKind
from repro.serving.frontend import QUEUE_SPAN

PHASES = ("prepare", "dispatch", "wait", "post")


@pytest.fixture(scope="module")
def parts():
    import jax

    from repro.configs import get_reduced
    from repro.core.perf_model import cpu_scale_perf_model
    from repro.models import init_params

    cfg = get_reduced("smollm-135m")
    return cfg, init_params(jax.random.PRNGKey(0), cfg), \
        cpu_scale_perf_model()


def _trace(n=8):
    from repro.core.request import simple_request
    return [simple_request(i, 0.04 * i, prompt=6 + 3 * (i % 3),
                           output=4 + 5 * (i % 2), ttft_slowdown=6.0,
                           tpot=0.05 if i % 2 else 0.15)
            for i in range(n)]


def _serve(parts, telemetry):
    """Serve the trace; returns (cluster, token streams, the (n_steps,
    lanes) of every decode scan, and (entry ns, prefill rids) of every
    engine batch)."""
    from repro.core.router import RoutingPolicy, make_real_cluster
    from repro.core.scheduler import SchedulerConfig

    cfg, params, virt = parts
    cl = make_real_cluster(
        2, cfg, params, virt, policy=RoutingPolicy(max_hops=1),
        total_pages=48, replica_pages=16, page_size=4, max_slots=8,
        max_len=64, sched_cfg=SchedulerConfig(
            page_size=4, prefill_emits_first_token=True),
        telemetry=telemetry)
    scans, batches = [], []
    for d in cl.drivers:
        eng = d.engine

        def decode(*a, _orig=eng._decode, **kw):
            scans.append((kw["n_steps"], a[2].shape[0]))
            return _orig(*a, **kw)

        def execute(b, *a, _orig=eng.execute, **kw):
            batches.append((time.perf_counter_ns(), {
                e.rid for e in b.entries if e.kind == StageKind.PREFILL}))
            return _orig(b, *a, **kw)
        eng._decode = decode
        eng.execute = execute
    streams: dict = {}
    for r in _trace():
        cl.submit(r, on_token=lambda rid, toks:
                  streams.setdefault(rid, []).extend(toks))
    cl.run_until_idle()
    return cl, streams, scans, batches


@pytest.fixture(scope="module")
def traced(parts):
    return _serve(parts, True)


def _counter(cl, key):
    return sum(d.engine.counters[key] for d in cl.drivers)


def test_engine_phases_nest_in_their_execute_span(traced):
    cl = traced[0]
    spans = cl.telemetry.tracer.spans()
    by_id = {s.id: s for s in spans}
    engine = [s for s in spans if s.name.startswith("repro.engine.")]
    assert {s.name for s in engine} >= {
        "repro.engine.intake", *(f"repro.engine.{k}.{p}"
                                 for k in ("prefill", "decode")
                                 for p in PHASES),
        "repro.engine.decode.reserve"}
    for s in engine:
        parent = by_id[s.parent]
        assert parent.name == "repro.driver.execute", s
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns


def test_one_dispatch_and_one_wait_per_device_call(traced):
    cl, _, scans, _ = traced
    names = [s.name for s in cl.telemetry.tracer.spans()]
    for kind, calls in (("prefill", _counter(cl, "prefill_calls")),
                        ("decode", _counter(cl, "decode_calls"))):
        assert calls > 0
        for phase in PHASES:
            assert names.count(f"repro.engine.{kind}.{phase}") == calls
    assert len(scans) == _counter(cl, "decode_calls")
    # each wait follows its own dispatch, inside the same parent
    seq = [s for s in cl.telemetry.tracer.spans()
           if s.name.endswith((".dispatch", ".wait"))]
    seq.sort(key=lambda s: s.start_ns)
    for a, b in zip(seq[::2], seq[1::2]):
        assert a.name.endswith(".dispatch") and b.name.endswith(".wait")
        assert a.name.rsplit(".", 1)[0] == b.name.rsplit(".", 1)[0]
        assert a.parent == b.parent and a.end_ns <= b.start_ns


def test_decode_lane_steps_count_the_scans(traced):
    cl, _, scans, _ = traced
    lane_steps = _counter(cl, "decode_lane_steps")
    assert lane_steps == sum(n * b for n, b in scans)
    assert 0 < _counter(cl, "decode_tokens") <= lane_steps
    assert 0 < _counter(cl, "decode_live_lanes") \
        <= sum(b for _, b in scans)
    disp = [s for s in cl.telemetry.tracer.spans()
            if s.name == "repro.engine.decode.dispatch"]
    assert sum(s.attrs["lane_steps"] for s in disp) == lane_steps


def test_one_queue_span_per_request_ending_at_its_first_prefill(traced):
    cl, _, _, batches = traced
    spans = cl.telemetry.tracer.spans()
    queue = [s for s in spans if s.name == QUEUE_SPAN]
    rids = sorted(r.rid for r in _trace())
    assert sorted(s.rid for s in queue) == rids
    execs = [s for s in spans if s.name == "repro.driver.execute"]
    for s in queue:
        assert s.end_ns is not None and s.parent == -1
        first = min(t for t, pre in batches if s.rid in pre)
        assert s.start_ns <= s.end_ns <= first
        # it ends inside the execute span of that batch
        assert any(e.start_ns <= s.end_ns and first <= e.end_ns
                   for e in execs)


def test_telemetry_off_records_nothing_and_serves_the_same(parts, traced):
    cl_on, streams_on, scans_on, _ = traced
    cl, streams, scans, _ = _serve(parts, False)
    assert cl.telemetry.tracer.records() == []
    assert cl.telemetry.tracer.spans() == []
    assert streams == streams_on
    assert scans == scans_on
    for key in ("decode_calls", "decode_tokens", "decode_lane_steps",
                "decode_live_lanes", "prefill_calls"):
        assert _counter(cl, key) == _counter(cl_on, key) > 0
