"""Telemetry subsystem: metrics registry, ring-buffer series, exporters,
and the attainment-driven autoscaler.

Covers (a) registry semantics — histogram bucket/percentile correctness
against a numpy reference, label isolation, counter monotonicity, the
zero-overhead disabled mode; (b) ring-buffer wraparound and windowed
aggregates; (c) the Prometheus text-exposition round trip and
histogram_quantile readout; (d) the JSONL step tracer; (e) autoscaler
hysteresis on a stub cluster; and (f) end-to-end: a deterministic trace
through a REAL 2-replica cluster whose Prometheus dump and step trace
must agree with the final ClusterStats, plus drain-with-migration
continuity of the token stream."""
import json
import math

import numpy as np
import pytest

from repro.telemetry import (Autoscaler, AutoscalerConfig, MetricsRegistry,
                             RingBuffer, StepTracer, TimeSeriesSampler,
                             parse_prometheus, prometheus_text,
                             quantile_from_exposition)
from repro.telemetry.instruments import ClusterTelemetry
from repro.telemetry.registry import _NOOP


# --------------------------- (a) registry ------------------------------- #
def test_counter_monotone_and_set_total():
    r = MetricsRegistry(enabled=True)
    c = r.counter("x_total", "", ("k",))
    c.labels(k="a").inc(2)
    c.labels(k="a").inc()
    assert c.labels(k="a").value == 3
    with pytest.raises(ValueError):
        c.labels(k="a").inc(-1)
    c.labels(k="b").set_total(7)       # pull-mirrored external counter
    c.labels(k="b").set_total(9)
    with pytest.raises(ValueError):
        c.labels(k="b").set_total(5)   # regression must be loud


def test_label_isolation_and_schema_enforcement():
    r = MetricsRegistry(enabled=True)
    c = r.counter("y_total", "", ("a", "b"))
    c.labels(a="1", b="1").inc(5)
    c.labels(a="1", b="2").inc(1)
    assert c.labels(a="1", b="1").value == 5
    assert c.labels(a="1", b="2").value == 1
    with pytest.raises(ValueError):
        c.labels(a="1")                # missing label
    with pytest.raises(ValueError):
        r.gauge("y_total")             # type conflict on re-register
    with pytest.raises(ValueError):
        r.counter("y_total", "", ("a",))   # label-schema conflict
    assert r.counter("y_total", "", ("a", "b")) is c   # idempotent


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-3.0, sigma=1.0, size=5000)
    bounds = np.logspace(-4, 1, 60)    # fine buckets -> tight estimate
    r = MetricsRegistry(enabled=True)
    h = r.histogram("lat_seconds", "", buckets=bounds.tolist())
    child = h.labels()
    for v in samples:
        child.observe(float(v))
    for q in (0.5, 0.9, 0.99):
        est = child.quantile(q)
        ref = float(np.quantile(samples, q))
        # linear-in-bucket interpolation is exact to bucket resolution:
        # the estimate must land within the bucket containing ref
        i = int(np.searchsorted(bounds, ref))
        lo = bounds[i - 1] if i > 0 else 0.0
        hi = bounds[i] if i < len(bounds) else samples.max()
        assert lo <= est <= hi * 1.0001, (q, est, ref, lo, hi)
    assert child.count == len(samples)
    assert child.sum == pytest.approx(samples.sum())


def test_histogram_quantile_edge_cases():
    r = MetricsRegistry(enabled=True)
    h = r.histogram("h", "", buckets=(1.0, 2.0))
    assert math.isnan(h.labels().quantile(0.5))      # empty
    h.observe(5.0)                                   # overflow bucket only
    assert h.labels().quantile(0.5) == 5.0           # observed extremum
    h.observe(0.5)
    assert 0.0 <= h.labels().quantile(0.0) <= 1.0


def test_disabled_registry_is_noop_and_shared():
    r = MetricsRegistry(enabled=False)
    c = r.counter("x_total", "", ("k",))
    h = r.histogram("h", "")
    assert c.labels(k="a") is _NOOP        # one shared child, no state
    assert h.labels() is _NOOP
    c.labels(k="a").inc(5)
    h.labels().observe(1.0)
    assert list(c.samples()) == []         # nothing recorded
    # exposition is well-formed but empty of samples
    txt = prometheus_text(r)
    assert "# TYPE x_total counter" in txt
    assert "x_total{" not in txt


def test_metrics_enabled_env(monkeypatch):
    from repro.telemetry import metrics_enabled
    monkeypatch.delenv("REPRO_METRICS", raising=False)
    assert metrics_enabled() is False
    monkeypatch.setenv("REPRO_METRICS", "1")
    assert metrics_enabled() is True
    assert MetricsRegistry().enabled is True
    monkeypatch.setenv("REPRO_METRICS", "0")
    assert MetricsRegistry().enabled is False


# ------------------------ (b) ring buffer ------------------------------- #
def test_ring_buffer_wraparound_preserves_order():
    rb = RingBuffer(capacity=4)
    for i in range(10):
        rb.push(float(i), float(i * i))
    assert len(rb) == 4
    assert rb.items() == [(6.0, 36.0), (7.0, 49.0), (8.0, 64.0),
                          (9.0, 81.0)]
    assert rb.last() == (9.0, 81.0)
    assert rb.window_mean(2) == pytest.approx((64 + 81) / 2)
    assert rb.window_max(4) == 81.0
    assert rb.window_mean(100) == pytest.approx((36 + 49 + 64 + 81) / 4)


def test_ring_buffer_empty_and_sampler():
    rb = RingBuffer(capacity=3)
    assert math.isnan(rb.window_mean(2)) and rb.last() is None
    s = TimeSeriesSampler(capacity=3)
    s.add_source("x", lambda: 42.0)
    row = s.sample(1.0)
    assert row == {"x": 42.0}
    s.push("y", 1.0, 7.0)
    assert s.get("x").last() == (1.0, 42.0)
    assert s.get("y").values() == [7.0]


# ------------------------- (c) exporters -------------------------------- #
def test_prometheus_round_trip_with_escaping():
    r = MetricsRegistry(enabled=True)
    c = r.counter("a_total", 'help with "quotes"', ("cls",))
    c.labels(cls='tp="0.05",x').inc(3)
    g = r.gauge("g", "", ("r",))
    g.labels(r="0").set(0.25)
    h = r.histogram("h_seconds", "", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(2.0)
    parsed = parse_prometheus(prometheus_text(r))
    assert parsed[("a_total", (("cls", 'tp="0.05",x'),))] == 3.0
    assert parsed[("g", (("r", "0"),))] == 0.25
    assert parsed[("h_seconds_bucket", (("le", "0.1"),))] == 1.0
    assert parsed[("h_seconds_bucket", (("le", "1"),))] == 2.0
    assert parsed[("h_seconds_bucket", (("le", "+Inf"),))] == 3.0
    assert parsed[("h_seconds_count", ())] == 3.0
    assert parsed[("h_seconds_sum", ())] == pytest.approx(2.55)
    q = quantile_from_exposition(parsed, "h_seconds", 0.5)
    assert 0.1 <= q <= 1.0


def test_step_tracer_records_and_span(tmp_path):
    path = tmp_path / "trace.jsonl"
    tr = StepTracer(path=str(path))
    tr.step(0, 1.5, {"replicas": 2.0})
    with tr.span("repro.driver.plan", replica=0):
        with tr.span("repro.engine.intake", rid=7):
            pass
    tr.close()
    recs = tr.records()
    wall = recs[0].pop("wall_ns")
    assert recs[0] == {"kind": "step", "step": 0, "t": 1.5,
                       "replicas": 2.0}
    inner, outer = recs[1], recs[2]        # in the order they closed
    assert outer["kind"] == "span" and outer["name"] == "repro.driver.plan"
    assert outer["replica"] == 0 and outer["parent"] == -1
    assert outer["rid"] is None
    assert inner["parent"] == outer["id"] and inner["rid"] == 7
    assert wall <= outer["start_ns"] <= inner["start_ns"] \
        <= inner["end_ns"] <= outer["end_ns"]
    recs[0]["wall_ns"] = wall
    on_disk = [json.loads(line) for line in
               path.read_text().strip().splitlines()]
    assert on_disk == recs
    off = StepTracer(enabled=False)
    off.step(0, 0.0, {})
    assert off.records() == []


# ------------------------- (e) autoscaler ------------------------------- #
class _StubDriver:
    def __init__(self, idx):
        self.idx = idx
        self.running, self.new_q, self.be = [], [], []


class _StubCluster:
    def __init__(self, n):
        self.drivers = [_StubDriver(i) for i in range(n)]
        self.draining = set()
        self.ups, self.drains = 0, []

    def add_replica(self):
        self.ups += 1
        self.drivers.append(_StubDriver(len(self.drivers)))

    def drain_replica(self, i):
        self.drains.append(i)
        d = self.drivers[i]
        self.draining.add(d.idx)
        self.drivers.remove(d)
        self.draining.discard(d.idx)


def _scaler(**kw):
    tel = ClusterTelemetry(enabled=True)
    cfg = AutoscalerConfig(min_replicas=1, max_replicas=3, window=2,
                           up_cooldown=0.1, down_cooldown=0.5,
                           down_patience=3, min_finished=2, **kw)
    return Autoscaler(tel, cfg), tel


def test_autoscaler_scales_up_on_attainment_and_respects_cooldown():
    sc, tel = _scaler()
    cl = _StubCluster(1)
    for _ in range(4):                     # windowed attainment 0.0
        tel.note_finish("tpot=0.05", False)
    tel.sampler.push("page_pressure", 0.0, 0.1)
    tel.sampler.push("queue_depth", 0.0, 0.0)
    d = sc.step(cl, 1.0)
    assert d is not None and d.action == "up" and cl.ups == 1
    assert sc.step(cl, 1.05) is None       # inside up_cooldown
    d = sc.step(cl, 1.3)                   # cooldown expired, still failing
    assert d is not None and cl.ups == 2
    assert len(cl.drivers) == 3
    sc.step(cl, 1.5)
    assert len(cl.drivers) == 3            # max_replicas cap


def test_autoscaler_scales_up_on_leading_signals():
    sc, tel = _scaler()
    cl = _StubCluster(1)                   # no finished requests at all
    tel.sampler.push("page_pressure", 0.0, 0.99)
    tel.sampler.push("queue_depth", 0.0, 0.0)
    d = sc.step(cl, 1.0)
    assert d is not None and "pressure" in d.reason
    tel2 = ClusterTelemetry(enabled=True)
    sc2 = Autoscaler(tel2, sc.cfg)
    cl2 = _StubCluster(1)
    tel2.sampler.push("page_pressure", 0.0, 0.1)
    tel2.sampler.push("queue_depth", 0.0, 50.0)
    d = sc2.step(cl2, 1.0)
    assert d is not None and "backlog" in d.reason


def test_autoscaler_scale_down_needs_patience_and_quiet():
    sc, tel = _scaler()
    cl = _StubCluster(3)
    for _ in range(4):
        tel.note_finish("tpot=0.05", True)     # attainment 1.0
    t = 1.0
    downs = []
    for i in range(8):
        tel.sampler.push("page_pressure", t, 0.1)
        tel.sampler.push("queue_depth", t, 0.0)
        d = sc.step(cl, t)
        if d is not None:
            downs.append((i, d))
        t += 0.3
    # first drain only after down_patience quiet steps + down_cooldown,
    # and the next one needs the full patience run again (hysteresis)
    assert len(downs) == 1 or (len(downs) == 2
                               and downs[1][0] - downs[0][0] >= 3)
    assert all(d.action == "down" for _, d in downs)
    # a pressure spike resets the quiet streak
    sc2, tel2 = _scaler()
    cl2 = _StubCluster(2)
    for _ in range(4):
        tel2.note_finish("c", True)
    t = 1.0
    for i in range(6):
        spike = 0.95 if i == 2 else 0.1
        tel2.sampler.push("page_pressure", t, spike)
        tel2.sampler.push("queue_depth", t, 0.0)
        sc2.step(cl2, t)
        t += 0.3
    # the window_max over 2 samples keeps the spike visible one extra
    # step, so only 3 clean quiet steps exist at the end: no drain (the
    # spike both reset the streak and may trigger an up)
    assert cl2.drains == []


def test_autoscaler_never_drains_last_live_replica():
    sc, tel = _scaler()
    cl = _StubCluster(1)
    for _ in range(4):
        tel.note_finish("c", True)
    t = 1.0
    for _ in range(10):
        tel.sampler.push("page_pressure", t, 0.0)
        tel.sampler.push("queue_depth", t, 0.0)
        sc.step(cl, t)
        t += 0.3
    assert cl.drains == [] and len(cl.drivers) == 1


# ---------------- (f) end-to-end on a real cluster ---------------------- #
@pytest.fixture(scope="module")
def tiny_cluster_parts():
    import jax

    from repro.configs import get_reduced
    from repro.core.perf_model import cpu_scale_perf_model
    from repro.models import init_params

    cfg = get_reduced("smollm-135m")
    return cfg, init_params(jax.random.PRNGKey(0), cfg), \
        cpu_scale_perf_model()


def _cluster(parts, n=2, **kw):
    from repro.core.router import RoutingPolicy, make_real_cluster
    from repro.core.scheduler import SchedulerConfig

    cfg, params, virt = parts
    defaults = dict(
        policy=RoutingPolicy(max_hops=1),
        total_pages=48, replica_pages=16, page_size=4,
        max_slots=8, max_len=64,
        sched_cfg=SchedulerConfig(page_size=4,
                                  prefill_emits_first_token=True),
        telemetry=True)
    defaults.update(kw)
    return make_real_cluster(n, cfg, params, virt, **defaults)


def _two_class_trace(n=6):
    from repro.core.request import simple_request
    return [simple_request(i, 0.05 * i, prompt=8, output=6,
                           ttft_slowdown=6.0,
                           tpot=0.05 if i % 2 else 0.15)
            for i in range(n)]


def test_e2e_prometheus_matches_cluster_stats(tiny_cluster_parts):
    """Acceptance: on a deterministic trace, the Prometheus dump and the
    JSONL step trace must agree with the final ClusterStats — per-class
    attainment, terminal counts, and the page-pressure series."""
    cl = _cluster(tiny_cluster_parts)
    for r in _two_class_trace():
        cl.submit(r)
    stats = cl.run_until_idle()
    assert stats.served == stats.submitted == 6

    parsed = parse_prometheus(cl.telemetry.prometheus())
    fin = {k: v for k, v in parsed.items()
           if k[0] == "repro_requests_finished_total"}
    assert sum(fin.values()) == stats.served
    att = sum(v for k, v in fin.items() if ("attained", "true") in k[1])
    assert att == stats.attained
    # per-class attainment readout agrees with the counter samples
    pc = cl.telemetry.per_class_attainment()
    assert set(pc) == {"tpot=0.05", "tpot=0.15"}
    for cls, frac in pc.items():
        tot = sum(v for k, v in fin.items() if ("slo_class", cls) in k[1])
        yes = sum(v for k, v in fin.items()
                  if ("slo_class", cls) in k[1]
                  and ("attained", "true") in k[1])
        assert frac == pytest.approx(yes / tot)
    # TTFT histogram exists per class and its quantile is finite
    q = quantile_from_exposition(parsed, "repro_ttft_seconds", 0.9,
                                 slo_class="tpot=0.05")
    assert math.isfinite(q) and q >= 0.0
    # routing/engine mirrors stayed consistent with ClusterStats
    assert parsed[("repro_routing_total", (("outcome", "best_effort"),))] \
        == stats.best_effort
    # step trace carries attainment + page-pressure series
    steps = cl.telemetry.tracer.records("step")
    assert steps, "no step records"
    assert all("page_pressure" in r and "budget_used_ratio" in r
               for r in steps)
    assert any("attain[tpot=0.05]" in r for r in steps)
    last = steps[-1]
    assert last["served_total"] == stats.served
    assert last["attained_total"] == stats.attained
    # span records cover the plan/execute phases
    names = {r["name"] for r in cl.telemetry.tracer.records("span")}
    assert "repro.driver.plan" in names and "repro.driver.execute" in names
    # as_dict carries the guarded ratios
    d = stats.as_dict()
    assert d["attainment"] == pytest.approx(stats.attained / stats.served)
    assert 0.0 <= d["prefix_hit_rate"] <= 1.0


def test_metrics_disabled_changes_nothing(tiny_cluster_parts):
    """Zero-overhead-when-disabled also means zero behavior change: the
    served/attained outcome of a deterministic trace is identical with
    telemetry on and off, and the disabled run records nothing."""
    outcomes = []
    for enabled in (True, False):
        cl = _cluster(tiny_cluster_parts, telemetry=enabled)
        streams = {}
        for r in _two_class_trace():
            cl.submit(r, on_token=lambda rid, toks:
                      streams.setdefault(rid, []).extend(toks))
        s = cl.run_until_idle()
        outcomes.append((s.served, s.attained, s.tokens_out,
                         tuple(sorted((k, tuple(v))
                                      for k, v in streams.items()))))
        if not enabled:
            assert cl.telemetry.tracer.records() == []
            assert cl.telemetry.sampler.n_samples == 0
    assert outcomes[0] == outcomes[1]


def test_drain_migrates_best_effort_with_identical_stream(
        tiny_cluster_parts):
    """drain_replica moves a mid-flight best-effort request to a live
    peer via preempt + drop/restore; the recompute replay must continue
    the token stream exactly (greedy determinism contract)."""
    from repro.core.request import simple_request

    # reference stream: same request served without any drain
    def run(drain):
        cl = _cluster(tiny_cluster_parts, n=2)
        toks = {}
        be_req = simple_request(100, 0.0, prompt=12, output=8,
                                ttft_slowdown=6.0, tpot=0.15)
        # force best-effort demotion: every verdict declines
        saved = [d.verdict for d in cl.drivers]
        for d in cl.drivers:
            d.verdict = lambda now, req, prompt=None: False
        cl.submit(be_req, on_token=lambda rid, t:
                  toks.setdefault(rid, []).extend(t))
        cl.step()
        for d, v in zip(cl.drivers, saved):
            d.verdict = v
        src = next(d for d in cl.drivers if len(d.be))
        if drain:
            # partially serve, then drain the replica holding the BE work
            for _ in range(2):
                cl.step()
            cl.drain_replica(cl.drivers.index(src))
            assert not len(src.be), "BE entry did not migrate"
        cl.run_until_idle()
        return toks.get(100, []), be_req.finished

    ref, ref_fin = run(drain=False)
    mig, mig_fin = run(drain=True)
    assert ref_fin and mig_fin
    assert ref == mig, "migrated stream diverged from reference"


def test_drained_replica_retires_and_stats_survive(tiny_cluster_parts):
    cl = _cluster(tiny_cluster_parts, n=2)
    for r in _two_class_trace(4):
        cl.submit(r)
    served_before = cl.run_until_idle().served
    assert served_before == 4
    cl.add_replica()
    assert len(cl.drivers) == 3
    cl.drain_replica(0)
    for _ in range(30):
        cl.step()
        if len(cl.drivers) == 2:
            break
    assert len(cl.drivers) == 2 and not cl.draining
    s = cl.stats
    assert s.served == served_before       # retired stats retained
    assert s.attainment == pytest.approx(s.attained / s.served)
    # budget conservation after retirement
    assert cl.budget.used == sum(d.engine.kv.used_pages
                                 for d in cl.drivers)


# ------------------ wall-clock export mode (gateway) -------------------- #
def test_sampler_wall_mode_values_identical_timestamps_wall():
    """wall_clock=True mirrors every push into a wall-timestamped ring:
    values (and the virtual rings the autoscaler reads) are identical to
    a virtual-only sampler; only the exported timestamps differ."""
    fake_now = [1000.0]
    virt = TimeSeriesSampler(capacity=8)
    wall = TimeSeriesSampler(capacity=8, wall_clock=True,
                             clock=lambda: fake_now[0])
    for i in range(12):                      # exercise wraparound too
        fake_now[0] += 0.5
        for s in (virt, wall):
            s.push("q", i * 0.1, float(i))
    assert wall.series["q"].items() == virt.series["q"].items()
    assert wall.series["q"].values() == wall.wall["q"].values()
    assert [t for t, _ in wall.wall["q"].items()] == \
        [1000.0 + 0.5 * (i + 1) for i in range(4, 12)]
    # last_time: exported base is wall when enabled, virtual otherwise
    assert virt.last_time("q") == pytest.approx(1.1)
    assert wall.last_time("q") == pytest.approx(1006.0)
    assert virt.last_time("missing") is None


def test_timeseries_prometheus_virtual_and_wall_consistent():
    """The exposition from the two modes must carry identical values per
    series; only the ``_timestamp`` series differs (virtual seconds vs
    wall epoch)."""
    from repro.telemetry import timeseries_prometheus_text

    virt = TimeSeriesSampler(capacity=8)
    wall = TimeSeriesSampler(capacity=8, wall_clock=True,
                             clock=lambda: 2_000_000_000.0)
    for s in (virt, wall):
        s.add_source("a", lambda: 3.5)
        s.add_source("b", lambda: 7.0)
        s.sample(0.25)
        s.sample(0.50)
    pv = parse_prometheus(timeseries_prometheus_text(virt))
    pw = parse_prometheus(timeseries_prometheus_text(wall))
    for name in ("a", "b"):
        key = ("repro_step_series", (("series", name),))
        tkey = ("repro_step_series_timestamp", (("series", name),))
        assert pv[key] == pw[key]            # values identical
        assert pv[tkey] == pytest.approx(0.50)       # virtual seconds
        assert pw[tkey] == pytest.approx(2_000_000_000.0)  # wall epoch
    assert timeseries_prometheus_text(TimeSeriesSampler()) == ""


def test_cluster_telemetry_wall_mode_flag_reaches_sampler():
    tel = ClusterTelemetry(enabled=True, wall_clock=True)
    assert tel.sampler.wall_clock
    tel.sampler.push("x", 0.1, 1.0)
    (t, v), = tel.sampler.wall["x"].items()
    assert v == 1.0 and t > 1e9              # real epoch timestamp
    assert tel.sampler.series["x"].items() == [(0.1, 1.0)]
