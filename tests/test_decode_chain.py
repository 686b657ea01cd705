"""Decode step budgets past the largest step bucket (256) run as a chain
of <= 256-step scans, one host sync each, on the reduced smollm-135m
engine at temperature 0.

A 300-step lane runs scans of 256 and 64 steps and emits the stream of
300 one-step decode calls; lanes that finished (budget or EOS) drop out
between scans; the counters and dispatch spans count each scan; and for
every budget up to ``max_len`` each scan's step count is a bucket, with
one scan for any budget that fits one.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.batch import Batch
from repro.core.slo import StageKind
from repro.models import init_params
from repro.serving.engine import (EngineConfig, ServingEngine, _DECODE_STEPS,
                                  _bucket)
from repro.telemetry.exporters import StepTracer

ECFG = dict(max_slots=4, max_len=512, page_size=16, total_pages=96)


@pytest.fixture(scope="module")
def parts():
    cfg = get_reduced("smollm-135m")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def make_engine(parts):
    return ServingEngine(*parts, EngineConfig(**ECFG))


def _engine(parts, prompts):
    """An engine with each of ``prompts`` ({rid: prompt}) prefilled, and
    ``eng._decode`` wrapped to record (n_steps, lanes, steps) per scan."""
    eng = make_engine(parts)
    for rid, prompt in prompts.items():
        assert eng.add_request(rid, prompt, expected_total=len(prompt))
        b = Batch()
        b.add(rid, StageKind.PREFILL, len(prompt))
        eng.execute(b)
    scans = []

    def decode(*a, _orig=eng._decode, **kw):
        scans.append((kw["n_steps"], a[2].shape[0], np.asarray(a[4])))
        return _orig(*a, **kw)
    eng._decode = decode
    return eng, scans


def _decode(eng, budgets):
    b = Batch()
    for rid, n in budgets.items():
        b.add(rid, StageKind.DECODE, n)
    return eng.execute(b)


def _prompt(seed, n=16):
    return np.random.default_rng(seed).integers(1, 512, n).tolist()


def test_300_steps_run_as_256_and_64_and_match_one_step_calls(parts):
    eng, scans = _engine(parts, {1: _prompt(0)})
    out = _decode(eng, {1: 300})[1]
    assert [(n, b) for n, b, _ in scans] == [(256, 1), (64, 1)]
    assert len(out) == 300
    assert eng.counters["decode_split_scans"] == 1

    ref, ref_scans = _engine(parts, {1: _prompt(0)})
    stream = []
    for _ in range(300):
        stream += _decode(ref, {1: 1})[1]
    assert {(n, b) for n, b, _ in ref_scans} == {(1, 1)}
    assert out == stream
    assert eng.kv.length(1) == ref.kv.length(1) == 16 + 300


def test_short_lane_leaves_after_the_first_scan(parts):
    eng, scans = _engine(parts, {1: _prompt(1), 2: _prompt(2)})
    out = _decode(eng, {1: 300, 2: 20})
    assert [(n, b) for n, b, _ in scans] == [(256, 2), (64, 1)]
    assert scans[1][2].tolist() == [300 - 256]
    assert len(out[1]) == 300 and len(out[2]) == 20
    assert eng.counters["decode_live_lanes"] == 3


def test_lane_that_hits_eos_is_absent_from_the_next_scan(parts):
    prompts = {1: _prompt(3), 2: _prompt(4)}
    free, _ = _engine(parts, prompts)
    want = _decode(free, {1: 300, 2: 300})
    # EOS at the last token that first appears inside the first scan
    k = max(i for i in range(256) if want[2][i] not in want[2][:i])

    eng, scans = _engine(parts, prompts)
    eng.reqs[2].eos = want[2][k]
    out = _decode(eng, {1: 300, 2: 300})
    assert out[2] == want[2][:k + 1] and eng.reqs[2].done
    assert out[1] == want[1]
    assert [(n, b) for n, b, _ in scans] == [(256, 2), (64, 1)]
    assert scans[1][2].tolist() == [300 - 256]          # lane 1 alone


def test_counters_and_dispatch_spans_count_each_scan(parts):
    eng, scans = _engine(parts, {1: _prompt(5), 2: _prompt(6)})
    eng.tracer = StepTracer()
    _decode(eng, {1: 300, 2: 20})
    _decode(eng, {1: 100, 2: 3})
    lane_steps = sum(n * b for n, b, _ in scans)
    assert [(n, b) for n, b, _ in scans] == [(256, 2), (64, 1), (128, 2)]
    assert eng.counters["decode_calls"] == len(scans) == 3
    assert eng.counters["decode_split_scans"] == 1
    assert eng.counters["decode_lane_steps"] == lane_steps
    assert eng.counters["decode_tokens"] == 300 + 20 + 100 + 3
    spans = eng.tracer.spans()
    disp = [s.attrs["lane_steps"] for s in spans
            if s.name == "repro.engine.decode.dispatch"]
    assert disp == [n * b for n, b, _ in scans]
    for phase in ("prepare", "wait", "post"):
        assert sum(s.name == f"repro.engine.decode.{phase}"
                   for s in spans) == 3
    assert sum(s.name == "repro.engine.decode.reserve" for s in spans) == 2


@pytest.fixture
def host_only_engine(parts):
    """An engine whose decode program is a host stand-in that emits
    token 7 for each step of a lane's budget, so a sweep of budgets
    exercises only the engine's chaining."""
    eng = make_engine(parts)
    scans = []

    def decode(params, cache, toks0, pos0, steps, eos, bt, enc, key, *,
               n_steps):
        scans.append(n_steps)
        steps = np.asarray(steps)
        em = np.where(np.arange(n_steps)[None, :] < steps[:, None], 7, -1)
        return cache, em, None, None
    eng._decode = decode
    return eng, scans


def test_every_budget_runs_scans_from_the_bucket_table(host_only_engine):
    eng, scans = host_only_engine
    max_len = ECFG["max_len"]
    for budget in range(1, max_len + 1):
        scans.clear()
        split0 = eng.counters["decode_split_scans"]
        assert eng.add_request(1, [3], expected_total=1)
        out = eng._decode_batched({1: budget})
        eng.finish(1)
        assert len(out[1]) == budget
        assert all(n in _DECODE_STEPS for n in scans), (budget, scans)
        full, rest = divmod(budget, 256)
        want = [256] * full + ([_bucket(rest, _DECODE_STEPS)] if rest else [])
        assert scans == want, budget
        assert eng.counters["decode_split_scans"] - split0 == len(want) - 1
        if budget <= 256:                   # one scan, as before chaining
            assert scans == [_bucket(budget, _DECODE_STEPS)]
