"""The program-span reduction on a small trace recorded on a TPU v5e by
``record_program_trace.py``: three steps, each a ~20 ms jitted loop of
matmuls under ``repro.engine.decode.dispatch`` and ``.wait`` and a 20 ms
host sleep under ``repro.engine.decode.post``, all inside
``repro.driver.execute``, then a 30 ms sleep outside any program span,
inside ``bench.window``."""
from pathlib import Path

import pytest

from bench.lib import program_trace, trace

DATA = Path(__file__).parent / "data" / "program.xplane.pb"
STEPS, POST_S, WAIT_S = 3, 0.02, 0.03


@pytest.fixture(scope="module")
def red():
    return program_trace.reduce(str(DATA))


def test_program_spans_reach_the_trace():
    bench, spans, devices = program_trace.load(str(DATA))
    names = [s[2] for s in spans]
    for name in ("repro.driver.execute", "repro.engine.decode.dispatch",
                 "repro.engine.decode.wait", "repro.engine.decode.post"):
        assert names.count(name) == STEPS
    assert len(devices) == 1 and any(s[2] == "bench.window" for s in bench)


def test_each_wait_holds_its_programs_device_work():
    """The device runs each dispatched program inside its dispatch and
    wait spans, and the wait ends when the program does, within
    ``SKEW_NS``: the host spans and the device ops share a clock."""
    _, spans, [(mods, ops)] = program_trace.load(str(DATA))
    disp = sorted(s for s in spans if s[2].endswith(".dispatch"))
    wait = sorted(s for s in spans if s[2].endswith(".wait"))
    skew = trace.SKEW_NS
    for d, w in zip(disp, wait):
        lo, hi = d[0] - skew, w[1] + skew
        runs = [m for m in mods if lo <= m[0] <= hi]
        assert len(runs) == 1, runs
        assert runs[0][1] <= hi and abs(runs[0][1] - w[1]) <= skew
        held = [o for o in ops if runs[0][0] <= o[0] <= runs[0][1]]
        assert held and all(lo <= o[0] and o[1] <= hi for o in held)
        assert w[1] - w[0] > 0.01e9          # the host waited on it


def test_idle_gaps_by_innermost_program_span(red):
    by = red["idle_by_program_span"]
    assert by["repro.engine.decode.post"] == pytest.approx(
        STEPS * POST_S, rel=0.3)
    assert by["no span"] >= STEPS * WAIT_S * 0.9
    assert "repro.driver.execute" not in by or \
        by["repro.driver.execute"] < 0.005
    label, secs = red["idle_gaps"][0]
    assert label == "no span" and secs >= WAIT_S * 0.9


def test_dispatch_and_wait_cover_the_busy_share(red):
    busy_pct = 100 * red["busy_s"] / red["window_s"]
    assert 20 < busy_pct < 60
    assert abs(red["dispatch_wait_pct"] - busy_pct) < 5
