"""The trace reduction on a small trace recorded on a TPU v5e by
``record_trace.py``: two bursts, each a ``paged_decode_attention`` call
and a 2048x2048 float32 matmul under ``bench.step``, each followed by a
50 ms host sleep under ``bench.idle``, inside ``bench.window``."""
from pathlib import Path

import pytest

from bench.lib import trace

DATA = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(str(DATA))


def test_names():
    assert trace.op_name("%paged_decode_attention.1 = f32[4,1,16,128]{3} "
                         "custom-call(...)") == "paged_decode_attention"
    assert trace.op_name("%fusion = f32[2]") == "fusion"
    assert trace.module_name("jit_work(78203)") == "jit_work"


def test_busy_and_window(red):
    assert red["devices"] == 1
    assert red["window_s"] >= 0.1                   # two 50 ms sleeps
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] < 0.01                     # two short bursts


def test_kernel_time(red):
    """Both calls of the kernel count (22 us each on the chip), though the
    device clock reads the first one as starting before the host span."""
    k = red["op_s"]["paged_decode_attention"]
    assert 3.5e-5 < k < 6e-5
    assert k < red["busy_s"]
    assert red["op_s"]["fusion"] > 3 * k            # the matmul is longer


def test_self_time():
    ops = [(0, 10, "while"), (1, 3, "a"), (4, 9, "while"), (5, 6, "b")]
    own = {(s, n): t for s, _, n, t in trace.self_times(ops)}
    assert own == {(0, "while"): 3, (1, "a"): 2, (4, "while"): 4,
                   (5, "b"): 1}


def test_breakdown(red):
    names = [n for n, _ in red["device_ops"]]
    assert "jit_work/paged_decode_attention" in names
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    label, secs = red["idle_gaps"][0]
    assert label == "bench.idle" and 0.045 < secs < 0.1
    assert red["idle_by_span"]["bench.idle"] > 0.09


def test_window_from_the_first_step_after_the_trace_opens(tmp_path):
    """A trace started mid-step (by the harness's timer) counts from the
    first ``bench.step`` after ``bench.window.open`` to the end of
    ``bench.window.close``."""
    import time

    import jax
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.step"):         # in flight: not counted
        with TraceAnnotation(trace.OPEN):
            pass
        time.sleep(0.2)
    with TraceAnnotation("bench.step"):
        time.sleep(0.1)
    with TraceAnnotation(trace.CLOSE):
        pass
    jax.profiler.stop_trace()
    red = trace.reduce(str(next(tmp_path.rglob("*.xplane.pb"))))
    skew = 2 * trace.SKEW_NS * 1e-9
    assert 0.1 <= red["window_s"] - skew < 0.18
