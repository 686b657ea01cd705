import pytest

from bench.lib import judge
from bench.lib.judge import Outcome


def out(rid, due, first, n, dt, out_len=None, limit=1.0, tpot=0.1,
        dropped=False):
    o = Outcome(rid=rid, cls="c", due=due, prompt_len=100,
                out_len=out_len or n, ttft_limit=limit, tpot_limit=tpot,
                dropped=dropped)
    if first is not None:
        o.times = [(first + i * dt, 1) for i in range(n)]
        o.tokens = list(range(n))
    return o


def test_met():
    assert out(0, 0.0, 0.5, 10, 0.05).met()
    assert not out(0, 0.0, 1.5, 10, 0.05).met()          # TTFT over
    assert not out(0, 0.0, 0.5, 10, 0.2).met()           # TPOT over
    assert not out(0, 0.0, 0.5, 10, 0.05, out_len=20).met()   # unfinished
    assert not out(0, 0.0, None, 0, 0.0, out_len=5).met()     # no token
    assert not out(0, 0.0, 0.5, 10, 0.05, dropped=True).met()  # declined


def test_unserved_ttft_is_the_wait():
    o = out(0, 2.0, None, 0, 0.0, out_len=5)
    assert o.ttft(12.0) == pytest.approx(10.0)
    assert o.tpot() is None


def test_stall_moves_the_tail():
    calm = [out(i, i * 0.5, i * 0.5 + 0.2, 5, 0.02) for i in range(20)]
    stall = [out(i, i * 0.5, max(i * 0.5, 6.0) + 0.2, 5, 0.02)
             for i in range(20)]           # nothing served from 0 to 6 s
    end = 20.0
    assert judge.ttft_p(calm, 90, end) == pytest.approx(0.2)
    assert judge.ttft_p(stall, 90, end) > 4.0
    assert judge.attainment_pct(calm) == 100.0
    assert judge.attainment_pct(stall) < 100.0


def test_percentile_is_linear():
    assert judge.percentile(list(range(11)), 90) == pytest.approx(9.0)


def test_rate_counts_the_window():
    o = out(0, 0.0, 1.0, 10, 1.0)          # tokens at 1..10 s
    p, n = judge.tokens_in([o], 0.0, 5.0)
    assert (p, n) == (100, 5)
    assert judge.tokens_per_s([o], 0.0, 5.0) == pytest.approx(21.0)
    late = out(0, 0.0, 6.0, 10, 1.0)       # a stall pushes it all out
    assert judge.tokens_per_s([late], 0.0, 5.0) == 0.0
