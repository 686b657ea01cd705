import numpy as np

from bench.lib import spec, traffic

MIXED = spec.traffic_file("mixed")


def test_same_seed_same_requests():
    a = traffic.generate(MIXED, 2.0, 45, 2 ** 31 + 5, 151936)
    b = traffic.generate(MIXED, 2.0, 45, 2 ** 31 + 5, 151936)
    assert [(r.due, r.cls, r.prompt, r.out_len) for r in a] == \
        [(r.due, r.cls, r.prompt, r.out_len) for r in b]


def test_seeds_share_sizes_and_gaps():
    """Without ``order_seed`` another seed orders the same work
    differently: the same classes, the same length quantiles per class,
    the same set of gaps."""
    free = {k: v for k, v in MIXED.items() if k != "order_seed"}
    a = traffic.generate(free, 2.0, 45, 1, 151936)
    b = traffic.generate(free, 2.0, 45, 2, 151936)
    assert [r.due for r in a] != [r.due for r in b]
    gaps = sorted(-np.log(1 - (np.arange(90) + 0.5) / 90) / 2.0)
    for reqs in (a, b):
        d = np.diff([r.due for r in reqs])      # every gap but the first
        assert len(d) == len(gaps) - 1
        assert all(np.min(np.abs(np.asarray(gaps) - x)) < 1e-9 for x in d)
        for c in ("chatbot", "coder", "summarizer"):
            assert sum(r.cls == c for r in reqs) == 30
    for c in ("chatbot", "coder", "summarizer"):
        q = traffic.lognormal_quantiles(*(MIXED["classes"][
            [k["name"] for k in MIXED["classes"]].index(c)]["output"][x]
            for x in ("mean", "std")), 30)
        for reqs in (a, b):
            got = sorted(r.out_len for r in reqs if r.cls == c
                         and not r.clipped)
            assert all(x in q for x in got)


def test_window_holds_all_due():
    reqs = traffic.generate(MIXED, 2.0, 45, 3, 151936)
    assert len(reqs) == 90
    assert 0.0 == reqs[0].due and reqs[-1].due < 45
    assert all(x.due <= y.due for x, y in zip(reqs, reqs[1:]))


def test_clip():
    """Every request fits the context; the clipped share on many seeds is
    the one the traffic file states."""
    t = MIXED
    shares = []
    for seed in range(20):
        c = t["clipped_pct"]
        reqs = traffic.generate(t, c["rate"], c["seconds"], seed, 1000)
        for r in reqs:
            assert len(r.prompt) + r.out_len <= t["max_context"]
            assert len(r.prompt) >= 4 and r.out_len >= 1
            if r.clipped:
                assert len(r.prompt) + r.out_len == t["max_context"]
        shares.append(100.0 * sum(r.clipped for r in reqs) / len(reqs))
    lo, hi = t["clipped_pct"]["range"]
    assert lo <= min(shares) and max(shares) <= hi


def test_lognormal_quantiles_match_table4_mean():
    q = traffic.lognormal_quantiles(763, 424, 2000)
    assert abs(q.mean() - 763) / 763 < 0.02


def test_seeds_draw_the_same_requests():
    """The same (class, prompt length, output length) pairs on every
    seed; only their order and the prompt tokens change."""
    def pairs(seed):
        return sorted((r.cls, len(r.prompt), r.out_len)
                      for r in traffic.generate(MIXED, 3.0, 51, seed, 1000))
    assert pairs(1) == pairs(2 ** 31 + 11) == pairs(7)


def test_order_seed_fixes_the_sequence():
    """With ``order_seed`` every run offers the same sizes at the same
    times; the run's seed draws only the prompt tokens."""
    a = traffic.generate(MIXED, 3.0, 51, 1, 151936)
    b = traffic.generate(MIXED, 3.0, 51, 2 ** 31 + 9, 151936)
    assert [(r.due, r.cls, len(r.prompt), r.out_len) for r in a] == \
        [(r.due, r.cls, len(r.prompt), r.out_len) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
