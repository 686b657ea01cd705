"""A whole run but the look for a chip, on the CPU at a tiny size: the
open loop through ``ClusterFrontend`` with the program's CPU sizes, then
the comparison that decides ``correct``.  A sound run is correct; each
fault a one-chip serving cell can have, planted in the timed path, makes
it not correct; the float8 control, put in the program's place, reads
far above the program and comes out not correct."""
import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.lib import spec

TINY = {"source": "tiny", "family": "dense_gqa", "qk_norm": True,
        "zero_load_ttft_s": {"a": 0.05, "b": 1e-4},
        "config": {"model_type": "qwen3", "hidden_size": 128,
                   "intermediate_size": 256, "num_hidden_layers": 2,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 32, "vocab_size": 512, "rope_theta": 10000.0,
                   "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
                   "torch_dtype": "float32"}}
TRAFFIC = {"max_context": 120, "drain_s": 30, "classes": [
    {"name": "a", "weight": 1, "prompt": {"mean": 40, "std": 20},
     "output": {"mean": 12, "std": 6}, "ttft_slowdown": 5, "tpot_s": 0.1},
    {"name": "b", "weight": 1, "prompt": {"mean": 60, "std": 20},
     "output": {"mean": 6, "std": 3}, "ttft_slowdown": 3, "tpot_s": 0.05}]}
LIMITS = {"max_logit_gap": 1e-3, "sample_requests": 4}
SEED = 2 ** 31 + 77
FAM = spec.family(TINY)


def altered_token(cluster):
    """Every decoded token replaced by its neighbour as it is produced."""
    eng = cluster.drivers[0].engine
    orig = eng._decode

    def wrong(*a, **kw):
        cache, em, pos, done = orig(*a, **kw)
        return cache, jnp.where(em >= 0, (em + 1) % 512, em), pos, done
    eng._decode = wrong


def state_unchanged(cluster):
    """The decode step hands back the KV pools as they came in."""
    eng = cluster.drivers[0].engine
    orig = eng._decode

    def stale(params, cache, *a, **kw):
        kept = jax.tree.map(jnp.copy, cache)
        _, em, pos, done = orig(params, cache, *a, **kw)
        return kept, em, pos, done
    eng._decode = stale


def first_token_altered(cluster):
    """The prefill's first token replaced as it is produced."""
    eng = cluster.drivers[0].engine
    orig = eng._prefill

    def wrong(*a, **kw):
        tok, cache = orig(*a, **kw)
        return (tok + 1) % 512, cache
    eng._prefill = wrong


def served(fault, control=False):
    res = run.serve(TINY, FAM, TRAFFIC, 2.0, 3.0, SEED, "tiny", 30, False,
                    full=False, on_cluster=fault)
    w = res["cluster"].drivers[0].engine.params
    got = run.check(res["outs"], {r.rid: r.prompt for r in res["reqs"]},
                    FAM, res["dims"], SEED, LIMITS, w, control=control)
    return got


def test_sound_run_is_correct():
    got = served(None, control=True)
    ok, checks = run.verdict(got, LIMITS)
    assert ok, checks
    assert got["sampled_tokens"] >= 20
    # the control: float8 products in the program's place read far above
    assert got["control_max_logit_gap"] > 3 * max(got["max_logit_gap"],
                                                  LIMITS["max_logit_gap"])
    c_ok, c_checks = run.verdict(
        dict(got, max_logit_gap=got["control_max_logit_gap"]), LIMITS)
    assert not c_ok, c_checks


def test_compile_in_the_window_is_not_correct():
    got = {"sampled_requests": 4, "max_logit_gap": 0.0,
           "compiles_in_window": 1}
    ok, checks = run.verdict(got, LIMITS)
    assert not ok and checks["compiles_in_window"]["limit"] == 0


@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   first_token_altered])
def test_fault_is_not_correct(fault):
    ok, checks = run.verdict(served(fault), LIMITS)
    assert not ok, checks


def test_readings_reuse_one_cluster_across_seeds():
    """The readings' mode: the backlog cleared, another seed's weights
    swapped in, and the next window's tokens compare against that seed's
    reference.  (At the program's CPU sizes nothing is warmed, so
    programs compile in the window here; on the chip the mode reports
    them.)"""
    from bench.lib import driver, traffic as gen
    res = run.serve(TINY, FAM, TRAFFIC, 2.0, 3.0, SEED, "tiny", 0, False,
                    full=False)
    cluster, dims = res["cluster"], res["dims"]
    driver.clear(cluster, res["outs"])
    assert cluster.idle
    seed = SEED + 1
    driver.swap_weights(cluster, lambda: FAM.make_weights(dims, seed))
    reqs = gen.generate(TRAFFIC, 2.0, 3.0, seed, dims.vocab)
    again = driver.run_window(cluster, reqs, 3.0, 30, run.ttft_fit(TINY))
    got = run.check(again["outs"], {r.rid: r.prompt for r in reqs}, FAM,
                    dims, seed, LIMITS, FAM.make_weights(dims, seed))
    ok, checks = run.verdict(got, LIMITS)
    assert ok and got["sampled_tokens"] >= 20, checks
    # the old seed's weights would not pass
    stale = run.check(again["outs"], {r.rid: r.prompt for r in reqs},
                      FAM, dims, seed, LIMITS, FAM.make_weights(dims, SEED))
    assert not run.verdict(stale, LIMITS)[0]
