"""The expert layer told which experts it holds (models/moe.py).

The router spans all E experts; a layer holding the first n of them
computes only their part of the routed sum, plus the shared experts.  The
share test ties that to the whole layer: over shares that cover the E
experts, the shares' routed parts, with the shared experts counted once,
add up to the uncut layer.  A share that holds experts [s, s + n) is the
layer with the router's columns rotated by s, so that those experts come
first: routing is the same up to that relabelling.  All on the CPU in
float32, where the dispatch and the dense oracle differ only in the order
of their additions: 1e-5 absolute on outputs of magnitude ~1 is a few
hundred ulps and far below what one wrongly routed unit moves (~1e-1).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig, MoEConfig
from repro.models.moe import capacity, init_moe, moe_forward
from repro.models.transformer import init_params
from repro.serving.engine import EngineConfig, ServingEngine
from repro.core.batch import Batch
from repro.core.slo import StageKind
from repro.telemetry.exporters import StepTracer

E, K, D, F = 8, 3, 32, 24
TOL = 1e-5


def _cfg(**moe):
    m = dict(n_experts=E, top_k=K, d_ff_expert=F, n_shared=1)
    m.update(moe)
    return ModelConfig(name="moe-share", arch_type="moe", n_layers=2,
                       d_model=D, n_heads=4, n_kv_heads=4, d_ff=48,
                       vocab=97, block_pattern=("attn", "attn_moe"),
                       moe=MoEConfig(**m))


def _share(p, start, n):
    """The weights of the share that holds experts [start, start + n):
    the router whole, rotated so that they come first, and their FFNs."""
    out = dict(p, router=jnp.roll(p["router"], -start, axis=1))
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = p[k][start:start + n]
    return out


def _x(seed=1, shape=(2, 9)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape + (D,))


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("n_shares", [2, 4, 8])
def test_shares_add_up_to_the_whole_layer(n_shares, norm):
    whole = _cfg(norm_topk_prob=norm)
    p = init_moe(jax.random.PRNGKey(0), whole)
    x = _x()
    cf = E / K                                         # dropless
    want, _ = moe_forward(p, x, whole, capacity_factor=cf)
    n = E // n_shares
    parts = []
    for i in range(n_shares):
        cfg = _cfg(norm_topk_prob=norm, n_held=n)
        parts.append(moe_forward(_share(p, i * n, n), x, cfg,
                                 capacity_factor=cf)[0])
    # each part carries the shared experts once: count them once in all
    no_shared = dict(p, shared_down=jnp.zeros_like(p["shared_down"]))
    shared_out = want - moe_forward(no_shared, x, whole,
                                    capacity_factor=cf)[0]
    got = sum(parts) - (n_shares - 1) * shared_out
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


@pytest.mark.parametrize("n", [8, 4, 1])
def test_held_dispatch_matches_the_dense_oracle(n):
    cfg = _cfg(n_held=n, norm_topk_prob=False)
    p = init_moe(jax.random.PRNGKey(2), cfg)
    assert p["w_gate"].shape == (n, D, F) and p["router"].shape == (D, E)
    x = _x(3)
    dense, _ = moe_forward(p, x, cfg, mode="dense")
    disp, _ = moe_forward(p, x, cfg, capacity_factor=E / K)
    np.testing.assert_allclose(np.asarray(disp), np.asarray(dense),
                               atol=TOL)


def test_routing_weights_without_renormalisation():
    """norm_topk_prob false: each routed unit is weighted by its softmax
    score (DeepSeek-V2), so the routed sum of a one-expert layer is that
    expert's FFN times its score."""
    cfg = _cfg(n_held=1, norm_topk_prob=False, n_shared=0)
    p = init_moe(jax.random.PRNGKey(4), cfg)
    x = _x(5)
    got, _ = moe_forward(p, x, cfg, capacity_factor=E / K)
    xt = x.reshape(-1, D)
    probs = jax.nn.softmax(xt @ p["router"], axis=-1)
    topv, topi = jax.lax.top_k(probs, K)
    w = jnp.sum(jnp.where(topi == 0, topv, 0.0), axis=-1)
    ffn = (jax.nn.silu(xt @ p["w_gate"][0]) * (xt @ p["w_up"][0])
           ) @ p["w_down"][0]
    np.testing.assert_allclose(np.asarray(got.reshape(-1, D)),
                               np.asarray(ffn * w[:, None]), atol=TOL)


def test_units_held_counts_live_units_routed_to_held_experts():
    cfg = _cfg(n_held=3)
    p = init_moe(jax.random.PRNGKey(6), cfg)
    x = _x(7)
    live = jnp.arange(9)[None, :] < jnp.asarray([[9], [4]])
    _, aux = moe_forward(p, x, cfg, capacity_factor=E / K, live=live)
    _, topi = jax.lax.top_k(
        jax.nn.softmax(x.reshape(-1, D) @ p["router"], -1), K)
    held = topi < 3
    want = int(jnp.sum(held & live.reshape(-1)[:, None]))
    assert int(aux["units_held"]) == want > 0
    _, dense = moe_forward(p, x, cfg, mode="dense", live=live)
    assert int(dense["units_held"]) == want


def test_capacity_is_dropless_at_the_serving_factor():
    cfg = _cfg(n_held=2)
    for t in (1, 7, 8, 2048):
        assert capacity(cfg, t, E / K) == t


def test_engine_counts_expert_rows_and_held_units():
    """The engine's counters: rows = MoE layers x held x capacity per step
    (capacity = the call's tokens), units = the device's count, and each
    prefill group's and decode scan's post span carries its share."""
    cfg = _cfg(n_held=2, norm_topk_prob=False)
    params = init_params(jax.random.PRNGKey(8), cfg)
    eng = ServingEngine(cfg, params, EngineConfig(max_slots=4, max_len=64,
                                                  total_pages=32))
    eng.tracer = StepTracer()
    rng = np.random.default_rng(0)
    for rid in (1, 2):
        assert eng.add_request(rid, rng.integers(1, 97, 13).tolist(), 40)
    b = Batch()
    b.add(1, StageKind.PREFILL, 13)
    b.add(2, StageKind.PREFILL, 13)
    eng.execute(b)
    b = Batch()
    b.add(1, StageKind.DECODE, 5)
    b.add(2, StageKind.DECODE, 3)
    eng.execute(b)
    # one prefill group (2 lanes x bucket 16), one decode scan (2 lanes x
    # 8 steps); one MoE layer holding 2 experts
    rows = 2 * capacity(cfg, 2 * 16, E / K) + 8 * 2 * capacity(cfg, 2, E / K)
    assert eng.counters["moe_expert_rows"] == rows == 2 * 32 + 8 * 2 * 2
    units = eng.counters["moe_routed_units_held"]
    # 26 live prompt tokens and 5 + 3 live decode lane-steps, each with
    # k units, some of them on the held experts
    assert 0 < units < K * (26 + 5 + 3)
    posts = [s for s in eng.tracer.spans() if s.name.endswith(".post")]
    assert sum(s.attrs["moe_expert_rows"] for s in posts) == rows
    assert sum(s.attrs["moe_routed_units_held"] for s in posts) == units


def test_dense_model_programs_return_no_expert_count():
    cfg = dataclasses.replace(_cfg(), block_pattern=("attn", "attn"),
                              moe=None, arch_type="dense")
    params = init_params(jax.random.PRNGKey(9), cfg)
    eng = ServingEngine(cfg, params, EngineConfig(max_slots=2, max_len=64,
                                                  total_pages=16))
    eng.tracer = StepTracer()
    assert eng.add_request(1, list(range(1, 12)), 30)
    b = Batch()
    b.add(1, StageKind.PREFILL, 11)
    eng.execute(b)
    b = Batch()
    b.add(1, StageKind.DECODE, 4)
    eng.execute(b)
    assert eng.counters["moe_expert_rows"] == 0
    assert all(not s.attrs for s in eng.tracer.spans()
               if s.name.endswith(".post"))
