"""YaRN rope scaling (models/layers.py) and its reach into every MLA path.

DeepSeek-V2 scales its rotary dims with YaRN: inverse frequencies blend
extrapolation and interpolation (divided by the factor) along a linear
ramp between two correction dims, at every position, and the softmax
scale gains mscale(factor, mscale_all_dim)**2.  The MLA paths that must
all see both: the naive full forward, the paged prefill (the gather
reference and the fused latent kernel, interpret mode on the CPU) and the
absorbed decode over the paged latent cache.  Logits of a prefill then
token-by-token decode through the pages must equal the full forward's.
All float32 on the CPU, where the paths differ only in summation order
and in the absorption's reassociation: 2e-4 absolute on logits of
magnitude ~3 is ~1e3 ulps, and leaving YaRN out of one path moves the
logits by over 1e-1.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.attention as attention
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, YarnScaling
from repro.models.layers import (apply_rope, rope_freqs, rope_softmax_scale,
                                 yarn_mscale)
from repro.models.transformer import (init_paged_cache, init_params,
                                      logits_fn, model_forward)

YARN = YarnScaling(factor=40, original_max_position_embeddings=4096,
                   beta_fast=32, beta_slow=1, mscale_all_dim=0.707)
TOL = 2e-4


def test_mscale_of_deepseek_v2():
    assert yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert rope_softmax_scale(YARN) == pytest.approx(1.2608 ** 2, abs=1e-3)
    assert yarn_mscale(1, 0.707) == 1.0 and rope_softmax_scale(None) == 1.0


def test_yarn_with_factor_one_is_plain_rope():
    one = dataclasses.replace(YARN, factor=1)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 64))
    pos = jnp.arange(9)[None, :] * 700 + jnp.asarray([[0], [5]])
    np.testing.assert_array_equal(np.asarray(apply_rope(x, pos, 1e4, one)),
                                  np.asarray(apply_rope(x, pos, 1e4)))
    assert rope_softmax_scale(one) == 1.0


def test_yarn_frequencies_ramp_between_the_correction_dims():
    """At DeepSeek-V2's rotary width 64 and 4096 original positions the
    correction dims are 10 (32 rotations) and 23 (1 rotation): below the
    first the frequencies are plain, from the second divided by 40."""
    plain, yarn = rope_freqs(64, 1e4), rope_freqs(64, 1e4, YARN)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4))
    high = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4))
    assert (math.floor(low), math.ceil(high)) == (10, 23)
    np.testing.assert_allclose(yarn[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(yarn[23:], plain[23:] / 40, rtol=1e-6)
    mid = yarn[11:23]
    assert bool(jnp.all((mid < plain[11:23]) & (mid > plain[11:23] / 40)))


def _cfg(scaling=YARN):
    return ModelConfig(
        name="mla-yarn", arch_type="moe", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=96, vocab=128, block_pattern=("mla", "mla_moe"),
        rope_scaling=scaling, tie_embeddings=False, mla_absorb=True,
        mla=MLAConfig(kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
                      qk_rope_head_dim=16, v_head_dim=16),
        moe=MoEConfig(n_experts=8, top_k=3, d_ff_expert=24, n_shared=1,
                      n_held=4, norm_topk_prob=False))


def _paged_logits(cfg, params, toks, prompt, page=4):
    """Prefill ``prompt`` tokens as one chunk, then decode the rest one
    token at a time through the paged latent pools; logits per
    position from prompt - 1 on."""
    S = toks.shape[1]
    max_pages = -(-S // page)
    cache = init_paged_cache(cfg, max_pages + 2, page, 1)
    bt = jnp.arange(2, max_pages + 2, dtype=jnp.int32)[None]
    h, cache, _ = model_forward(
        params, cfg, toks[:, :prompt], cache=cache,
        pos0=jnp.zeros((1,), jnp.int32), block_tables=bt,
        chunk_len=jnp.full((1,), prompt, jnp.int32), moe_cf=8 / 3)
    out = [logits_fn(params, cfg, h)[0, -1]]
    for t in range(prompt, S):
        h, cache, _ = model_forward(
            params, cfg, toks[:, t:t + 1], cache=cache,
            pos0=jnp.full((1,), t, jnp.int32), block_tables=bt,
            chunk_len=jnp.ones((1,), jnp.int32), moe_cf=8 / 3)
        out.append(logits_fn(params, cfg, h)[0, -1])
    return jnp.stack(out)


@pytest.mark.parametrize("prefill", ["gather", "fused"])
def test_mla_paths_agree_with_the_full_forward(monkeypatch, prefill):
    monkeypatch.setattr(attention, "PAGED_PREFILL_IMPL", prefill)
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 22), 1, 128)
    prompt = 13
    h, _, _ = model_forward(params, cfg, toks, moe_cf=8 / 3)
    want = logits_fn(params, cfg, h)[0, prompt - 1:]
    got = _paged_logits(cfg, params, toks, prompt)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    # the comparison can tell: without YaRN the logits move far past TOL
    plain = _cfg(scaling=None)
    h0, _, _ = model_forward(params, plain, toks, moe_cf=8 / 3)
    assert float(jnp.max(jnp.abs(
        logits_fn(params, plain, h0)[0, prompt - 1:] - want))) > 100 * TOL
