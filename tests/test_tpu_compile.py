"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The kernel tests elsewhere run in interpret mode, which accepts code the
TPU compiler refuses (vector loads on HBM refs, unaligned slices, too
much VMEM).  These tests compile every main-path kernel with
``interpret=False`` for a described v5e topology — no chip needed — at
published widths, in the form the engine calls them (per-layer pool
stacks addressed at a layer index):

  * GQA paged decode, fused chunked prefill (buckets 16 and 2048) and
    fused speculative verify at Qwen3-1.7B widths (16 query / 8 KV heads,
    head_dim 128, page 16, 28 layers, 1024 pages, 2048-token tables),
    with and without a sliding window;
  * the MLA latent-prefill kernel at DeepSeek-V2's kv_lora_rank 512 and
    rope head dim 64 (in the 128-lane pool the engine allocates), 16
    query heads, at the engine's 256-token chunk bucket, and at its
    2048-token bucket over DeepSeek-V2-Lite's 14 layers with the
    YaRN-scaled softmax scale.

The topology is described inside a module-scoped fixture (never at
import), and the persistent compilation cache is off around the compiles.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_prefill import (mla_paged_prefill_attention,
                                         paged_prefill_attention,
                                         paged_verify_attention)

LAYERS, PAGES, PAGE, TABLE = 28, 1024, 16, 128          # engine at 2048 ctx
H, KVH, HD = 16, 8, 128                                 # Qwen3-1.7B
MLA_H, LATENT, ROPE, ROPE_POOL = 16, 512, 64, 128       # DeepSeek-V2 MLA


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:               # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()      # a Mosaic kernel
    return compiled


def _f32(*s):
    return s, jnp.float32


def _i32(*s):
    return s, jnp.int32


POOL = _f32(LAYERS, PAGES, PAGE, KVH, HD)


@pytest.mark.parametrize("window", [None, 4096])
def test_paged_decode_compiles(one_chip, window):
    B = 8
    _compile(lambda q, kp, vp, t, n, layer: paged_decode_attention(
        q, kp, vp, t, n, window=window, layer=layer), one_chip,
        _f32(B, H, HD), POOL, POOL, _i32(B, TABLE), _i32(B), _i32())


@pytest.mark.parametrize("S,window", [(16, None), (2048, None),
                                      (2048, 4096)])
def test_fused_prefill_compiles(one_chip, S, window):
    B = 8
    compiled = _compile(
        lambda q, kn, vn, kp, vp, t, p0, n, layer: paged_prefill_attention(
            q, kn, vn, kp, vp, t, p0, n, window=window, layer=layer),
        one_chip, _f32(B, S, H, HD), _f32(B, S, KVH, HD),
        _f32(B, S, KVH, HD), POOL, POOL, _i32(B, TABLE), _i32(B), _i32(B),
        _i32())
    # the pools are updated in place: no pool-sized temporary
    pool_bytes = LAYERS * PAGES * PAGE * KVH * HD * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


def test_fused_verify_compiles(one_chip):
    B, S = 1, 5                        # last emitted token + 4 drafts
    _compile(
        lambda q, kn, vn, kp, vp, t, p0, n, layer: paged_verify_attention(
            q, kn, vn, kp, vp, t, p0, n, layer=layer),
        one_chip, _f32(B, S, H, HD), _f32(B, S, KVH, HD),
        _f32(B, S, KVH, HD), POOL, POOL, _i32(B, TABLE), _i32(B), _i32(B),
        _i32())


def test_mla_prefill_compiles(one_chip):
    B, S = 8, 256
    scale = (128 + ROPE) ** -0.5
    _compile(
        lambda ql, qr, cn, rn, cp, rp, t, p0, n, layer:
            mla_paged_prefill_attention(ql, qr, cn, rn, cp, rp, t, p0, n,
                                        scale=scale, layer=layer),
        one_chip, _f32(B, S, MLA_H, LATENT), _f32(B, S, MLA_H, ROPE),
        _f32(B, S, LATENT), _f32(B, S, ROPE),
        _f32(LAYERS, PAGES, PAGE, LATENT), _f32(LAYERS, PAGES, PAGE, ROPE_POOL),
        _i32(B, TABLE), _i32(B), _i32(B), _i32())


def test_mla_prefill_compiles_at_the_top_bucket(one_chip):
    """DeepSeek-V2-Lite's prefill: the engine's 2048-token bucket over
    its 14-layer latent pools, at the YaRN-scaled softmax scale."""
    B, S, layers = 8, 2048, 14
    scale = (128 + ROPE) ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    _compile(
        lambda ql, qr, cn, rn, cp, rp, t, p0, n, layer:
            mla_paged_prefill_attention(ql, qr, cn, rn, cp, rp, t, p0, n,
                                        scale=scale, layer=layer),
        one_chip, _f32(B, S, MLA_H, LATENT), _f32(B, S, MLA_H, ROPE),
        _f32(B, S, LATENT), _f32(B, S, ROPE),
        _f32(layers, PAGES, PAGE, LATENT),
        _f32(layers, PAGES, PAGE, ROPE_POOL),
        _i32(B, TABLE), _i32(B), _i32(B), _i32())
