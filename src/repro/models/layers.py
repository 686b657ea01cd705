"""Shared neural-net layers: norms, RoPE, MLPs, embeddings (pure JAX)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ------------------------------ norms ---------------------------------- #
def init_norm(d: int, kind: str = "rmsnorm"):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p, x, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (xf * p["scale"]).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (xf * p["scale"] + p["bias"]).astype(x.dtype)


def rms_head_norm(scale, x, eps: float = 1e-6):
    """Per-head q/k norm (Qwen3): x is (..., n_heads, head_dim)."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


# ------------------------------- RoPE ---------------------------------- #
def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature term, 0.1 * mscale * ln(factor) + 1
    (1 for factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         max_pos: int) -> float:
    """The rotary dim whose wavelength fits ``rotations`` times into the
    original context."""
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_freqs(head_dim: int, theta: float, scaling=None):
    """Rotary inverse frequencies (head_dim/2,).  With YaRN ``scaling``
    (config.YarnScaling) the dims below the ``beta_fast`` correction dim
    keep their frequency, those above the ``beta_slow`` one are divided
    by ``factor``, and a linear ramp blends the two in between; this
    holds at every position."""
    extra = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                        dtype=jnp.float32) / head_dim))
    if scaling is None:
        return extra
    s = scaling
    low = max(math.floor(_yarn_correction_dim(
        s.beta_fast, head_dim, theta, s.original_max_position_embeddings)),
        0)
    high = min(math.ceil(_yarn_correction_dim(
        s.beta_slow, head_dim, theta, s.original_max_position_embeddings)),
        head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / s.factor * ramp + extra * (1.0 - ramp)


def rope_softmax_scale(scaling) -> float:
    """Factor on the attention softmax scale: mscale(factor,
    mscale_all_dim)**2 under YaRN with ``mscale_all_dim`` set, else 1."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def apply_rope(x, positions, theta: float = 10000.0, scaling=None):
    """x: (B, S, H, hd); positions: (B, S) int32.  Rotate-half layout;
    ``scaling``: YaRN (config.YarnScaling) or None."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta, scaling)              # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (B,S,1,hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------- MLPs ----------------------------------- #
def init_mlp(key, d_model: int, d_ff: int, act: str = "swiglu",
             use_bias: bool = False, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = d_model ** -0.5
    scale_out = d_ff ** -0.5
    p = {"w_up": jax.random.normal(k2, (d_model, d_ff), dtype) * scale_in,
         "w_down": jax.random.normal(k3, (d_ff, d_model), dtype) * scale_out}
    if act == "swiglu":
        p["w_gate"] = jax.random.normal(k1, (d_model, d_ff), dtype) * scale_in
    if use_bias:
        p["b_up"] = jnp.zeros((d_ff,), dtype)
        p["b_down"] = jnp.zeros((d_model,), dtype)
    return p


def apply_mlp(p, x, act: str = "swiglu", shard=None):
    """shard: serving ShardPlan inside shard_map — w_gate/w_up/b_up are
    column-sharded on d_ff, so the hidden activation is all-gathered (a
    concatenation, bit-identical to the unsharded order) before the
    replicated w_down contraction."""
    up = x @ p["w_up"]
    if "b_up" in p:
        up = up + p["b_up"]
    if act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * up
    else:
        h = jax.nn.gelu(up)
    if shard is not None and shard.mlp:
        h = jax.lax.all_gather(h, shard.axis, axis=h.ndim - 1, tiled=True)
    out = h @ p["w_down"]
    if "b_down" in p:
        out = out + p["b_down"]
    return out


# ----------------------------- embeddings ------------------------------- #
def init_embed(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"embed": jax.random.normal(key, (vocab, d_model), dtype) * 0.02}


def embed(p, tokens):
    return jnp.take(p["embed"], tokens, axis=0)


def unembed(p_out, x):
    return x @ p_out


def init_unembed(key, vocab: int, d_model: int, dtype=jnp.float32):
    return jax.random.normal(key, (d_model, vocab), dtype) * d_model ** -0.5
