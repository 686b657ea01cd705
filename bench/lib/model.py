"""A configuration file as run: its sizes, the program's ``ModelConfig``
built from them, and the weights, made on the device from the seed.

The weights are the benchmark's: one jitted call draws every leaf from
``--seed`` in the tree layout the program serves (``init_params``'s
layout for a dense GQA stack with tied embeddings), in the dtype the
file states, at the scales the program's own initialiser uses.  The
plain reference builds the same weights from the same seed with this
function, so it takes nothing the program made.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference and the counts need (hashable, so it can
    be a static argument of a jitted function)."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qk_norm: bool
    rope_theta: float
    rms_eps: float


def dims(cfg_file: dict) -> Dims:
    m = cfg_file["config"]
    if m.get("model_type") not in ("qwen3", "phi3"):
        raise ValueError(f"no plain reference for {m.get('model_type')!r}")
    if not m.get("tie_word_embeddings"):
        raise ValueError("the reference assumes tied embeddings")
    return Dims(n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                n_heads=m["num_attention_heads"],
                n_kv_heads=m["num_key_value_heads"],
                head_dim=m.get("head_dim") or (m["hidden_size"]
                                               // m["num_attention_heads"]),
                d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                qk_norm=bool(cfg_file.get("qk_norm")),
                rope_theta=float(m["rope_theta"]),
                rms_eps=float(m["rms_norm_eps"]))


def dtype_of(cfg_file: dict):
    return {"float32": jnp.float32,
            "bfloat16": jnp.bfloat16}[cfg_file["config"]["torch_dtype"]]


def program_config(cfg_file: dict, name: str):
    """The program's ``ModelConfig`` for this file."""
    from repro.models.config import ModelConfig
    d = dims(cfg_file)
    if d.rms_eps != 1e-6:
        raise ValueError("the program's RMSNorm eps is 1e-6")
    return ModelConfig(
        name=name, arch_type="dense", n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
        d_ff=d.d_ff, vocab=d.vocab, head_dim=d.head_dim,
        qk_norm=d.qk_norm, rope_theta=d.rope_theta, tie_embeddings=True,
        source=cfg_file["source"])


@partial(jax.jit, static_argnums=(1, 2))
def _weights(key, d: Dims, dtype):
    L, D, H, K, hd, F, V = (d.n_layers, d.d_model, d.n_heads, d.n_kv_heads,
                            d.head_dim, d.d_ff, d.vocab)
    ks = iter(jax.random.split(key, 8))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    attn = {"wq": normal((L, D, H, hd), D ** -0.5),
            "wk": normal((L, D, K, hd), D ** -0.5),
            "wv": normal((L, D, K, hd), D ** -0.5),
            "wo": normal((L, H, hd, D), (H * hd) ** -0.5)}
    if d.qk_norm:
        attn["q_norm"] = jnp.ones((L, hd), jnp.float32)
        attn["k_norm"] = jnp.ones((L, hd), jnp.float32)
    layer = {"norm1": {"scale": jnp.ones((L, D), jnp.float32)},
             "attn": attn,
             "norm2": {"scale": jnp.ones((L, D), jnp.float32)},
             "mlp": {"w_up": normal((L, D, F), D ** -0.5),
                     "w_down": normal((L, F, D), F ** -0.5),
                     "w_gate": normal((L, D, F), D ** -0.5)}}
    return {"embed": {"embed": normal((V, D), 0.02)},
            "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
            "segments": [{"p": layer}]}


def make_weights(d: Dims, seed: int, dtype=jnp.float32):
    """Every weight from ``seed`` in one jitted call on the default
    device."""
    return _weights(jax.random.PRNGKey(seed), d, dtype)
