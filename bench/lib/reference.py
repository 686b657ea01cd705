"""Plain reference forward of the dense GQA block both configurations
use: RMSNorm, RoPE (rotate-half over the whole head), optional per-head
qk-norm, causal softmax attention with grouped KV heads, SwiGLU, tied
embeddings.  Straightforward ``jax.numpy`` on one sequence at a time:
no cache, no pages, no kernels, no batching.  It imports nothing of the
program.

The reference keeps everything in float32 and runs every matrix product
at ``Precision.HIGHEST``.  The control (``control=True``) is the same
forward with both operands of every matrix product rounded to float8
e4m3 at a per-tensor scale, the precision one step below the bfloat16
products the configurations state; the products themselves stay exact.
Both read the float32 weights; the control rounds one layer at a time,
so it needs no second copy of the model.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.lib.model import Dims

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0     # largest finite float8 e4m3
ROWS = 256          # logit rows per block, so (ROWS, vocab) fits beside
BUCKET = 512        # sequences are padded to a multiple (causal: inert)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def fp8(x):
    """``x`` rounded to float8 e4m3 at a per-tensor scale (its largest
    magnitude maps to 448), returned in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(control: bool):
    if not control:
        return partial(jnp.einsum, precision=HI)
    return lambda spec, a, b: jnp.einsum(spec, fp8(a), fp8(b), precision=HI)


def _layer(d: Dims, control: bool, x, p):
    S = x.shape[0]
    pos = jnp.arange(S)
    ein = _ein(control)
    h = _rms(x, p["norm1"]["scale"], d.rms_eps)
    a = p["attn"]
    q = ein("sd,dhk->shk", h, a["wq"])
    k = ein("sd,dhk->shk", h, a["wk"])
    v = ein("sd,dhk->shk", h, a["wv"])
    if d.qk_norm:
        q = _rms(q, a["q_norm"], d.rms_eps)
        k = _rms(k, a["k_norm"], d.rms_eps)
    q, k = _rope(q, pos, d.rope_theta), _rope(k, pos, d.rope_theta)
    group = d.n_heads // d.n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = ein("qhd,khd->hqk", q, k) * d.head_dim ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    ctx = ein("hqk,khd->qhd", prob, v)
    x = x + ein("shk,hkd->sd", ctx, a["wo"])
    h2 = _rms(x, p["norm2"]["scale"], d.rms_eps)
    m = p["mlp"]
    up = ein("sd,df->sf", h2, m["w_up"])
    gate = ein("sd,df->sf", h2, m["w_gate"])
    x = x + ein("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"])
    return x, None


@partial(jax.jit, static_argnums=(0, 1))
def hidden(d: Dims, control: bool, w, tokens):
    """Final-normed hidden states (S, D) of one padded sequence."""
    x = jnp.take(w["embed"]["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(partial(_layer, d, control), x,
                        w["segments"][0]["p"])
    return _rms(x, w["final_norm"]["scale"], d.rms_eps)


@jax.jit
def _gaps(h, emb, choose):
    """Per row: the reference's best logit minus its logit at ``choose``
    (a vector of token ids), in blocks of ROWS rows."""
    def block(args):
        hb, cb = args
        lg = jnp.matmul(hb, emb.T, precision=HI)
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, cb[:, None], 1)[:, 0]

    n = h.shape[0] // ROWS
    g = jax.lax.map(block, (h.reshape(n, ROWS, -1), choose.reshape(n, ROWS)))
    return g.reshape(-1)


@jax.jit
def _control_argmax(h, emb):
    """Per row, the token the control's logits put first."""
    n = h.shape[0] // ROWS
    emb8 = fp8(emb)

    def block(hb):
        lg = jnp.matmul(fp8(hb), emb8.T, precision=HI)
        return jnp.argmax(lg, -1).astype(jnp.int32)

    return jax.lax.map(block, h.reshape(n, ROWS, -1)).reshape(-1)


def _pad(seq: list) -> jnp.ndarray:
    n = -(-len(seq) // BUCKET) * BUCKET
    return jnp.asarray(list(seq) + [0] * (n - len(seq)), jnp.int32)


def served_gaps(d: Dims, w32, prompt: list, served: list) -> jnp.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where it is the argmax)."""
    seq = list(prompt) + list(served[:-1])
    toks = _pad(seq)
    h = hidden(d, False, w32, toks)
    choose = jnp.zeros_like(toks).at[len(prompt) - 1:len(seq)].set(
        jnp.asarray(served, jnp.int32))
    g = _gaps(h, w32["embed"]["embed"], choose)
    return g[len(prompt) - 1:len(seq)]


def control_gaps(d: Dims, w32, prompt: list, served: list):
    """The control at the same prompts and served tokens: at each position
    the token the float8 forward puts first, and how far its reference
    logit lies below the reference's best."""
    seq = list(prompt) + list(served[:-1])
    toks = _pad(seq)
    emb = w32["embed"]["embed"]
    top = _control_argmax(hidden(d, True, w32, toks), emb)
    h = hidden(d, False, w32, toks)
    g = _gaps(h, emb, top)
    return g[len(prompt) - 1:len(seq)]
