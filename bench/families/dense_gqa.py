"""The dense GQA family: a configuration file's sizes, the program's
``ModelConfig``, the weights, the plain reference and its control, and the
operations and bytes of the model and of its kernels.

The block: RMSNorm, RoPE (rotate-half over the whole head), optional
per-head qk-norm, causal softmax attention with grouped KV heads, SwiGLU,
tied embeddings.  ``dims`` refuses every published key this family does
not model, so a configuration with another mechanism (partial or scaled
RoPE, untied embeddings, another norm eps) needs a family of its own.

The weights are the benchmark's: one jitted call draws every leaf from
``--seed`` in the tree layout the program serves (``init_params``'s
layout for a dense GQA stack with tied embeddings), in the dtype the
file states, at the scales the program's own initialiser uses.  The
plain reference builds the same weights from the same seed with this
function, so it takes nothing the program made.

The reference is straightforward ``jax.numpy`` on one sequence at a
time: no cache, no pages, no kernels, no batching.  It imports nothing
of the program.  It keeps everything in float32 and runs every matrix
product at ``Precision.HIGHEST``.  The control (``control=True``) is the
same forward with both operands of every matrix product rounded to
float8 e4m3 at a per-tensor scale, the precision one step below the
bfloat16 products the configuration states; the products themselves stay
exact.  Both read the float32 weights; the control rounds one layer at a
time, so it needs no second copy of the model.

Model FLOPs (``mfu_pct``) count the work the requests asked for, not
what the program computed for padded or finished lanes.  A kernel's
operations and bytes count the work it was handed: every lane and step
of each call as the engine shaped it (finished lanes of a decode scan
included, padded lanes of a prefill group skipped as the kernel skips
them), at the keys each query may attend, so a share of its roofline
measures the kernel alone and the engine's waste shows elsewhere
(``decode_tokens_per_call``).  Bytes count each operand once (what a
kernel that reuses everything on chip would move), so a share of the
roofline never exceeds what the kernel could reach.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0     # largest finite float8 e4m3
ROWS = 256          # logit rows per block, so (ROWS, vocab) fits beside
BUCKET = 512        # sequences are padded to a multiple (causal: inert)
F32 = 4
# the published keys this family models; any other key is refused
MODELLED = {"architectures", "model_type", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "vocab_size", "max_position_embeddings", "rope_theta",
            "rms_norm_eps", "tie_word_embeddings", "hidden_act",
            "attention_bias", "torch_dtype"}


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
    unknown = sorted(set(m) - MODELLED)
    if unknown:
        raise ValueError(f"dense_gqa does not model {unknown}")
    if m.get("model_type") != "qwen3":
        raise ValueError(f"dense_gqa does not model {m.get('model_type')!r}")
    if not m.get("tie_word_embeddings"):
        raise ValueError("dense_gqa models tied embeddings only")
    if m.get("hidden_act", "silu") != "silu" or m.get("attention_bias"):
        raise ValueError("dense_gqa models SwiGLU without attention bias")
    if float(m["rms_norm_eps"]) != 1e-6:
        raise ValueError("the program's RMSNorm eps is 1e-6")
    return Dims(n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                n_heads=m["num_attention_heads"],
                n_kv_heads=m["num_key_value_heads"],
                head_dim=m.get("head_dim") or (m["hidden_size"]
                                               // m["num_attention_heads"]),
                d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                qk_norm=bool(cfg_file.get("qk_norm")),
                rope_theta=float(m["rope_theta"]),
                rms_eps=float(m["rms_norm_eps"]))


def program_config(cfg_file: dict, name: str):
    """The program's ``ModelConfig`` for this file."""
    from repro.models.config import ModelConfig
    d = dims(cfg_file)
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


# -- the plain reference and its control ---------------------------------

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


# -- operations and bytes -------------------------------------------------

def layer_matmul_flops(d: Dims) -> int:
    """Projection and FFN FLOPs of one token through one layer."""
    qkv = 2 * d.d_model * (d.n_heads + 2 * d.n_kv_heads) * d.head_dim
    o = 2 * d.n_heads * d.head_dim * d.d_model
    ffn = 2 * 3 * d.d_model * d.d_ff
    return qkv + o + ffn


def attn_flops(d: Dims, keys: int) -> int:
    """QK^T and PV FLOPs of one query over ``keys`` keys, one layer."""
    return 4 * d.n_heads * d.head_dim * keys


def prefill_model_flops(d: Dims, prompt: int) -> int:
    """A whole prompt through the stack, causal (position i attends i+1
    keys), and one projection to the vocabulary for the first token."""
    keys = prompt * (prompt + 1) // 2
    return (d.n_layers * (prompt * layer_matmul_flops(d)
                          + attn_flops(d, 1) * keys)
            + 2 * d.d_model * d.vocab)


def decode_model_flops(d: Dims, context: int) -> int:
    """One decoded token whose query attends ``context`` keys."""
    return (d.n_layers * (layer_matmul_flops(d) + attn_flops(d, context))
            + 2 * d.d_model * d.vocab)


def prefill_call_cost(d: Dims, pos0, chunk) -> tuple[int, int]:
    """(ops, bytes) of ``paged_prefill_attention`` over one prefill call,
    all layers: each lane's ``chunk`` new positions after ``pos0`` cached
    ones, causal (position i attends pos0 + i + 1 keys); q in and out,
    the chunk's K/V in and written to pages, and every key read once."""
    ops = nbytes = 0
    for p, n in zip(pos0, chunk):
        p, n = int(p), int(n)
        if n <= 0:
            continue
        keys = n * p + n * (n + 1) // 2
        ops += attn_flops(d, 1) * keys
        nbytes += (2 * n * d.n_heads * d.head_dim
                   + 2 * (2 * n + p + n) * d.n_kv_heads * d.head_dim) * F32
    return d.n_layers * ops, d.n_layers * nbytes


def decode_call_cost(d: Dims, pos0, steps, n_steps: int) -> tuple[int,
                                                                   int]:
    """(ops, bytes) of ``paged_decode_attention`` over one decode scan of
    ``n_steps`` steps, all layers.  Every lane runs every step; a lane
    with a budget of ``k`` steps advances k times and then stays frozen
    (the kernel still attends its pos0 + k + 1 keys), so at step s it
    attends pos0 + min(s, k) + 1 keys.  q in and out, K/V read once."""
    n = int(n_steps)
    keys = 0
    for p, k in zip(pos0, steps):
        p, k = int(p), min(int(k), n)
        keys += n * (int(p) + 1) + k * (k - 1) // 2 + k * (n - k)
    ops = attn_flops(d, keys)
    qo = 2 * n * len(pos0) * d.n_heads * d.head_dim * F32
    kv = 2 * keys * d.n_kv_heads * d.head_dim * F32
    return d.n_layers * ops, d.n_layers * (qo + kv)


def _prefill_kernel(d: Dims, call):
    kind, _, pos0, chunk, _ = call
    return prefill_call_cost(d, pos0, chunk) if kind == "prefill" else None


def _decode_kernel(d: Dims, call):
    kind, _, pos0, steps, n_steps = call
    return (decode_call_cost(d, pos0, steps, n_steps) if kind == "decode"
            else None)


# device-trace kernel name -> (ops, bytes) of one recorded engine call
# (kind, t, pos0, chunk or steps, n_steps), None for a call it is not in
KERNELS = {"paged_prefill_attention": _prefill_kernel,
           "paged_decode_attention": _decode_kernel}
