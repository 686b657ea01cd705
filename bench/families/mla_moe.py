"""The MLA + MoE family (DeepSeek-V2, ``model_type`` ``deepseek_v2``): a
configuration file's sizes, the program's ``ModelConfig``, the weights,
the plain reference and its control, and the operations and bytes of the
model and of its kernel.

The block: RMSNorm; multi-head latent attention (MLA, DeepSeek-V2
arXiv:2405.04434 section 2.1) without a query LoRA: the keys and values
come of one compressed latent (``kv_lora_rank``, RMS-normed) and a
decoupled rotary key shared by every head; YaRN rope scaling
(arXiv:2309.00071) on the rotary dims and mscale(factor,
mscale_all_dim)**2 on the softmax scale; then the first
``first_k_dense_replace`` layers a SwiGLU FFN, every later layer an MoE
FFN: a softmax router over all routed experts, greedy top-k, the top-k
scores themselves as weights (``norm_topk_prob`` false), plus the shared
experts as one SwiGLU of
``n_shared_experts`` times the expert width.  Untied embeddings.
``dims`` refuses every published key and value it does not model: a
query LoRA, grouped routing (``n_group``/``topk_group`` != 1), another
``topk_method`` or ``scoring_func``, a ``routed_scaling_factor`` other
than 1, MoE layers not every layer, another rope scaling or norm eps.  ``seq_aux`` is accepted: the sequence-level
auxiliary loss is a training term, and serving has none.

The file's layout: the published ``config.json`` keys as run (with
``torch_dtype``) stand in its ``config`` group, which the harness and
this module read, and again at its top level, where a configuration is
compared with the catalog's published entry key by key; ``dims`` refuses
a file whose two copies differ.  The other top-level keys
(``FILE_KEYS``) describe the cut.  The expert share: ``n_routed_experts``
is the number of routed experts held here, the router's first ones, and
``router_experts`` the router's published width.  The router keeps its
width and its top-k; the program and the reference alike compute only
the held experts' part of each MoE layer (plus the shared experts) and
pass that on to the next layer, as one expert-parallel rank does.

The weights are the benchmark's: one jitted call draws every leaf from
``--seed`` in the tree layout the program serves (``init_params``'s
layout: a one-layer ``mla`` segment, then a stacked ``mla_moe`` segment,
untied head), in the dtype the file states, at the scales the program's
own initialisers use.  The plain reference reads the same weights.

The reference is straightforward ``jax.numpy`` on one sequence at a
time: no cache, no pages, no kernels, no batching, no absorption (keys
and values are expanded from the latent, as the paper writes MLA), and
the MoE layer as a dense sum over the held experts with each token's
routing weight (zero where the router did not pick the expert).  It
imports nothing of the program, keeps everything in float32 and runs
every matrix product at ``Precision.HIGHEST``.  Departures from the
published modelling code, none of which changes the model: the rotary
dims are in rotate-half order, where the published code permutes
``q_pe``/``k_pe`` from interleaved pairs first (with random weights a
relabelling of the projection's columns); the latent and the rotary key
come of two matrices where the published code has one
(``kv_a_proj_with_mqa``, the same columns); ``kv_b_proj`` is held as
``w_uk`` and ``w_uv``.  The control (``control=True``) is the same
forward with both operands of every matrix product (the router's
included) rounded to float8 e4m3 at a per-tensor scale, one step below
the bfloat16 products the configuration states.

Model FLOPs count the chip's share of the work the requests asked for:
the absorbed MLA the program runs (queries folded into the latent,
attention over the latent and the rotary key, the context up-projected),
the dense FFN, the shared experts, the router, and of the routed experts
the expected share of the deployment's top-k work that lands on the held
experts (top_k x held / router width per token).  The one kernel,
``mla_paged_prefill_attention``, counts the work each prefill call handed
it, as ``dense_gqa`` counts its kernels.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0     # largest finite float8 e4m3
ROWS = 256          # logit rows per block, so (ROWS, vocab) fits beside
BUCKET = 512        # sequences are padded to a multiple (causal: inert)
F32 = 4
# the published keys this family models; any other key is refused
MODELLED = {"attention_bias", "first_k_dense_replace", "hidden_act",
            "hidden_size", "intermediate_size", "kv_lora_rank",
            "max_position_embeddings", "model_type", "moe_intermediate_size",
            "moe_layer_freq", "n_group", "n_routed_experts",
            "n_shared_experts", "norm_topk_prob", "num_attention_heads",
            "num_experts_per_tok", "num_hidden_layers",
            "num_key_value_heads", "q_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "rms_norm_eps", "rope_scaling", "rope_theta",
            "routed_scaling_factor", "scoring_func", "seq_aux",
            "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
            "vocab_size", "torch_dtype"}
# the file's own keys beside the published ones
FILE_KEYS = {"source", "family", "config", "changed", "router_experts",
             "deployment", "precision", "reference", "zero_load_ttft_s"}
YARN_KEYS = {"type", "factor", "original_max_position_embeddings",
             "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference and the counts need (hashable, so it can
    be a static argument of a jitted function)."""
    n_layers: int
    n_dense: int            # leading dense layers
    d_model: int
    n_heads: int
    kv_lora: int
    qk_nope: int
    qk_rope: int
    v_head: int
    d_ff: int               # the dense layers' FFN width
    router: int             # routed experts the router spans
    n_held: int             # routed experts held here: the router's first
    top_k: int
    d_expert: int
    n_shared: int
    norm_topk: bool
    vocab: int
    rope_theta: float
    yarn: tuple             # (factor, original max positions, beta_fast,
    #                          beta_slow, mscale_all_dim)
    rms_eps: float


def _refuse(why: str):
    raise ValueError(f"mla_moe does not model {why}")


def dims(cfg_file: dict) -> Dims:
    m = cfg_file["config"]
    if {k: v for k, v in cfg_file.items() if k not in FILE_KEYS} != m:
        raise ValueError("the file's top-level published keys differ from "
                         "its config group")
    unknown = sorted(set(m) - MODELLED)
    if unknown:
        _refuse(unknown)
    if m.get("model_type") != "deepseek_v2":
        _refuse(f"model_type {m.get('model_type')!r}")
    if m.get("q_lora_rank") is not None:
        _refuse("a query LoRA (q_lora_rank)")
    if m.get("topk_method") != "greedy":
        _refuse(f"topk_method {m.get('topk_method')!r}")
    if m.get("n_group") != 1 or m.get("topk_group") != 1:
        _refuse("grouped routing (n_group, topk_group)")
    if m.get("scoring_func") != "softmax":
        _refuse(f"scoring_func {m.get('scoring_func')!r}")
    if m.get("moe_layer_freq") != 1:
        _refuse("MoE layers other than every layer past the dense ones")
    if m.get("tie_word_embeddings") is not False:
        _refuse("tied or unstated tie_word_embeddings")
    if m.get("hidden_act") != "silu" or m.get("attention_bias"):
        _refuse("an activation other than silu, or attention bias")
    if float(m["routed_scaling_factor"]) != 1:
        _refuse("a routed_scaling_factor other than 1")
    if float(m["rms_norm_eps"]) != 1e-6:
        _refuse("an RMSNorm eps other than the program's 1e-6")
    if m["num_key_value_heads"] != m["num_attention_heads"]:
        _refuse("grouped KV heads beside MLA")
    rs = m.get("rope_scaling")
    if not (isinstance(rs, dict) and rs.get("type") == "yarn"
            and set(rs) <= YARN_KEYS
            and rs.get("mscale", 1) == rs.get("mscale_all_dim", 0)):
        # unequal mscales would scale cos/sin too, which is not modelled
        _refuse(f"rope_scaling {rs!r}")
    router = cfg_file.get("router_experts", m["n_routed_experts"])
    n_held = m["n_routed_experts"]
    if not 0 < n_held <= router:
        raise ValueError(f"{n_held} experts held, outside the router's "
                         f"{router}")
    if not 0 <= m["first_k_dense_replace"] < m["num_hidden_layers"]:
        _refuse("a stack without MoE layers")
    return Dims(
        n_layers=m["num_hidden_layers"], n_dense=m["first_k_dense_replace"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        kv_lora=m["kv_lora_rank"], qk_nope=m["qk_nope_head_dim"],
        qk_rope=m["qk_rope_head_dim"], v_head=m["v_head_dim"],
        d_ff=m["intermediate_size"], router=router, n_held=n_held,
        top_k=m["num_experts_per_tok"],
        d_expert=m["moe_intermediate_size"], n_shared=m["n_shared_experts"],
        norm_topk=bool(m["norm_topk_prob"]),
        vocab=m["vocab_size"], rope_theta=float(m["rope_theta"]),
        yarn=(float(rs["factor"]),
              int(rs["original_max_position_embeddings"]),
              float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
              float(rs.get("mscale_all_dim", 0))),
        rms_eps=float(m["rms_norm_eps"]))


def program_config(cfg_file: dict, name: str):
    """The program's ``ModelConfig`` for this file: absorbed MLA decode
    (the published decode form) and the held expert share."""
    from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                     YarnScaling)
    d = dims(cfg_file)
    factor, orig, fast, slow, msc_all = d.yarn
    return ModelConfig(
        name=name, arch_type="moe", n_layers=d.n_layers, d_model=d.d_model,
        n_heads=d.n_heads, n_kv_heads=d.n_heads, d_ff=d.d_ff, vocab=d.vocab,
        block_pattern=("mla",) * d.n_dense
        + ("mla_moe",) * (d.n_layers - d.n_dense),
        rope_theta=d.rope_theta,
        rope_scaling=YarnScaling(
            factor=factor, original_max_position_embeddings=orig,
            beta_fast=fast, beta_slow=slow, mscale_all_dim=msc_all),
        tie_embeddings=False,
        mla=MLAConfig(kv_lora_rank=d.kv_lora, q_lora_rank=None,
                      qk_nope_head_dim=d.qk_nope, qk_rope_head_dim=d.qk_rope,
                      v_head_dim=d.v_head),
        moe=MoEConfig(n_experts=d.router, top_k=d.top_k,
                      d_ff_expert=d.d_expert, n_shared=d.n_shared,
                      n_held=d.n_held, norm_topk_prob=d.norm_topk),
        mla_absorb=True, source=cfg_file["source"])


@partial(jax.jit, static_argnums=(1, 2))
def _weights(key, d: Dims, dtype):
    D, H, r, F = d.d_model, d.n_heads, d.kv_lora, d.d_expert
    ks = iter(jax.random.split(key, 24))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    def mla(L):
        return {"w_dkv": normal((L, D, r), D ** -0.5),
                "w_krope": normal((L, D, d.qk_rope), D ** -0.5),
                "w_uk": normal((L, r, H, d.qk_nope), r ** -0.5),
                "w_uv": normal((L, r, H, d.v_head), r ** -0.5),
                "wo": normal((L, H, d.v_head, D), (H * d.v_head) ** -0.5),
                "kv_norm": ones(L, r),
                "wq": normal((L, D, H, d.qk_nope + d.qk_rope), D ** -0.5)}

    def norms(L):
        return {"norm1": {"scale": ones(L, D)},
                "norm2": {"scale": ones(L, D)}}

    def squeeze(tree):                  # a one-layer segment is unstacked
        return jax.tree.map(lambda a: a[0], tree)

    Ld, Lm, S = d.n_dense, d.n_layers - d.n_dense, d.n_shared * F
    dense = {**norms(Ld), "attn": mla(Ld),
             "mlp": {"w_up": normal((Ld, D, d.d_ff), D ** -0.5),
                     "w_down": normal((Ld, d.d_ff, D), d.d_ff ** -0.5),
                     "w_gate": normal((Ld, D, d.d_ff), D ** -0.5)}}
    moe = {**norms(Lm), "attn": mla(Lm),
           "moe": {"router": jax.random.normal(next(ks), (Lm, D, d.router),
                                               jnp.float32) * D ** -0.5,
                   "w_gate": normal((Lm, d.n_held, D, F), D ** -0.5),
                   "w_up": normal((Lm, d.n_held, D, F), D ** -0.5),
                   "w_down": normal((Lm, d.n_held, F, D), F ** -0.5),
                   "shared_gate": normal((Lm, D, S), D ** -0.5),
                   "shared_up": normal((Lm, D, S), D ** -0.5),
                   "shared_down": normal((Lm, S, D), S ** -0.5)}}
    segs = []
    for L, p in ((Ld, dense), (Lm, moe)):
        if L:
            segs.append({"p": squeeze(p) if L == 1 else p})
    return {"embed": {"embed": normal((d.vocab, D), 0.02)},
            "final_norm": {"scale": ones(D)},
            "unembed": normal((D, d.vocab), D ** -0.5),
            "segments": segs}


def make_weights(d: Dims, seed: int, dtype=jnp.float32):
    """Every weight from ``seed`` in one jitted call on the default
    device."""
    return _weights(jax.random.PRNGKey(seed), d, dtype)


# -- the plain reference and its control ---------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d: Dims):
    """The rotary inverse frequencies under YaRN, as the published
    modelling code computes them: extrapolated below the ``beta_fast``
    correction dim, interpolated (divided by the factor) above the
    ``beta_slow`` one, a linear ramp between."""
    factor, orig, fast, slow, _ = d.yarn
    dim, base = d.qk_rope, d.rope_theta

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = 1.0 / (factor * base ** (jnp.arange(0, dim, 2,
                                                dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    keep = 1.0 - ramp                   # 1: extrapolate, 0: interpolate
    return inter * (1 - keep) + extra * keep


def softmax_scale(d: Dims) -> float:
    factor, _, _, _, msc_all = d.yarn
    s = (d.qk_nope + d.qk_rope) ** -0.5
    return s * yarn_mscale(factor, msc_all) ** 2 if msc_all else s


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


def _rope(x, pos, inv_freq):
    """Rotate-half RoPE of x (S, ..., rope) at positions ``pos`` (S,)."""
    ang = pos[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def fp8(x):
    """``x`` rounded to float8 e4m3 at a per-tensor scale (its largest
    magnitude maps to 448), returned in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(control: bool):
    if not control:
        return partial(jnp.einsum, precision=HI)
    return lambda spec, a, b: jnp.einsum(spec, fp8(a), fp8(b), precision=HI)


def _attention(d: Dims, ein, x, p):
    """MLA as the paper writes it: keys and values expanded from the
    latent, the decoupled rotary key shared by all heads."""
    S = x.shape[0]
    pos = jnp.arange(S)
    inv_freq = yarn_inv_freq(d)
    h = _rms(x, p["norm1"]["scale"], d.rms_eps)
    a = p["attn"]
    q = ein("sd,dhk->shk", h, a["wq"])
    q_nope, q_pe = q[..., :d.qk_nope], _rope(q[..., d.qk_nope:], pos,
                                             inv_freq)
    latent = _rms(ein("sd,dr->sr", h, a["w_dkv"]), a["kv_norm"], d.rms_eps)
    k_pe = _rope(ein("sd,dr->sr", h, a["w_krope"]), pos, inv_freq)
    k_nope = ein("sr,rhk->shk", latent, a["w_uk"])
    v = ein("sr,rhk->shk", latent, a["w_uv"])
    s = (ein("qhn,khn->hqk", q_nope, k_nope)
         + ein("qhr,kr->hqk", q_pe, k_pe)) * softmax_scale(d)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -1e30)
    ctx = ein("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v)
    return x + ein("qhv,hvd->qd", ctx, a["wo"])


def _swiglu(ein, h, w_gate, w_up, w_down):
    return ein("sf,fd->sd", jax.nn.silu(ein("sd,df->sf", h, w_gate))
               * ein("sd,df->sf", h, w_up), w_down)


def _dense_layer(d: Dims, control: bool, x, p):
    ein = _ein(control)
    x = _attention(d, ein, x, p)
    h = _rms(x, p["norm2"]["scale"], d.rms_eps)
    m = p["mlp"]
    return x + _swiglu(ein, h, m["w_gate"], m["w_up"], m["w_down"]), None


def _moe_layer(d: Dims, control: bool, x, p):
    """The held experts' part of the routed sum, plus the shared experts:
    each held expert runs on every token, weighted by the token's routing
    weight for it (0 where the router did not pick it)."""
    ein = _ein(control)
    x = _attention(d, ein, x, p)
    h = _rms(x, p["norm2"]["scale"], d.rms_eps)
    m = p["moe"]
    probs = jax.nn.softmax(ein("sd,de->se", h, m["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], idx].set(top)
    weight = weight[:, :d.n_held]                                # (S, held)
    gate = ein("sd,edf->esf", h, m["w_gate"])
    up = ein("sd,edf->esf", h, m["w_up"])
    y = ein("esf,efd->esd", jax.nn.silu(gate) * up, m["w_down"])
    routed = ein("esd,se->sd", y, weight)
    shared = _swiglu(ein, h, m["shared_gate"], m["shared_up"],
                     m["shared_down"])
    return x + routed + shared, None


@partial(jax.jit, static_argnums=(0, 1))
def hidden(d: Dims, control: bool, w, tokens):
    """Final-normed hidden states (S, D) of one padded sequence."""
    x = jnp.take(w["embed"]["embed"], tokens, axis=0).astype(jnp.float32)
    segs = iter(w["segments"])
    for layer, count in ((_dense_layer, d.n_dense),
                         (_moe_layer, d.n_layers - d.n_dense)):
        if count == 1:
            x, _ = layer(d, control, x, next(segs)["p"])
        elif count:
            x, _ = jax.lax.scan(partial(layer, d, control), x,
                                next(segs)["p"])
    return _rms(x, w["final_norm"]["scale"], d.rms_eps)


@jax.jit
def _gaps(h, head, choose):
    """Per row: the reference's best logit minus its logit at ``choose``
    (a vector of token ids), in blocks of ROWS rows."""
    def block(args):
        hb, cb = args
        lg = jnp.matmul(hb, head, precision=HI)
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, cb[:, None], 1)[:, 0]

    n = h.shape[0] // ROWS
    g = jax.lax.map(block, (h.reshape(n, ROWS, -1), choose.reshape(n, ROWS)))
    return g.reshape(-1)


@jax.jit
def _control_argmax(h, head):
    """Per row, the token the control's logits put first."""
    n = h.shape[0] // ROWS
    head8 = fp8(head)

    def block(hb):
        lg = jnp.matmul(fp8(hb), head8, precision=HI)
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
    g = _gaps(h, w32["unembed"], choose)
    return g[len(prompt) - 1:len(seq)]


def control_gaps(d: Dims, w32, prompt: list, served: list):
    """The control at the same prompts and served tokens: at each position
    the token the float8 forward puts first, and how far its reference
    logit lies below the reference's best."""
    seq = list(prompt) + list(served[:-1])
    toks = _pad(seq)
    top = _control_argmax(hidden(d, True, w32, toks), w32["unembed"])
    h = hidden(d, False, w32, toks)
    g = _gaps(h, w32["unembed"], top)
    return g[len(prompt) - 1:len(seq)]


# -- operations and bytes -------------------------------------------------

def latent_width(d: Dims) -> int:
    """One token's cached latent: the compressed kv and the rotary key."""
    return d.kv_lora + d.qk_rope


def mla_matmul_flops(d: Dims) -> int:
    """Projection FLOPs of one token through one absorbed MLA layer: the
    query, the latent and rotary key, the query folded into the latent
    (q_nope . w_uk), the latent context up-projected (. w_uv), and wo."""
    H = d.n_heads
    q = 2 * d.d_model * H * (d.qk_nope + d.qk_rope)
    kv = 2 * d.d_model * latent_width(d)
    absorb = 2 * H * d.qk_nope * d.kv_lora + 2 * H * d.kv_lora * d.v_head
    o = 2 * H * d.v_head * d.d_model
    return q + kv + absorb + o


def attn_flops(d: Dims, keys: int) -> int:
    """Scores over latent and rotary key and the latent context of one
    query over ``keys`` keys, one layer."""
    return 2 * d.n_heads * (latent_width(d) + d.kv_lora) * keys


def ffn_flops(d: Dims, layer: int) -> int:
    """FFN FLOPs of one token through ``layer``: the dense SwiGLU, or the
    router, the shared experts and the held experts' expected share of
    the top-k routed work."""
    if layer < d.n_dense:
        return 2 * 3 * d.d_model * d.d_ff
    per_expert = 2 * 3 * d.d_model * d.d_expert
    routed = per_expert * d.top_k * d.n_held / d.router
    return round(2 * d.d_model * d.router + d.n_shared * per_expert + routed)


def layer_flops(d: Dims) -> int:
    """Projection and FFN FLOPs of one token through the whole stack."""
    return sum(mla_matmul_flops(d) + ffn_flops(d, i)
               for i in range(d.n_layers))


def prefill_model_flops(d: Dims, prompt: int) -> int:
    """A whole prompt through the stack, causal (position i attends i+1
    keys), and one projection to the vocabulary for the first token."""
    keys = prompt * (prompt + 1) // 2
    return (prompt * layer_flops(d) + d.n_layers * attn_flops(d, keys)
            + 2 * d.d_model * d.vocab)


def decode_model_flops(d: Dims, context: int) -> int:
    """One decoded token whose query attends ``context`` keys."""
    return (layer_flops(d) + d.n_layers * attn_flops(d, context)
            + 2 * d.d_model * d.vocab)


def prefill_call_cost(d: Dims, pos0, chunk) -> tuple[int, int]:
    """(ops, bytes) of ``mla_paged_prefill_attention`` over one prefill
    call, all layers: each lane's ``chunk`` new positions after ``pos0``
    cached ones, causal; the absorbed and rotary queries in and the
    latent context out, the chunk's latents in and written to pages, and
    every cached latent read once (at the model's rotary width; the pool
    pads it to 128 lanes)."""
    ops = nbytes = 0
    H, lat = d.n_heads, latent_width(d)
    for p, n in zip(pos0, chunk):
        p, n = int(p), int(n)
        if n <= 0:
            continue
        keys = n * p + n * (n + 1) // 2
        ops += attn_flops(d, keys)
        q_io = n * H * (d.kv_lora + d.qk_rope) + n * H * d.kv_lora
        nbytes += (q_io + (2 * n + p + n) * lat) * F32
    return d.n_layers * ops, d.n_layers * nbytes


def _prefill_kernel(d: Dims, call):
    kind, _, pos0, chunk, _ = call
    return prefill_call_cost(d, pos0, chunk) if kind == "prefill" else None


# device-trace kernel name -> (ops, bytes) of one recorded engine call
# (kind, t, pos0, chunk or steps, n_steps), None for a call it is not in.
# Decode runs the absorbed attention as XLA operations, not a kernel.
KERNELS = {"mla_paged_prefill_attention": _prefill_kernel}
