"""Sharding rules: parameter/activation/cache PartitionSpecs per mesh.

Strategy (DESIGN.md §6):
  * tensor parallel on "model": attention heads, FFN hidden dim, experts,
    vocab;
  * data parallel on "data" (x "pod"): batch dim of activations / inputs;
  * FSDP-style weight sharding on "data" for params whose replicated copy
    would not fit HBM (always on here: it is a strict memory win and XLA
    re-gathers at use);
  * long-context (batch 1) shapes shard the KV/sequence dim on "data".

Rules are keyed by parameter path regexes, mirroring how production JAX
frameworks (MaxText et al.) express logical-axis rules.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def _data_axes(mesh: Mesh):
    return (("pod", "data") if "pod" in mesh.axis_names else "data")


# (regex over path, spec builder(data_ax) -> tuple of axis names/None)
# Paths look like: segments/0/p/attn/wq, shared_attn/moe/w_gate, embed/embed
_RULES = [
    # embeddings / unembedding: vocab on model, d_model on data
    (r"embed/embed$",            lambda d: ("model", d)),
    (r"pos_embed$",              lambda d: (None, d)),
    (r"unembed$",                lambda d: (d, "model")),
    # attention: stacked segments have a leading layer axis handled later
    (r"attn/wq$",                lambda d: (d, "model", None)),
    (r"attn/wk$",                lambda d: (d, "model", None)),
    (r"attn/wv$",                lambda d: (d, "model", None)),
    (r"attn/wo$",                lambda d: ("model", None, d)),
    (r"cross/wq$",               lambda d: (d, "model", None)),
    (r"cross/wk$",               lambda d: (d, "model", None)),
    (r"cross/wv$",               lambda d: (d, "model", None)),
    (r"cross/wo$",               lambda d: ("model", None, d)),
    # MLA
    (r"attn/w_dkv$",             lambda d: (d, None)),
    (r"attn/w_krope$",           lambda d: (d, None)),
    (r"attn/w_uk$",              lambda d: (None, "model", None)),
    (r"attn/w_uv$",              lambda d: (None, "model", None)),
    (r"attn/w_dq$",              lambda d: (d, None)),
    (r"attn/w_uq$",              lambda d: (None, "model", None)),
    # dense FFN
    (r"mlp/w_gate$",             lambda d: (d, "model")),
    (r"mlp/w_up$",               lambda d: (d, "model")),
    (r"mlp/w_down$",             lambda d: ("model", d)),
    (r"mlp/b_up$",               lambda d: ("model",)),
    # MoE: expert parallel on model, d_model on data
    (r"moe/router$",             lambda d: (d, None)),
    (r"moe/w_gate$",             lambda d: ("model", d, None)),
    (r"moe/w_up$",               lambda d: ("model", d, None)),
    (r"moe/w_down$",             lambda d: ("model", None, d)),
    (r"moe/shared_gate$",        lambda d: (d, "model")),
    (r"moe/shared_up$",          lambda d: (d, "model")),
    (r"moe/shared_down$",        lambda d: ("model", d)),
    # SSM: inner channels on model
    (r"ssm/w_in$",               lambda d: (d, "model")),
    (r"ssm/conv_w$",             lambda d: (None, "model")),
    (r"ssm/conv_b$",             lambda d: ("model",)),
    (r"ssm/w_out$",              lambda d: ("model", d)),
    (r"ssm/norm_scale$",         lambda d: ("model",)),
    # encoder (whisper)
    (r"encoder/pos$",            lambda d: (None, None)),
]


def param_spec(path: str, shape: tuple, mesh: Mesh,
               stacked: bool, mode: str = "fsdp") -> P:
    """mode="fsdp" (default): weights sharded over "data" on one dim
    (often the contracting one) + tensor parallel over "model".
    mode="tp": weights replicated over "data" - removes the activation
    reshard collectives that fsdp-on-contracting-dims induces
    (EXPERIMENTS.md Perf iteration 5); viable when params/TP fit HBM."""
    d = _data_axes(mesh)
    for pat, builder in _RULES:
        if re.search(pat, path):
            spec = tuple(builder(d))
            if mode == "tp":
                spec = tuple(None if ax == d else ax for ax in spec)
            if stacked:
                spec = (None,) + spec
            spec = spec[:len(shape)]
            # drop axes that do not divide the dimension evenly
            spec = tuple(_fit(ax, dim, mesh) for ax, dim in
                         zip(spec, shape))
            return P(*spec)
    return P()                                   # replicate (norms, scalars)


def _axis_size(mesh: Mesh, ax) -> int:
    if ax is None:
        return 1
    if isinstance(ax, tuple):
        return int(np.prod([mesh.shape[a] for a in ax]))
    return mesh.shape[ax]


def _fit(ax, dim: int, mesh: Mesh):
    if ax is None:
        return None
    if dim % _axis_size(mesh, ax) == 0:
        return ax
    if isinstance(ax, tuple):                    # try a shorter axis product
        for sub in (ax[1:], ax[:1]):
            if sub and dim % _axis_size(mesh, sub) == 0:
                return sub if len(sub) > 1 else sub[0]
    return None


def _path_str(kp) -> str:
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def params_shardings(params_shape, cfg: ModelConfig, mesh: Mesh,
                     mode: str = "fsdp"):
    """NamedShardings for an (abstract) params pytree."""
    segs = cfg.segments()

    def one(kp, leaf):
        path = _path_str(kp)
        stacked = False
        m = re.match(r"segments/(\d+)/", path)
        if m:
            stacked = segs[int(m.group(1))][1] > 1
        if path.startswith("encoder/layers/"):
            stacked = True
        return NamedSharding(mesh, param_spec(path, leaf.shape, mesh,
                                              stacked, mode))

    return jax.tree_util.tree_map_with_path(one, params_shape)


# --------------------------- activations/io ---------------------------- #
def batch_spec(mesh: Mesh, batch: int, extra_dims: int = 1) -> P:
    """Shard the batch dim over (pod, data) when divisible, else fewer."""
    d = _data_axes(mesh)
    ax = _fit(d, batch, mesh)
    return P(ax, *([None] * extra_dims))


def cache_shardings(cache_shape, cfg: ModelConfig, mesh: Mesh, batch: int,
                    seq_shard: bool = False):
    """KV cache: batch on data (x pod); kv-heads on model where divisible.
    seq_shard=True (long-context, batch=1): sequence dim on data instead."""
    d = _data_axes(mesh)
    segs = cfg.segments()

    def one(kp, leaf):
        path = _path_str(kp)
        m = re.match(r"(\d+)/", path)
        stacked = bool(m) and segs[int(m.group(1))][1] > 1
        pre = (None,) if stacked else ()
        shape = leaf.shape[1:] if stacked else leaf.shape
        b_ax = _fit(d, shape[0], mesh) if not seq_shard else None
        if path.endswith("/k") or path.endswith("/v"):
            seq_ax = _fit(d, shape[1], mesh) if seq_shard else None
            kv_ax = _fit("model", shape[2], mesh)
            spec = pre + (b_ax, seq_ax, kv_ax, None)
        elif path.endswith("ckv") or path.endswith("krope"):
            seq_ax = _fit(d, shape[1], mesh) if seq_shard else None
            spec = pre + (b_ax, seq_ax, None)
        elif path.endswith("state"):                  # (B, H, P, N)
            h_ax = _fit("model", shape[1], mesh)
            spec = pre + (b_ax, h_ax, None, None)
        elif path.endswith("conv"):                   # (B, K-1, C)
            c_ax = _fit("model", shape[2], mesh)
            spec = pre + (b_ax, None, c_ax)
        else:
            spec = pre + (b_ax,) + (None,) * (len(shape) - 1)
        return NamedSharding(mesh, P(*spec[:len(leaf.shape)]))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


# ======================== serving (mesh-sharded engine) ================= #
# The rules above shard TRAINING.  Serving shards differently: cross-shard
# combination is always by CONCATENATION (all_gather of per-head context /
# psum of disjoint expert outputs), never a partial-sum of activations
# through an output projection — that is what keeps sharded greedy streams
# bit-identical to the single-device path (float addition order never
# changes for any token's logits).  Consequences:
#   * wq/wk/wv column-shard on the head axis; wo stays REPLICATED and is
#     applied after an all_gather of the per-head context;
#   * MoE experts shard on the expert axis with replicated routing; each
#     token's expert outputs are psum'd (exactly one shard contributes a
#     non-zero value per (token, expert) pair, and x + 0.0 is exact);
#   * MLA latent pools (ckv/krope) are headless vector tokens: every shard
#     computes identical page writes, so the pools stay REPLICATED while
#     q/k up-projections head-shard;
#   * SSM state is O(1) per request: compute is replicated, but the
#     at-rest conv/state buffers lane(slot)-shard to spread memory;
#   * embed/unembed/norms replicate so logits (and sampling) are computed
#     identically everywhere.

MLA_KINDS = ("mla", "mla_moe")


@dataclasses.dataclass(frozen=True)
class ServingShardPlan:
    """What actually shards for one model config on one serving mesh axis.

    Each flag is a divisibility-gated capability; the SAME plan object
    drives spec generation (at-rest placement + shard_map specs) and the
    in-model gather/psum decisions, so the two can never disagree."""
    axis: str                  # mesh axis name ("model" by default)
    size: int                  # number of shards along that axis
    heads: bool                # GQA q/kv heads shard (H % n == KVH % n == 0)
    mla_heads: bool            # MLA q heads shard (latent pools replicate)
    experts: bool              # MoE held experts shard (held % n == 0)
    mlp: bool                  # dense-FFN hidden dim shards (d_ff % n == 0)
    ssm_lanes: bool            # SSM state lane-shards at rest

    @property
    def any(self) -> bool:
        return self.heads or self.mla_heads or self.experts or self.mlp


def serving_shard_plan(cfg: ModelConfig, mesh: Mesh, axis: str = "model",
                       max_seqs: int = 0) -> ServingShardPlan:
    n = int(mesh.shape[axis])
    kinds = {k for k, _ in cfg.segments()}
    has_attn = bool(kinds - {"ssm"} - set(MLA_KINDS) - {"cross_attn"})
    has_mla = bool(kinds & set(MLA_KINDS))
    heads = (n > 1 and has_attn
             and cfg.n_heads % n == 0 and cfg.n_kv_heads % n == 0)
    mla_heads = n > 1 and has_mla and cfg.n_heads % n == 0
    experts = (n > 1 and cfg.moe is not None
               and cfg.moe.n_held % n == 0)
    mlp = n > 1 and cfg.d_ff > 0 and cfg.d_ff % n == 0
    ssm_lanes = (n > 1 and cfg.ssm is not None and "ssm" in kinds
                 and max_seqs > 0 and max_seqs % n == 0)
    return ServingShardPlan(axis=axis, size=n, heads=heads,
                            mla_heads=mla_heads, experts=experts,
                            mlp=mlp, ssm_lanes=ssm_lanes)


def make_serving_mesh(devices=None, axis: str = "model") -> Mesh:
    """A 1-D serving mesh over the given devices (default: all)."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), (axis,))


def tree_named(mesh: Mesh, specs):
    """Map a pytree of PartitionSpecs to NamedShardings on ``mesh``."""
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                        is_leaf=lambda x: isinstance(x, P))


def serving_param_specs(params, cfg: ModelConfig, plan: ServingShardPlan):
    """PartitionSpecs for serving params (shard_map in_specs / placement)."""
    segs = cfg.segments()
    ax = plan.axis

    def spec_for(kind: str, path: str) -> P:
        if kind == "cross_attn":
            return P()                       # whisper/VLM blocks replicate
        mla = kind in MLA_KINDS
        gate = plan.mla_heads if mla else plan.heads
        tail = path.rsplit("/", 1)[-1]
        if gate and re.search(r"attn/(wq|wk|wv|w_uq|w_uk|w_uv)$", path):
            # (d|r|q_lora, heads, head_dim): column-shard the head axis
            return P(None, ax, None)
        if gate and not mla and re.search(r"attn/(bq|bk|bv)$", path):
            return P(ax, None)
        if plan.experts and re.search(r"moe/(w_gate|w_up|w_down)$", path):
            return P(ax, None, None)         # (held, ...) expert-parallel
        if plan.mlp and re.search(r"mlp/(w_gate|w_up)$", path):
            return P(None, ax)
        if plan.mlp and tail == "b_up" and "mlp/" in path:
            return P(ax)
        return P()                           # wo/router/shared/ssm/norms/...

    def one(kp, leaf):
        path = _path_str(kp)
        kind, stacked = "attn", False
        m = re.match(r"segments/(\d+)/", path)
        if m:
            kind, count = segs[int(m.group(1))]
            stacked = count > 1
        elif path.startswith("shared_attn/"):
            kind = "shared_attn"
        elif path.startswith("encoder/"):
            return P()
        elif "/" not in path or path.startswith(("embed", "unembed",
                                                 "pos_embed", "final")):
            return P()
        sp = spec_for(kind, path)
        if stacked and sp != P():
            sp = P(None, *tuple(sp))
        return sp

    return jax.tree_util.tree_map_with_path(one, params)


def serving_cache_specs(pools, cfg: ModelConfig, plan: ServingShardPlan,
                        lane_view: bool = False):
    """PartitionSpecs for the paged cache pools.

    ``lane_view=False`` describes the at-rest pools owned by
    ``PagedKVManager`` (SSM conv/state lane-shard on the slot axis);
    ``lane_view=True`` describes the cache pytree passed through the
    jitted programs, where SSM leaves are gathered per-lane rows and the
    compute is replicated (spec P())."""
    segs = cfg.segments()
    ax = plan.axis

    def one(kp, leaf):
        path = _path_str(kp)
        m = re.match(r"(\d+)/", path)
        stacked = bool(m) and segs[int(m.group(1))][1] > 1
        pre = (None,) if stacked else ()
        if path.endswith("k_pages") or path.endswith("v_pages"):
            if plan.heads:                   # (P, page, KVH, hd)
                return P(*pre, None, None, ax, None)
            return P()
        if path.endswith("ckv_pages") or path.endswith("krope_pages"):
            return P()                       # latent pools replicate
        if path.endswith("conv") or path.endswith("state"):
            if plan.ssm_lanes and not lane_view:
                return P(*pre, ax)           # slot axis lane-shards at rest
            return P()
        return P()

    return jax.tree_util.tree_map_with_path(one, pools)
