"""Decoder stack: block assembly, segment scanning, caches, entry points.

The layer list (``cfg.block_pattern``) is grouped into homogeneous segments;
each segment's parameters are stacked on a leading axis and executed with
``lax.scan`` (compact HLO — essential for compiling 60-80 layer models for a
512-device mesh on one CPU).  ``shared_attn`` segments (Zamba2) reuse ONE
parameter block across occurrences but keep per-occurrence KV caches.

Entry point semantics:
  * ``model_forward(..., cache=None)``          — full causal (training).
  * ``model_forward(..., cache, pos0)``         — chunked prefill / decode:
    the S new tokens are written into each layer cache at offset pos0 (B,).
Returns hidden states, the new caches and the step's auxiliary terms
(``aux_loss``, the MoE load-balance loss, and ``moe_units_held``, the
live (token, expert) units routed to held experts, summed over layers);
``logits`` / ``loss`` heads live in losses.py and the serving/training
layers.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.attention import (attn_forward, attn_output,
                                    cross_attn_forward, init_attn, init_mla,
                                    krope_pool_width, mla_forward,
                                    project_cross_kv)
from repro.models.config import ModelConfig
from repro.models.layers import (apply_mlp, apply_norm, embed, init_embed,
                                 init_mlp, init_norm, init_unembed)
from repro.models.moe import init_moe, moe_forward
from repro.models.ssm import init_ssm, ssm_decode_step, ssm_forward

ATTN_KINDS = ("attn", "attn_moe", "cross_attn", "shared_attn")
MLA_KINDS = ("mla", "mla_moe")
MOE_KINDS = ("attn_moe", "mla_moe")


def _no_aux():
    return {"aux_loss": jnp.zeros((), jnp.float32),
            "moe_units_held": jnp.zeros((), jnp.int32)}


def _add_aux(a, b):
    return jax.tree.map(jnp.add, a, b)


# ------------------------------ blocks --------------------------------- #
def init_block(key, kind: str, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {"norm1": init_norm(cfg.d_model, cfg.norm)}
    if kind in ATTN_KINDS:
        p["attn"] = init_attn(ks[0], cfg, dtype=dtype)
    elif kind in MLA_KINDS:
        p["attn"] = init_mla(ks[0], cfg, dtype=dtype)
    elif kind == "ssm":
        p["ssm"] = init_ssm(ks[0], cfg, dtype=dtype)
        return p                                   # Mamba block: no FFN half
    if kind == "cross_attn":
        p["norm_x"] = init_norm(cfg.d_model, cfg.norm)
        p["cross"] = init_attn(ks[2], cfg, cross=True, dtype=dtype)
    p["norm2"] = init_norm(cfg.d_model, cfg.norm)
    if kind in MOE_KINDS:
        p["moe"] = init_moe(ks[1], cfg, dtype=dtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act,
                            cfg.use_bias, dtype=dtype)
    return p


def block_forward(p, kind: str, cfg: ModelConfig, x, *, positions,
                  cache=None, pos0=None, enc_kv=None, moe_cf=None,
                  block_tables=None, chunk_len=None, verify=False,
                  shard=None, layer=None):
    """Returns (x, new_cache, aux): aux as ``model_forward`` gives it,
    for this layer.

    shard: serving ShardPlan when executing inside the engine's
    shard_map (distributed/sharding.py) — attention heads / MoE experts
    / dense-FFN hidden run shard-local, everything else replicated.
    Cross-attention params stay replicated (shard is not forwarded).
    layer: index into per-layer page-pool stacks (paged segments whose
    pools ride in the layer scan's carry); None = this layer's own cache."""
    if kind == "cross_attn":
        shard = None            # whole block replicates (serving specs)
    aux = _no_aux()
    if kind == "ssm":
        h = apply_norm(p["norm1"], x, cfg.norm)
        if cache is not None and x.shape[1] == 1:
            y, new_cache = ssm_decode_step(p["ssm"], h, cfg, cache)
        else:
            y, new_cache = ssm_forward(
                p["ssm"], h, cfg, cache=cache,
                chunk_len=chunk_len if cache is not None else None)
        return x + y.astype(x.dtype), new_cache, aux

    h = apply_norm(p["norm1"], x, cfg.norm)
    if kind in MLA_KINDS:
        self_cache = cache.get("self") if cache else None
        y, new_self = mla_forward(p["attn"], h, cfg, positions=positions,
                                  cache=self_cache, pos0=pos0,
                                  block_tables=block_tables,
                                  chunk_len=chunk_len, shard=shard,
                                  layer=layer)
    else:
        self_cache = cache.get("self") if cache else None
        ctx, new_self = attn_forward(p["attn"], h, cfg, positions=positions,
                                     cache=self_cache, pos0=pos0,
                                     block_tables=block_tables,
                                     chunk_len=chunk_len, verify=verify,
                                     layer=layer)
        y = attn_output(p["attn"], ctx, shard=shard)
    x = x + y.astype(x.dtype)
    if kind == "cross_attn":
        hx = apply_norm(p["norm_x"], x, cfg.norm)
        x = x + cross_attn_forward(p["cross"], hx, enc_kv,
                                   gated=cfg.arch_type == "vlm")
    h2 = apply_norm(p["norm2"], x, cfg.norm)
    if kind in MOE_KINDS:
        live = (None if chunk_len is None else
                jnp.arange(x.shape[1])[None, :] < chunk_len[:, None])
        y2, moe_aux = moe_forward(p["moe"], h2, cfg,
                                  capacity_factor=moe_cf, shard=shard,
                                  live=live)
        aux = {"aux_loss": moe_aux["aux_loss"],
               "moe_units_held": moe_aux["units_held"]}
    else:
        y2 = apply_mlp(p["mlp"], h2, cfg.act, shard=shard)
    new_cache = {"self": new_self} if cache is not None else None
    return x + y2.astype(x.dtype), new_cache, aux


# ----------------------------- model init ------------------------------ #
def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 8 + len(cfg.segments()))
    params = {"embed": init_embed(ks[0], cfg.vocab, cfg.d_model, dtype),
              "final_norm": init_norm(cfg.d_model, cfg.norm)}
    if not cfg.tie_embeddings:
        params["unembed"] = init_unembed(ks[1], cfg.vocab, cfg.d_model, dtype)
    if cfg.learned_pos:
        params["pos_embed"] = (jax.random.normal(
            ks[2], (cfg.learned_pos, cfg.d_model), dtype) * 0.02)
    shared = None
    segs = []
    for i, (kind, n) in enumerate(cfg.segments()):
        kseg = ks[3 + i]
        if kind == "shared_attn":
            if shared is None:
                shared = init_block(kseg, kind, cfg, dtype)
            segs.append({})               # marker: params live in shared_attn
        elif n == 1:
            segs.append({"p": init_block(kseg, kind, cfg, dtype)})
        else:
            keys = jax.random.split(kseg, n)
            stacked = jax.vmap(
                lambda k: init_block(k, kind, cfg, dtype))(keys)
            segs.append({"p": stacked})
    params["segments"] = segs
    if shared is not None:
        params["shared_attn"] = shared
    return params


# ------------------------------ caches --------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.float32, enc_len: int = 0):
    """Per-segment cache pytree (stacked along layers inside a segment)."""
    def attn_cache(n):
        shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        c = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if n > 1:
            c = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape), c)
        return {"self": c}

    def mla_cache(n):
        c = {"ckv": jnp.zeros((batch, max_len, cfg.mla.kv_lora_rank), dtype),
             "krope": jnp.zeros((batch, max_len, cfg.mla.qk_rope_head_dim),
                                dtype)}
        if n > 1:
            c = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape), c)
        return {"self": c}

    def ssm_cache(n):
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        nheads = d_in // s.head_dim
        conv_ch = d_in + 2 * s.d_state
        c = {"conv": jnp.zeros((batch, s.d_conv - 1, conv_ch), dtype),
             "state": jnp.zeros((batch, nheads, s.head_dim, s.d_state),
                                dtype)}
        if n > 1:
            c = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape), c)
        return c

    caches = []
    for kind, n in cfg.segments():
        if kind == "ssm":
            caches.append(ssm_cache(n))
        elif kind in MLA_KINDS:
            caches.append(mla_cache(n))
        else:
            caches.append(attn_cache(n))
    return caches


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     max_seqs: int, dtype=jnp.float32):
    """Paged serving cache: attention-bearing segments get global page
    pools shared by every sequence (addressed via block tables); SSM
    segments keep O(1) per-sequence state rows (max_seqs lanes) since
    their state does not grow with context."""
    def attn_pages(n):
        shape = (n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
        c = {"k_pages": jnp.zeros(shape, dtype),
             "v_pages": jnp.zeros(shape, dtype)}
        if n > 1:
            c = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape), c)
        return {"self": c}

    def mla_pages(n):
        c = {"ckv_pages": jnp.zeros(
                 (n_pages, page_size, cfg.mla.kv_lora_rank), dtype),
             "krope_pages": jnp.zeros(
                 (n_pages, page_size, krope_pool_width(cfg)), dtype)}
        if n > 1:
            c = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape), c)
        return {"self": c}

    def ssm_state(n):
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        nheads = d_in // s.head_dim
        conv_ch = d_in + 2 * s.d_state
        c = {"conv": jnp.zeros((max_seqs, s.d_conv - 1, conv_ch), dtype),
             "state": jnp.zeros((max_seqs, nheads, s.head_dim, s.d_state),
                                dtype)}
        if n > 1:
            c = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape), c)
        return c

    caches = []
    for kind, n in cfg.segments():
        if kind == "ssm":
            caches.append(ssm_state(n))
        elif kind in MLA_KINDS:
            caches.append(mla_pages(n))
        else:
            caches.append(attn_pages(n))
    return caches


# ---------------------------- full forward ----------------------------- #
def model_forward(params, cfg: ModelConfig, tokens_or_embeds, *,
                  cache=None, pos0=None, enc_states=None, moe_cf=None,
                  block_tables=None, chunk_len=None, verify=False,
                  shard=None):
    """Returns (hidden (B,S,D), new_cache, aux): aux holds ``aux_loss``
    (MoE load balance, summed over layers) and ``moe_units_held`` (live
    (token, expert) units routed to held experts, summed over layers; a
    token is live below its lane's ``chunk_len``, every token without).

    block_tables: (B, max_pages) per-lane page tables when ``cache`` holds
    paged pools (init_paged_cache); chunk_len: (B,) true chunk lengths so
    padded positions are never written into pages.
    shard: serving ShardPlan when tracing inside the engine's shard_map;
    None (default) is the unsharded single-device path.
    """
    if tokens_or_embeds.dtype in (jnp.int32, jnp.int64):
        x = embed(params["embed"], tokens_or_embeds)
    else:
        x = tokens_or_embeds
    B, S = x.shape[:2]
    if pos0 is None:
        pos0_arr = jnp.zeros((B,), jnp.int32)
    else:
        pos0_arr = pos0
    positions = pos0_arr[:, None] + jnp.arange(S)[None, :]
    if cfg.learned_pos:
        pe = jnp.take(params["pos_embed"],
                      jnp.clip(positions, 0, cfg.learned_pos - 1), axis=0)
        x = x + pe

    aux_total = _no_aux()
    new_caches = [] if cache is not None else None
    segs = cfg.segments()
    for i, (kind, n) in enumerate(segs):
        seg_p = params["segments"][i]
        seg_c = cache[i] if cache is not None else None
        if "p" not in seg_p:          # shared_attn marker segment
            p = params["shared_attn"]
            x, c_new, aux = block_forward(
                p, "shared_attn", cfg, x, positions=positions,
                cache=seg_c, pos0=pos0_arr, enc_kv=None, moe_cf=moe_cf,
                block_tables=block_tables, chunk_len=chunk_len,
                verify=verify, shard=shard)
            aux_total = _add_aux(aux_total, aux)
            if cache is not None:
                new_caches.append(c_new)
            continue
        p = seg_p["p"]
        enc_kv = None
        if kind == "cross_attn":
            # single-layer segments for VLM; whisper uses stacked cross
            if n == 1:
                enc_kv = project_cross_kv(p["cross"], enc_states)
        if n == 1:
            x, c_new, aux = block_forward(
                p, kind, cfg, x, positions=positions, cache=seg_c,
                pos0=pos0_arr, enc_kv=enc_kv, moe_cf=moe_cf,
                block_tables=block_tables, chunk_len=chunk_len,
                verify=verify, shard=shard)
            aux_total = _add_aux(aux_total, aux)
            if cache is not None:
                new_caches.append(c_new)
        elif _paged(seg_c):
            # page pools ride in the carry as (n, P, page, ...) stacks and
            # each layer updates its slice in place (kernel aliasing /
            # direct scatter at the layer index); as scan xs -> ys they
            # would be copied into a second full-size stack
            def body(carry, p_l):
                xx, c, li = carry
                ekv = None
                if kind == "cross_attn":
                    ekv = project_cross_kv(p_l["cross"], enc_states)
                xx, c, aux = block_forward(
                    p_l, kind, cfg, xx, positions=positions, cache=c,
                    pos0=pos0_arr, enc_kv=ekv, moe_cf=moe_cf,
                    block_tables=block_tables, chunk_len=chunk_len,
                    verify=verify, shard=shard, layer=li)
                return (xx, c, li + 1), aux
            (x, c_new, _), auxs = jax.lax.scan(
                body, (x, seg_c, jnp.int32(0)), p)
            new_caches.append(c_new)
            aux_total = _add_aux(aux_total,
                                 jax.tree.map(jnp.sum, auxs))
        else:
            def body(carry, layer):
                xx = carry
                p_l, c_l = layer
                ekv = None
                if kind == "cross_attn":
                    ekv = project_cross_kv(p_l["cross"], enc_states)
                xx, c_new, aux = block_forward(
                    p_l, kind, cfg, xx, positions=positions, cache=c_l,
                    pos0=pos0_arr, enc_kv=ekv, moe_cf=moe_cf,
                    block_tables=block_tables, chunk_len=chunk_len,
                    verify=verify, shard=shard)
                return xx, (c_new, aux)
            if cfg.remat and cache is None:
                # checkpoint each layer: backward recomputes the block
                # instead of keeping its activations (Perf iteration 1)
                body = jax.checkpoint(body)
            if cache is not None:
                x, (c_new, auxs) = jax.lax.scan(body, x, (p, seg_c))
                new_caches.append(c_new)
            else:
                x, (_, auxs) = jax.lax.scan(body, x, (p, None))
            aux_total = _add_aux(aux_total,
                                 jax.tree.map(jnp.sum, auxs))
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return x, new_caches, aux_total


def _paged(seg_cache) -> bool:
    """A segment cache holding page pools (attention / MLA), as opposed
    to dense buffers, SSM lane state or no cache."""
    return (isinstance(seg_cache, dict) and "self" in seg_cache
            and any(k.endswith("_pages") for k in seg_cache["self"]))


def logits_fn(params, cfg: ModelConfig, hidden):
    if cfg.tie_embeddings:
        w = params["embed"]["embed"].T
    else:
        w = params["unembed"]
    return hidden @ w
