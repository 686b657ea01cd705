"""Attention mixers: GQA self-attention (RoPE, qk-norm, sliding window),
cross-attention, and MLA (DeepSeek-V2 multi-head latent attention).

Every mixer supports three execution modes through one code path:
  * full causal ("train" / whole-prompt prefill): q_len == kv written
  * chunked prefill: q chunk at start offsets ``pos0`` attends to the KV
    cache below it plus causally within the chunk
  * decode: q_len == 1 (or spec-verify of a few tokens) against the cache

KV caches come in two layouts sharing one code path:
  * dense: fixed-capacity buffers (B, S_max, n_kv, hd) with per-sequence
    lengths (training-time eval, naive references),
  * paged: global page pools (n_pages, page, n_kv, hd) owned by
    serving/kvcache.py's PagedKVManager, addressed through per-sequence
    block tables.  Chunked prefill dispatches per backend: the fused
    Pallas kernel (kernels/paged_prefill.py) writes the chunk's KV into
    pool pages in-kernel and attends over the paged history in one pass
    on TPU, while the gather reference (paged_write scatter + dense
    attention over the gathered slab) serves CPU/GPU and parity tests.
    Decode attention likewise dispatches to the Pallas paged-decode
    kernel on TPU and a pure-JAX block-table gather elsewhere.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rms_head_norm

NEG_INF = -1e30

# Paged-decode backend: "auto" picks the Pallas kernel on TPU and the
# pure-JAX gather everywhere else; tests may force "pallas" / "gather".
PAGED_DECODE_IMPL = "auto"

# Paged chunked-prefill backend: "fused" runs the Pallas kernel that
# writes the chunk's KV into pool pages in-kernel and attends over the
# paged history in the same pass (kernels/paged_prefill.py); "gather" is
# the unfused block-table reference (paged_write scatter + dense
# attention over the gathered slab).  "auto" = fused on TPU, gather
# elsewhere; tests force "fused" (interpret=True on CPU) for parity.
PAGED_PREFILL_IMPL = "auto"

# Multi-token speculative-verify backend: the target model scores the
# sl+1 verify window ([last emitted] + drafts) as a short chunk over the
# paged history.  Mathematically this IS a chunked prefill, so "fused"
# reuses the same in-kernel page-write + paged-history attention pass
# (kernels/paged_prefill.paged_verify_attention); "gather" is the
# scatter+slab reference.  Tracked separately from PAGED_PREFILL_IMPL so
# benchmarks/tests can A/B the verify path on its own.
PAGED_VERIFY_IMPL = "auto"

# Trace-time op audit: how many paged-KV device ops each traced program
# contains (page scatters, slab attentions, fused prefill/verify kernels).  The
# engine snapshots deltas around its jitted calls — compilation happens
# once per shape, so fresh traces reveal the per-chunk op count that the
# fused kernel removes (benchmarks/overhead.py).
OP_STATS = {"paged_write": 0, "prefill_attn": 0, "fused_prefill": 0,
            "verify_write": 0, "verify_attn": 0, "fused_verify": 0}


def _paged_prefill_impl() -> str:
    if PAGED_PREFILL_IMPL == "auto":
        return "fused" if jax.default_backend() == "tpu" else "gather"
    return PAGED_PREFILL_IMPL


def _paged_verify_impl() -> str:
    if PAGED_VERIFY_IMPL == "auto":
        return "fused" if jax.default_backend() == "tpu" else "gather"
    return PAGED_VERIFY_IMPL


# ----------------------------- paged KV --------------------------------- #
def paged_write(pages, vals, block_table, pos0, chunk_len,
                op_key: str = "paged_write", layer=None):
    """Scatter per-token vectors of a chunk into KV pages.

    pages: (P, page, ...), or a (layers, P, page, ...) stack written at
    index ``layer`` (the scatter addresses the stack directly, so a pool
    carried through the layer scan updates in place); vals: (B, S, ...);
    block_table: (B, max_pages); pos0 / chunk_len: (B,) int32.  Token i
    of lane b lands at global position pos0[b]+i inside the lane's block
    table; positions at or past chunk_len[b] (padding / inactive lanes)
    are dropped, so one call can serve bucketed prefill chunks and masked
    decode lanes alike.  ``op_key`` picks the OP_STATS counter (verify
    audits separately).
    """
    OP_STATS[op_key] += 1
    lead = 0 if layer is None else 1
    P, page = pages.shape[lead:lead + 2]
    B, S = vals.shape[:2]
    tail = pages.shape[lead + 2:]
    rows = (1 if layer is None else pages.shape[0]) * P * page
    pos = pos0[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]   # (B,S)
    slot = jnp.clip(pos // page, 0, block_table.shape[1] - 1)
    pid = jnp.take_along_axis(block_table, slot, axis=1)
    flat = pid * page + pos % page
    if layer is not None:
        flat = flat + layer * (P * page)
    valid = jnp.arange(S)[None, :] < chunk_len[:, None]
    flat = jnp.where(valid, flat, rows)              # OOB index -> dropped
    out = pages.reshape((rows,) + tail).at[flat.reshape(-1)].set(
        vals.astype(pages.dtype).reshape((B * S,) + tail), mode="drop")
    return out.reshape(pages.shape)


def paged_gather(pages, block_table, layer=None):
    """Materialize each lane's logical KV stream from its pages.
    pages: (P, page, ...) or a (layers, P, page, ...) stack read at
    ``layer``; block_table: (B, max_pages) -> (B, max_pages*page, ...)."""
    g = pages[block_table] if layer is None else pages[layer, block_table]
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def paged_decode_attention(q, k_pages, v_pages, block_table, kv_len, *,
                           window=None, scale=None, layer=None):
    """Single-token decode attention against paged KV — the backend
    dispatch point.  q: (B, 1, H, hd) -> (B, 1, H, hd)."""
    impl = PAGED_DECODE_IMPL
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "gather"
    if impl == "pallas":
        from repro.kernels import ops
        out = ops.paged_attention(q[:, 0], k_pages, v_pages,
                                  block_table, kv_len, layer, scale=scale,
                                  window=window)
        return out[:, None].astype(q.dtype)
    k = paged_gather(k_pages, block_table, layer).astype(q.dtype)
    v = paged_gather(v_pages, block_table, layer).astype(q.dtype)
    B = q.shape[0]
    mask = causal_mask(B, 1, k.shape[1], kv_len - 1, kv_len, window)
    return sdpa(q, k, v, mask, scale)


# ------------------------------ init ----------------------------------- #
def init_attn(key, cfg: ModelConfig, cross: bool = False, dtype=jnp.float32):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h, hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, kv, hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, kv, hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (h, hd, d), dtype) * (h * hd) ** -0.5,
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    if cross:
        p["gate"] = jnp.zeros((), jnp.float32)   # VLM tanh gating
    return p


# ----------------------------- core math -------------------------------- #
def sdpa(q, k, v, mask, scale: Optional[float] = None):
    """q: (B,Sq,H,hd)  k/v: (B,Sk,KV,hd)  mask: (B,1,Sq,Sk) bool."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    if H != KV:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scale = scale if scale is not None else hd ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_mask(B, Sq, Sk, pos0, kv_len, window: Optional[int] = None):
    """Mask for chunked/causal attention.

    Query i (global position pos0+i) may see key j iff j <= pos0+i and
    j < kv_len (valid cache) and, with a sliding window, j > pos0+i-window.
    pos0, kv_len: (B,) int32.
    """
    q_pos = pos0[:, None] + jnp.arange(Sq)[None, :]            # (B,Sq)
    k_idx = jnp.arange(Sk)[None, None, :]                       # (1,1,Sk)
    m = k_idx <= q_pos[:, :, None]
    m &= k_idx < kv_len[:, None, None]
    if window is not None:
        m &= k_idx > q_pos[:, :, None] - window
    return m[:, None, :, :]                                     # (B,1,Sq,Sk)


def sdpa_chunked(q, k, v, *, pos0, kv_len, window=None, causal=True,
                 chunk: int = 1024, scale=None):
    """Flash-style attention: lax.scan over KV chunks with running
    (max, denom, acc).  Never materializes the (Sq, Sk) score matrix —
    the XLA-level analogue of kernels/flash_attention.py, used by the
    optimized dry-run variant for long-sequence shapes (§Perf iteration 1).

    q: (B,Sq,H,hd)  k/v: (B,Sk,KV,hd)  pos0/kv_len: (B,) int32.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    if H != KV:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scale = hd ** -0.5 if scale is None else scale
    chunk = min(chunk, Sk)
    if Sk % chunk:
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Sk = Sk + pad
    nc = Sk // chunk
    kc = k.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    qf = (q.astype(jnp.float32) * scale)
    q_pos = pos0[:, None] + jnp.arange(Sq)[None, :]          # (B,Sq)

    def body(carry, inp):
        m, l, acc = carry
        ci, kci, vci = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kci.astype(jnp.float32))
        k_pos = ci * chunk + jnp.arange(chunk)[None, None, :]   # (1,1,chunk)
        mask = k_pos < kv_len[:, None, None]
        if causal:
            mask &= k_pos <= q_pos[:, :, None]
        if window is not None:
            mask &= k_pos > q_pos[:, :, None] - window
        s = jnp.where(mask[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = (acc * alpha
                   + jnp.einsum("bhqk,bkhd->bhqd", p,
                                vci.astype(jnp.float32)))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq, 1), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(nc), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)       # (B,Sq,H,hd)


# --------------------------- self-attention ----------------------------- #
def attn_forward(p, x, cfg: ModelConfig, *, positions, cache=None,
                 pos0=None, layer_window: Optional[int] = None,
                 causal: bool = True, block_tables=None, chunk_len=None,
                 verify: bool = False, layer=None):
    """Returns (out, new_cache).

    cache: None (full-causal, no cache kept), dict(k, v) fixed buffers, or
    dict(k_pages, v_pages) page pools addressed via ``block_tables`` —
    per-layer stacks addressed at ``layer`` when the layer scan carries
    them.
    pos0: (B,) write offsets into the cache (chunked prefill / decode).
    chunk_len: (B,) true (unpadded) chunk lengths for paged writes.
    causal=False: bidirectional (encoder) attention, no cache.
    verify=True: the multi-token chunk is a speculative verify window —
    same math as chunked prefill, but dispatched via PAGED_VERIFY_IMPL
    and audited under the verify OP_STATS keys.
    """
    B, Sq, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if "q_norm" in p:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if cfg.learned_pos == 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    window = layer_window if layer_window is not None else cfg.sliding_window
    chunked = (cfg.attn_impl == "chunked"
               and (cache["k"].shape[1]
                    if cache is not None and "k" in cache else Sq)
               > cfg.attn_chunk)
    if cache is None:
        if chunked and causal:
            zeros = jnp.zeros((B,), jnp.int32)
            out = sdpa_chunked(q, k, v, pos0=zeros,
                               kv_len=jnp.full((B,), Sq, jnp.int32),
                               window=window, chunk=cfg.attn_chunk)
            return out, None
        if causal:
            mask = causal_mask(B, Sq, Sq, jnp.zeros((B,), jnp.int32),
                               jnp.full((B,), Sq, jnp.int32), window)
        else:
            mask = jnp.ones((B, 1, Sq, Sq), bool)
        return sdpa(q, k, v, mask), None

    if "k_pages" in cache:
        if chunk_len is None:
            chunk_len = jnp.full((B,), Sq, jnp.int32)
        impl = _paged_verify_impl() if verify else _paged_prefill_impl()
        if Sq > 1 and impl == "fused":
            # fused chunked prefill / spec verify: the kernel scatters the
            # chunk's KV into pool pages in-kernel AND attends over the
            # paged history in the same pass — one device op where the
            # gather reference below issues three (2 scatters + attention).
            # The engine's CoW barrier ran over [pos0, pos0+chunk_len)
            # before this call, so every written page is exclusive.
            from repro.kernels import ops
            if verify:
                OP_STATS["fused_verify"] += 1
                out, kp, vp = ops.paged_verify(
                    q, k, v, cache["k_pages"], cache["v_pages"],
                    block_tables, pos0, chunk_len, layer, window=window)
            else:
                OP_STATS["fused_prefill"] += 1
                out, kp, vp = ops.paged_prefill(
                    q, k, v, cache["k_pages"], cache["v_pages"],
                    block_tables, pos0, chunk_len, layer, window=window)
            return out.astype(q.dtype), {"k_pages": kp, "v_pages": vp}
        wkey = "verify_write" if verify and Sq > 1 else "paged_write"
        kp = paged_write(cache["k_pages"], k, block_tables, pos0, chunk_len,
                         op_key=wkey, layer=layer)
        vp = paged_write(cache["v_pages"], v, block_tables, pos0, chunk_len,
                         op_key=wkey, layer=layer)
        new_cache = {"k_pages": kp, "v_pages": vp}
        kv_len = pos0 + Sq
        if Sq == 1:
            return paged_decode_attention(q, kp, vp, block_tables, kv_len,
                                          window=window,
                                          layer=layer), new_cache
        OP_STATS["verify_attn" if verify else "prefill_attn"] += 1
        ck = paged_gather(kp, block_tables, layer).astype(q.dtype)
        cv = paged_gather(vp, block_tables, layer).astype(q.dtype)
        mask = causal_mask(B, Sq, ck.shape[1], pos0, kv_len, window)
        return sdpa(q, ck, cv, mask), new_cache

    ck, cv = cache["k"], cache["v"]
    upd = jax.vmap(lambda buf, new, s: jax.lax.dynamic_update_slice(
        buf, new, (s, 0, 0)))
    ck = upd(ck, k.astype(ck.dtype), pos0)
    cv = upd(cv, v.astype(cv.dtype), pos0)
    kv_len = pos0 + Sq
    if chunked:
        out = sdpa_chunked(q, ck.astype(q.dtype), cv.astype(q.dtype),
                           pos0=pos0, kv_len=kv_len, window=window,
                           chunk=cfg.attn_chunk)
        return out, {"k": ck, "v": cv}
    mask = causal_mask(B, Sq, ck.shape[1], pos0, kv_len, window)
    out = sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype), mask)
    return out, {"k": ck, "v": cv}


def attn_output(p, ctx, shard=None):
    """shard: serving ShardPlan inside shard_map — wq/wk/wv are column-
    sharded on the head axis so ``ctx`` holds this shard's heads; the
    full per-head context is re-assembled by CONCATENATION (all_gather,
    bit-identical to the unsharded head order) before the replicated
    ``wo`` contraction.  Cross-attention params stay replicated and pass
    shard=None."""
    if shard is not None and shard.heads:
        ctx = jax.lax.all_gather(ctx, shard.axis, axis=2, tiled=True)
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])
    if "bo" in p:
        out = out + p["bo"]
    return out


# --------------------------- cross-attention ---------------------------- #
def cross_attn_forward(p, x, enc_kv, enc_len=None, gated: bool = False):
    """enc_kv: dict(k, v) precomputed from encoder/image states, or raw
    encoder states under key "states" (projected here)."""
    B, Sq, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    k, v = enc_kv["k"], enc_kv["v"]
    Sk = k.shape[1]
    if enc_len is None:
        mask = jnp.ones((B, 1, Sq, Sk), bool)
    else:
        mask = (jnp.arange(Sk)[None, None, None, :]
                < enc_len[:, None, None, None])
    ctx = sdpa(q, k.astype(q.dtype), v.astype(q.dtype), mask)
    out = attn_output(p, ctx)
    if gated and "gate" in p:
        out = jnp.tanh(p["gate"]) * out
    return out


def project_cross_kv(p, states):
    """Precompute cross-attention K/V once per request (image/audio
    embeddings are static after their prefill)."""
    k = jnp.einsum("bsd,dhk->bshk", states, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", states, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return {"k": k, "v": v}


# ------------------------------- MLA ------------------------------------ #
def init_mla(key, cfg: ModelConfig, dtype=jnp.float32):
    c = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk_dim = c.qk_nope_head_dim + c.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    s = d ** -0.5
    p = {
        "w_dkv": jax.random.normal(ks[0], (d, c.kv_lora_rank), dtype) * s,
        "w_krope": jax.random.normal(ks[1], (d, c.qk_rope_head_dim), dtype) * s,
        "w_uk": jax.random.normal(ks[2], (c.kv_lora_rank, h,
                                          c.qk_nope_head_dim), dtype)
                * c.kv_lora_rank ** -0.5,
        "w_uv": jax.random.normal(ks[3], (c.kv_lora_rank, h, c.v_head_dim),
                                  dtype) * c.kv_lora_rank ** -0.5,
        "wo": jax.random.normal(ks[4], (h, c.v_head_dim, d), dtype)
              * (h * c.v_head_dim) ** -0.5,
        "kv_norm": jnp.ones((c.kv_lora_rank,), jnp.float32),
    }
    if c.q_lora_rank:
        p["w_dq"] = jax.random.normal(ks[5], (d, c.q_lora_rank), dtype) * s
        p["w_uq"] = jax.random.normal(ks[6], (c.q_lora_rank, h, qk_dim),
                                      dtype) * c.q_lora_rank ** -0.5
        p["q_norm"] = jnp.ones((c.q_lora_rank,), jnp.float32)
    else:
        p["wq"] = jax.random.normal(ks[5], (d, h, qk_dim), dtype) * s
    return p


def krope_pool_width(cfg: ModelConfig) -> int:
    """Lane width of the paged rope-key pool: ``qk_rope_head_dim`` padded
    to a multiple of 128.  A TPU lays a 64-wide row out in a 128-lane
    tile anyway, and the fused MLA kernel can only DMA whole tiles, so the
    pad costs no device memory; the pad lanes hold zeros."""
    return -(-cfg.mla.qk_rope_head_dim // 128) * 128


def mla_forward(p, x, cfg: ModelConfig, *, positions, cache=None, pos0=None,
                block_tables=None, chunk_len=None, shard=None, layer=None):
    """MLA: cache the compressed c_kv (kv_lora_rank) + shared rope key.

    Cache layout: {"ckv": (B,S,r), "krope": (B,S,rope_hd)} — this is the
    paper-exact compressed cache (DeepSeek-V2 §2.1), 9x smaller than GQA.
    Paged layout: {"ckv_pages": (P,page,r), "krope_pages": (P,page,w)},
    w = ``krope_pool_width(cfg)`` (rope_hd lane-padded), addressed via
    ``block_tables`` (the latent stream is paged exactly like GQA KV, just
    with vector-valued tokens); per-layer stacks addressed at ``layer``
    when the layer scan carries them.

    Paged multi-token chunks dispatch per PAGED_PREFILL_IMPL: "fused" runs
    the latent-space Pallas kernel (kernels/paged_prefill.py) that writes
    the chunk's ckv/krope rows into pool pages in-kernel and attends over
    the paged latent history in the same absorbed pass — one device op
    where the gather reference issues three (2 latent scatters + a slab
    attention).

    shard: serving ShardPlan inside shard_map — q up-projections /
    w_uk / w_uv are head-sharded while the latent pools stay replicated
    (the ckv/krope streams are headless, every shard writes identical
    rows); the per-head context is all-gathered before ``wo``.
    """
    c = cfg.mla
    B, Sq, _ = x.shape
    nope, rope_hd = c.qk_nope_head_dim, c.qk_rope_head_dim
    # queries
    if "w_dq" in p:
        ql = x @ p["w_dq"]
        ql = rms_head_norm(p["q_norm"], ql)
        q = jnp.einsum("bsr,rhk->bshk", ql, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    # compressed kv
    ckv = rms_head_norm(p["kv_norm"], x @ p["w_dkv"])          # (B,Sq,r)
    krope = apply_rope((x @ p["w_krope"])[:, :, None, :],
                       positions, cfg.rope_theta)[:, :, 0, :]  # (B,Sq,rope_hd)

    if cache is not None and "ckv_pages" in cache:
        if chunk_len is None:
            chunk_len = jnp.full((B,), Sq, jnp.int32)
        if Sq > 1 and _paged_prefill_impl() == "fused":
            # fused latent-page prefill: in-kernel ckv/krope page writes +
            # absorbed attention over the paged latent history in ONE
            # pallas_call (the engine's CoW barrier ran before this call).
            from repro.kernels import ops
            OP_STATS["fused_prefill"] += 1
            q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, p["w_uk"])
            ctx_lat, cc, ck = ops.mla_paged_prefill(
                q_lat, q_rope, ckv, krope, cache["ckv_pages"],
                cache["krope_pages"], block_tables, pos0, chunk_len, layer,
                scale=(nope + rope_hd) ** -0.5)
            ctx = jnp.einsum("bqhr,rhv->bqhv", ctx_lat.astype(x.dtype),
                             p["w_uv"])
            if shard is not None and shard.mla_heads:
                ctx = jax.lax.all_gather(ctx, shard.axis, axis=2,
                                         tiled=True)
            out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])
            return out, {"ckv_pages": cc, "krope_pages": ck}
        cc = paged_write(cache["ckv_pages"], ckv, block_tables, pos0,
                         chunk_len, layer=layer)
        pad = cache["krope_pages"].shape[-1] - rope_hd
        ck = paged_write(cache["krope_pages"],
                         jnp.pad(krope, ((0, 0), (0, 0), (0, pad))),
                         block_tables, pos0, chunk_len, layer=layer)
        if Sq > 1:
            OP_STATS["prefill_attn"] += 1
        kv_len = pos0 + Sq
        new_cache = {"ckv_pages": cc, "krope_pages": ck}
        ckv_all = paged_gather(cc, block_tables, layer).astype(x.dtype)
        krope_all = paged_gather(ck, block_tables, layer)[..., :rope_hd]
        krope_all = krope_all.astype(x.dtype)
        q_pos0 = pos0
    elif cache is not None:
        upd2 = jax.vmap(lambda buf, new, s: jax.lax.dynamic_update_slice(
            buf, new, (s, 0)))
        cc = upd2(cache["ckv"], ckv.astype(cache["ckv"].dtype), pos0)
        ck = upd2(cache["krope"], krope.astype(cache["krope"].dtype), pos0)
        kv_len = pos0 + Sq
        new_cache = {"ckv": cc, "krope": ck}
        ckv_all, krope_all = cc.astype(x.dtype), ck.astype(x.dtype)
        q_pos0 = pos0
    else:
        ckv_all, krope_all = ckv, krope
        kv_len = jnp.full((B,), Sq, jnp.int32)
        new_cache = None
        q_pos0 = jnp.zeros((B,), jnp.int32)

    Sk = ckv_all.shape[1]
    scale = (nope + rope_hd) ** -0.5
    if cfg.mla_absorb and Sq <= 4:
        # Absorbed-matmul decode (DeepSeek-V2 §2.1.3 / §Perf iteration 2):
        # fold w_uk into the query and w_uv into the output so attention
        # runs directly against the compressed latent cache — per-step
        # cost O(S*r*h) instead of O(S*r*h*(nope+dv)) for the expansion.
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, p["w_uk"])
        logits = (jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv_all)
                  + jnp.einsum("bqhr,bkr->bhqk", q_rope, krope_all)
                  ).astype(jnp.float32) * scale
        mask = causal_mask(B, Sq, Sk, q_pos0, kv_len)
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        ctx_lat = jnp.einsum("bhqk,bkr->bqhr", probs, ckv_all)
        ctx = jnp.einsum("bqhr,rhv->bqhv", ctx_lat, p["w_uv"])
        if shard is not None and shard.mla_heads:
            ctx = jax.lax.all_gather(ctx, shard.axis, axis=2, tiled=True)
        out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])
        return out, new_cache
    # naive path: expand keys/values from the latent
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_all, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv_all, p["w_uv"])
    logits = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhr,bkr->bhqk",
                           q_rope, krope_all)).astype(jnp.float32) * scale
    mask = causal_mask(B, Sq, Sk, q_pos0, kv_len)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if shard is not None and shard.mla_heads:
        ctx = jax.lax.all_gather(ctx, shard.axis, axis=2, tiled=True)
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])
    return out, new_cache
