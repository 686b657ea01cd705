"""Jit'd public wrappers around the Pallas kernels.

Handle GQA expansion, padding to tile multiples, layout moves, and the
interpret-mode switch: ``interpret=None`` interprets the kernel bodies
only where the backend is the CPU; on TPU the same calls compile to
Mosaic.  The raw kernel entry points compile unless a caller asks for
``interpret=True``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bh
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_prefill import (mla_paged_prefill_attention,
                                         paged_prefill_attention,
                                         paged_verify_attention)
from repro.kernels.ssd_scan import ssd_scan


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@partial(jax.jit, static_argnames=("causal", "q_offset", "kv_len",
                                   "block_q", "block_k", "interpret"))
def attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
              kv_len: int = None, block_q: int = 128, block_k: int = 128,
              interpret: bool = None):
    """Flash attention with GQA.  q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd)."""
    interpret = _interpret_default() if interpret is None else interpret
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    kv_len = k.shape[1] if kv_len is None else kv_len
    if H != KV:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
    bq = min(block_q, max(Sq, 1))
    bk = min(block_k, kf.shape[1])
    qf, _ = _pad_to(qf, 1, bq)
    kf, _ = _pad_to(kf, 1, bk)
    vf, _ = _pad_to(vf, 1, bk)
    o = flash_attention_bh(qf, kf, vf, causal=causal, q_offset=q_offset,
                           kv_len=kv_len, block_q=bq, block_k=bk,
                           interpret=interpret)
    o = o[:, :Sq].reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    return o.astype(q.dtype)


@partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_attention(q, k_pages, v_pages, block_table, seq_lens, layer=None,
                    *, scale: float = None, window: int = None,
                    interpret: bool = None):
    """Decode attention against paged KV.  q: (B,H,hd); k/v_pages:
    (n_pages, page, KVH, hd), or a per-layer stack read at ``layer``;
    block_table: (B,max_pages); seq_lens: (B,); window: sliding-window
    size in tokens (None = full causal)."""
    interpret = _interpret_default() if interpret is None else interpret
    return paged_decode_attention(q, k_pages, v_pages,
                                  block_table.astype(jnp.int32),
                                  seq_lens.astype(jnp.int32),
                                  scale=scale, window=window, layer=layer,
                                  interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_prefill(q, k_new, v_new, k_pages, v_pages, block_table, pos0,
                  chunk_len, layer=None, *, scale: float = None,
                  window: int = None, interpret: bool = None):
    """Fused chunked-prefill attention: writes the chunk's K/V into pool
    pages in-kernel and attends over each lane's paged history in the
    same pass.  q: (B,S,H,hd); k_new/v_new: (B,S,KVH,hd); k/v_pages:
    (n_pages,page,KVH,hd) or a per-layer stack updated at ``layer``;
    block_table: (B,max_pages); pos0/chunk_len: (B,).  Returns (out,
    k_pages', v_pages'); the pool buffers update in place via the
    kernel's input→output aliasing."""
    interpret = _interpret_default() if interpret is None else interpret
    return paged_prefill_attention(q, k_new, v_new, k_pages, v_pages,
                                   block_table.astype(jnp.int32),
                                   pos0.astype(jnp.int32),
                                   chunk_len.astype(jnp.int32),
                                   scale=scale, window=window, layer=layer,
                                   interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_verify(q, k_new, v_new, k_pages, v_pages, block_table, pos0,
                 chunk_len, layer=None, *, scale: float = None,
                 window: int = None, interpret: bool = None):
    """Fused speculative-verify attention: scores the sl+1 verify window
    ([last emitted] + drafts) as an in-kernel chunk over the paged
    history — one device op replacing 2 page scatters + a slab attention.
    Same shapes/returns as :func:`paged_prefill`."""
    interpret = _interpret_default() if interpret is None else interpret
    return paged_verify_attention(q, k_new, v_new, k_pages, v_pages,
                                  block_table.astype(jnp.int32),
                                  pos0.astype(jnp.int32),
                                  chunk_len.astype(jnp.int32),
                                  scale=scale, window=window, layer=layer,
                                  interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_paged_prefill(q_lat, q_rope, ckv_new, krope_new, ckv_pages,
                      krope_pages, block_table, pos0, chunk_len,
                      layer=None, *, scale: float, interpret: bool = None):
    """Fused MLA chunked prefill: writes the chunk's ckv/krope latents
    into pool pages in-kernel and attends over the paged latent history
    in the same (absorbed, latent-space) pass.  q_lat: (B,S,H,r) =
    q_nope·w_uk; q_rope: (B,S,H,rope); ckv_new: (B,S,r); krope_new:
    (B,S,rope); pools: (n_pages,page,r|rope).  Returns (ctx_lat,
    ckv_pages', krope_pages'); the caller up-projects through w_uv."""
    interpret = _interpret_default() if interpret is None else interpret
    return mla_paged_prefill_attention(
        q_lat, q_rope, ckv_new, krope_new, ckv_pages, krope_pages,
        block_table.astype(jnp.int32), pos0.astype(jnp.int32),
        chunk_len.astype(jnp.int32), scale=scale, layer=layer,
        interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(xh, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = None):
    interpret = _interpret_default() if interpret is None else interpret
    return ssd_scan(xh, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
