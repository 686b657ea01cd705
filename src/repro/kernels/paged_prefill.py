"""Fused chunked-prefill paged-attention Pallas TPU kernels.

One kernel invocation per prefill group does, per lane, what the unfused
path spread over three device ops per layer (two ``paged_write`` scatters
plus a dense attention over the gathered slab):

  1. **In-kernel KV page writes** — the chunk's fresh K/V rows are written
     straight into the lane's pool pages.  Each touched page is copied
     HBM→VMEM by DMA together with the page-aligned slice of new rows,
     merged under the write mask (so partially-filled boundary pages keep
     their existing tokens), and copied back by DMA.  The page pools are
     passed in HBM (``memory_space=pltpu.HBM``) and aliased input→output
     (``input_output_aliases``), so untouched pages flow through and the
     update is in place — no pool-sized copy, and no vector load or store
     ever touches a pool ref.
  2. **Chunked causal attention over paged history** — the chunk's query
     rows, one tile of ``TQ`` positions per grid step, run the
     page-streaming flash loop of kernels/paged_attention.py over the
     lane's block table: both the request's existing KV history *and* the
     chunk itself (its pages were written, and the write DMAs waited on,
     in step 1 at the lane's first tile), with causal + sliding-window
     masking per query position.

Masking contract (the CoW-safe write mask): token i of lane b lands at
global position ``pos0[b] + i``; positions at or past ``chunk_len[b]``
are never written, so padded chunk tails and inactive (padded) lanes —
which alias another lane's block table — touch nothing.  The engine runs
``PagedKVManager.ensure_writable`` over exactly ``[pos0, pos0+chunk_len)``
before the call, so every page the kernel writes is exclusively owned and
unpublished (bit-identical sharing is preserved; see
docs/ARCHITECTURE.md).  Query tiles wholly past ``chunk_len`` (bucket
padding, masked lanes) are skipped and output zeros.

Grid is (batch, S // TQ), both axes sequential ("arbitrary": a lane's
writes at tile 0 must land before its later tiles read them); block
tables / pos0 / chunk_len arrive via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``).  The new rows are passed page-aligned
(``_align_rows``: shifted by ``pos0 % page`` and padded) in HBM, so each
touched page's rows are one contiguous DMA.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import (LANES, _dot_nt, finish,
                                           gqa_attend, gqa_scratch, nbytes,
                                           online_softmax_update,
                                           page_block_loop, page_copies,
                                           pages_per_block, stack_layers,
                                           vmem_limit)

# query rows (positions x heads sharing a KV stream) per grid step
_Q_ROWS = 256


def _q_tile(S: int, heads_per_kv: int) -> int:
    """Largest divisor of S with at most ``_Q_ROWS`` rows per tile."""
    cap = max(1, _Q_ROWS // heads_per_kv)
    return max(d for d in range(1, min(S, cap) + 1) if S % d == 0)


def _align_rows(x, pos0, page: int, dtype):
    """(B, S, ...) new rows -> (B, S_al, ...) with row ``r`` holding the
    token at lane position ``(pos0 // page) * page + r``: touched page j
    of the lane is rows ``[j*page, (j+1)*page)``."""
    B, S = x.shape[:2]
    s_al = -(-(S + page - 1) // page) * page
    zeros = jnp.zeros((s_al,) + x.shape[2:], dtype)

    def one(row, off):
        idx = (off,) + (0,) * (x.ndim - 2)
        return jax.lax.dynamic_update_slice(zeros, row.astype(dtype), idx)
    return jax.vmap(one)(x, pos0 % page)


def _write_chunk(table_ref, layer, b, pos0, n_tok, page: int, pairs, wbufs,
                 sems):
    """Phase 1: merge the lane's new rows into its pool pages by DMA.
    ``pairs``: (pool stack ref, aligned new-rows ref) in HBM; ``wbufs``:
    one (2, page, ...) VMEM buffer per pair; ``sems``: DMA
    (len(pairs), 2)."""
    off = pos0 % page
    w_lo = pos0 // page
    n_w = jnp.where(n_tok > 0, (off + n_tok + page - 1) // page, 0)

    def body(j, carry):
        pid = table_ref[b, w_lo + j]
        loads = []
        for n, ((pool, new), buf) in enumerate(zip(pairs, wbufs)):
            loads.append(pltpu.make_async_copy(
                pool.at[layer, pid], buf.at[0], sems.at[n, 0]))
            loads.append(pltpu.make_async_copy(
                new.at[b, pl.ds(j * page, page)], buf.at[1], sems.at[n, 1]))
        for c in loads:
            c.start()
        for c in loads:
            c.wait()
        stores = []
        for n, ((pool, _), buf) in enumerate(zip(pairs, wbufs)):
            shape = buf.shape[1:]
            rows = j * page + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            keep = (rows >= off) & (rows < off + n_tok)
            buf[0] = jnp.where(keep, buf[1], buf[0])
            stores.append(pltpu.make_async_copy(
                buf.at[0], pool.at[layer, pid], sems.at[n, 0]))
        for c in stores:
            c.start()
        for c in stores:
            c.wait()
        return carry

    jax.lax.fori_loop(0, n_w, body, 0)


def _prefill_kernel(table_ref, pos0_ref, clen_ref, layer_ref, q_ref, kn_ref,
                    vn_ref, kp_in_ref, vp_in_ref, o_ref, kp_ref, vp_ref,
                    kbuf, vbuf, sems, m_ref, l_ref, acc_ref, wk, wv, wsems,
                    *, scale: float, page: int, n_kvh: int, group: int,
                    window: Optional[int]):
    del kp_in_ref, vp_in_ref          # aliased to kp_ref / vp_ref
    b, qt = pl.program_id(0), pl.program_id(1)
    TQ = q_ref.shape[1]
    layer = layer_ref[0]
    pos0 = pos0_ref[b]
    n_tok = clen_ref[b]

    @pl.when(qt == 0)
    def _():
        _write_chunk(table_ref, layer, b, pos0, n_tok, page,
                     [(kp_ref, kn_ref), (vp_ref, vn_ref)], [wk, wv], wsems)

    live = qt * TQ < n_tok

    @pl.when(live)
    def _():
        gqa_attend(table_ref, layer, b, q_ref, kp_ref, vp_ref, kbuf, vbuf,
                   sems,
                   m_ref, l_ref, acc_ref, o_ref, q_start=pos0 + qt * TQ,
                   kv_len=pos0 + n_tok, scale=scale, window=window,
                   page=page, n_kvh=n_kvh, group=group)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def paged_prefill_attention(q, k_new, v_new, k_pages, v_pages, block_table,
                            pos0, chunk_len, *, scale: float = None,
                            window: Optional[int] = None,
                            layer=None, interpret: bool = False):
    """Fused chunked-prefill attention with in-kernel paged KV writes.

    q: (B, S, H, hd); k_new/v_new: (B, S, KVH, hd) — the chunk's fresh
    projections; k/v_pages: (n_pages, page, KVH, hd), or a (layers,
    n_pages, page, KVH, hd) stack updated in place at index ``layer``
    (the returned pools have the input's shape); block_table:
    (B, max_pages) int32; pos0/chunk_len: (B,) int32 (the CoW-safe write
    mask: rows at or past chunk_len are dropped, lanes with chunk_len 0
    neither write nor contribute).  Returns (out (B, S, H, hd),
    k_pages', v_pages') with the chunk's KV landed in the pools; output
    rows at or past chunk_len are zero.
    """
    unstacked = layer is None
    (k_pages, v_pages), layer = stack_layers((k_pages, v_pages), layer)
    B, S, H, hd = q.shape
    page, KVH = k_pages.shape[2:4]
    max_pages = block_table.shape[1]
    assert H % KVH == 0
    group = H // KVH
    scale = hd ** -0.5 if scale is None else scale
    TQ = _q_tile(S, group)
    pb = pages_per_block(page, max_pages)
    kn = _align_rows(k_new, pos0, page, k_pages.dtype)
    vn = _align_rows(v_new, pos0, page, v_pages.dtype)
    scratch, scratch_bytes = gqa_scratch(TQ, KVH, group, hd, pb, page,
                                         k_pages.dtype)
    wbuf = (2, page, KVH, hd)
    scratch += [pltpu.VMEM(wbuf, k_pages.dtype),
                pltpu.VMEM(wbuf, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2))]

    kernel = functools.partial(
        _prefill_kernel, scale=scale, page=page, n_kvh=KVH, group=group,
        window=window)
    q_block = pl.BlockSpec((1, TQ, H, hd), lambda b, t, *_: (b, t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # block_table, pos0, chunk_len, layer
        grid=(B, S // TQ),
        in_specs=[
            q_block,
            pl.BlockSpec(memory_space=pltpu.HBM),           # aligned k rows
            pl.BlockSpec(memory_space=pltpu.HBM),           # aligned v rows
            pl.BlockSpec(memory_space=pltpu.HBM),           # k_pages (HBM)
            pl.BlockSpec(memory_space=pltpu.HBM),           # v_pages (HBM)
        ],
        out_specs=[
            q_block,
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        scratch_shapes=scratch,
    )
    out, kp, vp = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # pools update in place: operand indices include the 4 scalar-
        # prefetch args, so k_pages/v_pages are operands 7/8
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(
                *scratch_bytes, 2 * nbytes(wbuf, k_pages.dtype),
                4 * nbytes((TQ, H, hd), q.dtype))),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_table, pos0, chunk_len, layer, q, kn, vn, k_pages, v_pages)
    return (out, kp[0], vp[0]) if unstacked else (out, kp, vp)


def _mla_prefill_kernel(table_ref, pos0_ref, clen_ref, layer_ref, ql_ref,
                        qr_ref, cn_ref, rn_ref, cp_in_ref, rp_in_ref, o_ref,
                        cp_ref, rp_ref, cbuf, rbuf, sems, m_ref, l_ref,
                        acc_ref, wc, wr, wsems, *, scale: float, page: int):
    """MLA latent-space analogue of :func:`_prefill_kernel`.

    The paged history is HEADLESS — one (kv_lora_rank,) latent vector plus
    one (rope_hd,) decoupled-rope key per token, shared by every query
    head — so phase 1 writes ``ckv``/``krope`` rows (no head axis) and
    phase 2 runs the flash loop with all ``TQ * H`` query rows of the tile
    folded onto the single latent "kv head" (row r is query position
    ``r // H``).  Queries arrive pre-absorbed: ``q_lat = q_nope · w_uk``
    lives in latent space, so per-page logits are the two-term sum
    ``q_lat · ckv + q_rope · krope`` and the context accumulates in latent
    space; the caller up-projects through ``w_uv`` afterwards."""
    del cp_in_ref, rp_in_ref          # aliased to cp_ref / rp_ref
    b, qt = pl.program_id(0), pl.program_id(1)
    _, TQ, H, r = ql_ref.shape
    rope = qr_ref.shape[-1]
    pb = cbuf.shape[1]
    T = pb * page
    rows = TQ * H
    layer = layer_ref[0]
    pos0 = pos0_ref[b]
    n_tok = clen_ref[b]

    @pl.when(qt == 0)
    def _():
        _write_chunk(table_ref, layer, b, pos0, n_tok, page,
                     [(cp_ref, cn_ref), (rp_ref, rn_ref)], [wc, wr], wsems)

    live = qt * TQ < n_tok

    @pl.when(live)
    def _():
        q_start = pos0 + qt * TQ
        kv_len = pos0 + n_tok
        hi = (jnp.minimum(kv_len, q_start + TQ) + page - 1) // page
        n_blk = (hi + pb - 1) // pb
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def compute(t, slot):
            k_pos = t * T + jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (rows, T), 0) // H
            valid = (k_pos < kv_len) & (k_pos <= q_pos)
            ck = cbuf.at[slot].reshape(T, r)[...].astype(jnp.float32)
            rk = rbuf.at[slot].reshape(T, rope)[...].astype(jnp.float32)
            ql = ql_ref[0].astype(jnp.float32).reshape(rows, r)
            qr = qr_ref[0].astype(jnp.float32).reshape(rows, rope)
            s = (_dot_nt(ql, ck) + _dot_nt(qr, rk)) * scale
            online_softmax_update(s, valid, ck, m_ref, l_ref, acc_ref)

        page_block_loop(n_blk, page_copies(table_ref, layer, b, 0, hi, pb,
                                           [(cp_ref, cbuf), (rp_ref, rbuf)],
                                           sems), compute)
        o_ref[0] = finish(acc_ref, l_ref).reshape(TQ, H, r).astype(
            o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def mla_paged_prefill_attention(q_lat, q_rope, ckv_new, krope_new,
                                ckv_pages, krope_pages, block_table,
                                pos0, chunk_len, *, scale: float,
                                layer=None, interpret: bool = False):
    """Fused MLA chunked prefill with in-kernel latent page writes.

    q_lat: (B, S, H, r) absorbed queries (``q_nope · w_uk``); q_rope:
    (B, S, H, rope); ckv_new: (B, S, r) / krope_new: (B, S, rope) — the
    chunk's fresh latents; ckv_pages: (n_pages, page, r) / krope_pages:
    (n_pages, page, w), w >= rope (a lane-padded pool: the rope operands
    are zero-padded to w, which leaves every logit unchanged); both pools
    may instead be (layers, ...) stacks updated at index ``layer``.  Same
    write-mask contract as
    :func:`paged_prefill_attention`.  Returns (ctx_lat (B, S, H, r),
    ckv_pages', krope_pages'); the caller applies ``w_uv``/``wo``.
    MLA has no sliding window, so none is supported here.
    """
    unstacked = layer is None
    (ckv_pages, krope_pages), layer = stack_layers((ckv_pages, krope_pages),
                                                   layer)
    B, S, H, r = q_lat.shape
    page, rope = krope_pages.shape[2:]
    pad = rope - q_rope.shape[-1]
    if pad:
        q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, 0), (0, pad)))
        krope_new = jnp.pad(krope_new, ((0, 0), (0, 0), (0, pad)))
    max_pages = block_table.shape[1]
    TQ = _q_tile(S, H)
    pb = pages_per_block(page, max_pages)
    rows = TQ * H
    cn = _align_rows(ckv_new, pos0, page, ckv_pages.dtype)
    rn = _align_rows(krope_new, pos0, page, krope_pages.dtype)
    cbuf, rbuf = (2, pb, page, r), (2, pb, page, rope)
    stats = (rows, LANES)
    scratch = [pltpu.VMEM(cbuf, ckv_pages.dtype),
               pltpu.VMEM(rbuf, krope_pages.dtype),
               pltpu.SemaphoreType.DMA((2, 2)),
               pltpu.VMEM(stats, jnp.float32),
               pltpu.VMEM(stats, jnp.float32),
               pltpu.VMEM((rows, r), jnp.float32),
               pltpu.VMEM((2, page, r), ckv_pages.dtype),
               pltpu.VMEM((2, page, rope), krope_pages.dtype),
               pltpu.SemaphoreType.DMA((2, 2))]
    buf_bytes = [nbytes(cbuf, ckv_pages.dtype),
                 nbytes(rbuf, krope_pages.dtype),
                 2 * nbytes(stats, jnp.float32),
                 nbytes((rows, r), jnp.float32),
                 4 * nbytes((TQ, H, r + rope), q_lat.dtype)]

    kernel = functools.partial(_mla_prefill_kernel, scale=scale, page=page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # block_table, pos0, chunk_len, layer
        grid=(B, S // TQ),
        in_specs=[
            pl.BlockSpec((1, TQ, H, r), lambda b, t, *_: (b, t, 0, 0)),
            pl.BlockSpec((1, TQ, H, rope), lambda b, t, *_: (b, t, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),           # aligned ckv rows
            pl.BlockSpec(memory_space=pltpu.HBM),           # aligned krope
            pl.BlockSpec(memory_space=pltpu.HBM),           # ckv_pages (HBM)
            pl.BlockSpec(memory_space=pltpu.HBM),           # krope_pages (HBM)
        ],
        out_specs=[
            pl.BlockSpec((1, TQ, H, r), lambda b, t, *_: (b, t, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        scratch_shapes=scratch,
    )
    out, cp, rp = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, r), q_lat.dtype),
            jax.ShapeDtypeStruct(ckv_pages.shape, ckv_pages.dtype),
            jax.ShapeDtypeStruct(krope_pages.shape, krope_pages.dtype),
        ],
        # operands 0-3 are the scalar-prefetch args; pools are 8/9
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(*buf_bytes)),
        interpret=interpret,
        name="mla_paged_prefill_attention",
    )(block_table, pos0, chunk_len, layer, q_lat, q_rope, cn, rn,
      ckv_pages, krope_pages)
    return (out, cp[0], rp[0]) if unstacked else (out, cp, rp)


def paged_verify_attention(q, k_new, v_new, k_pages, v_pages, block_table,
                           pos0, chunk_len, *, scale: float = None,
                           window: Optional[int] = None,
                           layer=None, interpret: bool = False):
    """Fused multi-token speculative-verify attention.

    The target model scores a verify window of sl+1 tokens — the last
    emitted token plus the draft's sl proposals — against the paged
    history.  That is exactly a chunked prefill of length sl+1 starting at
    pos0 (causal within the window, full attention over the history), so
    this entry point shares ``_prefill_kernel``: one pallas_call writes
    the window's KV into pool pages in-kernel and attends in the same
    pass, where the gather reference issues 2 scatters + a slab
    attention per layer.  Rejected drafts are rolled back by the caller
    via block-table truncation (``PagedKVManager.truncate``); any stale
    KV they left in-page is masked by seq_len on later reads and
    overwritten by the next verify window.

    Same shapes/contract as :func:`paged_prefill_attention`.
    """
    return paged_prefill_attention(
        q, k_new, v_new, k_pages, v_pages, block_table, pos0, chunk_len,
        scale=scale, window=window, layer=layer, interpret=interpret)
