"""Paged decode-attention Pallas TPU kernel (decode hot spot), and the
page-streaming flash loop it shares with the fused prefill kernels.

One query token per sequence attends to a KV cache scattered across pages
(PagedAttention re-tiled for TPU).  The grid is (batch,); the block table
and sequence lengths arrive via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``).  The page pools stay in HBM
(``memory_space=pltpu.HBM``) and are only ever touched by DMA: blocks of
``128 // page`` pages are copied into a double-buffered VMEM scratch with
``pltpu.make_async_copy`` (block t+1 is in flight while block t is
scored), and the kernel reads VMEM only.  GQA is handled per KV head: the
head's rows are a sublane-strided view of the page block, and its ``G``
query heads form the score rows.  Flash-style online softmax keeps
(max, denominator, accumulator) per KV head in VMEM scratch.

The page loop is bounded by the lane's length: it starts at the first
page that can hold a visible key (0, or the first page intersecting the
sliding window) and stops after the page holding the last key, so a
decode touches O(len / page) pages, or O(window / page) with a window.
The boundary pages are masked per position.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def pages_per_block(page: int, max_pages: int) -> int:
    """Pages fetched per flash step: 128 tokens' worth (one lane width of
    scores), capped by the table width."""
    return max(1, min(LANES // page if page < LANES else 1, max_pages))


def vmem_limit(*buffers) -> int:
    """Scoped-VMEM request for a kernel whose scratch and double-buffered
    blocks total ``sum(buffers)`` bytes: twice that plus 8 MiB headroom
    for intermediate values, at least the 16 MiB default, at most 96 MiB
    of v5e's 128 MiB."""
    need = 2 * sum(buffers) + (8 << 20)
    return int(min(max(need, 16 << 20), 96 << 20))


def nbytes(shape, dtype) -> int:
    n = jnp.dtype(dtype).itemsize
    for d in shape:
        n *= d
    return n


def _lanes(x, n: int):
    """Widen or narrow a lane-broadcast (rows, 128) statistic to n lanes."""
    if n == LANES:
        return x
    if n < LANES:
        return x[:, :n]
    if n % LANES == 0:
        return jnp.concatenate([x] * (n // LANES), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _dot_nt(a, b):
    """a (m, d) @ b (n, d)^T -> (m, n) in f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _dot_nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def page_block_loop(n_blk, copies, compute) -> None:
    """Double-buffered DMA loop over ``n_blk`` page blocks.

    ``copies(t, slot)`` builds the async-copy descriptors that land block
    t in buffer ``slot``; ``compute(t, slot)`` consumes it.  Block t+1 is
    started before block t is waited on, so the copy of the next block
    overlaps the scoring of this one."""
    @pl.when(n_blk > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def body(t, carry):
        slot = t % 2

        @pl.when(t + 1 < n_blk)
        def _():
            for c in copies(t + 1, 1 - slot):
                c.start()

        for c in copies(t, slot):
            c.wait()
        compute(t, slot)
        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)


def stack_layers(pools, layer):
    """Kernels address page pools as a (layers, n_pages, ...) stack at a
    layer index (a scalar-prefetch operand): the model's layer scan
    carries the stacked pools and updates one layer in place.  A single
    pool (``layer`` None) is a stack of one; the leading unit axis is a
    free reshape.  Returns (stacked pools, (1,) int32 layer index)."""
    if layer is None:
        return [p[None] for p in pools], jnp.zeros((1,), jnp.int32)
    return list(pools), jnp.asarray(layer, jnp.int32).reshape(1)


def page_copies(table_ref, layer, b, lo, hi, pb, pools_bufs, sems):
    """Descriptor factory for :func:`page_block_loop`: block t covers
    table slots ``lo + t*pb ..``; slots at or past ``hi`` re-fetch slot
    ``hi - 1`` (a mapped page — its positions are masked by the caller).
    ``pools_bufs`` is a list of (HBM pool stack ref, (2, pb, ...) VMEM
    buffer); ``sems`` a DMA semaphore array (len(pools_bufs), 2)."""
    def copies(t, slot):
        out = []
        for i in range(pb):
            j = jnp.minimum(lo + t * pb + i, hi - 1)
            pid = table_ref[b, j]
            for n, (pool, buf) in enumerate(pools_bufs):
                out.append(pltpu.make_async_copy(
                    pool.at[layer, pid], buf.at[slot, i], sems.at[n, slot]))
        return out
    return copies


def online_softmax_update(s, valid, v, m_ref, l_ref, acc_ref):
    """One flash step for a (rows, T) score tile ``s`` against values
    ``v`` (T, d): masked entries contribute exactly zero.  m/l scratch
    hold lane-broadcast (rows, 128) statistics; acc is (rows, d)."""
    T, d = s.shape[1], acc_ref.shape[-1]
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.where(valid, jnp.exp(s - _lanes(m_next, T)), 0.0)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
    acc_ref[...] = (_lanes(alpha, d) * acc_ref[...]
                    + _dot_nn(p.astype(v.dtype), v))
    m_ref[...] = m_next


def finish(acc_ref, l_ref):
    d = acc_ref.shape[-1]
    return acc_ref[...] / _lanes(jnp.maximum(l_ref[...], 1e-30), d)


def gqa_attend(table_ref, layer, b, q_ref, k_pool, v_pool, kbuf, vbuf, sems,
               m_ref, l_ref, acc_ref, o_ref, *, q_start, kv_len,
               scale: float, window: Optional[int], page: int,
               n_kvh: int, group: int):
    """Attention of the (1, TQ, H, hd) query block at global positions
    ``q_start ..`` over the lane's paged KV at positions < ``kv_len``
    (causal, optional sliding window); writes the (1, TQ, H, hd) output
    block.  Shared by the decode kernel (TQ = 1) and the fused prefill /
    verify kernel (one q tile of a chunk)."""
    _, TQ, H, hd = q_ref.shape
    pb = kbuf.shape[1]
    T = pb * page
    rows = TQ * group
    q_last = q_start + TQ - 1
    kv_hi = jnp.minimum(kv_len, q_last + 1)         # exclusive key bound
    hi = (kv_hi + page - 1) // page
    if window is None:
        lo = jnp.int32(0)
    else:
        lo = jnp.maximum((q_start - window + 1) // page, 0)
    lo = jnp.minimum(lo, hi)
    n_blk = (hi - lo + pb - 1) // pb

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def compute(t, slot):
        base = (lo + t * pb) * page
        k_pos = base + jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, T), 0) // group
        valid = (k_pos < kv_len) & (k_pos <= q_pos)
        if window is not None:
            valid &= k_pos > q_pos - window
        kv_k = kbuf.at[slot].reshape(T * n_kvh, hd)
        kv_v = vbuf.at[slot].reshape(T * n_kvh, hd)
        for h in range(n_kvh):
            k = kv_k[pl.ds(h, T, stride=n_kvh), :].astype(jnp.float32)
            v = kv_v[pl.ds(h, T, stride=n_kvh), :].astype(jnp.float32)
            q = q_ref[0, :, h * group:(h + 1) * group, :]
            q = q.astype(jnp.float32).reshape(rows, hd)
            s = _dot_nt(q, k) * scale
            online_softmax_update(s, valid, v, m_ref.at[h], l_ref.at[h],
                                  acc_ref.at[h])

    page_block_loop(n_blk, page_copies(table_ref, layer, b, lo, hi, pb,
                                       [(k_pool, kbuf), (v_pool, vbuf)],
                                       sems), compute)
    for h in range(n_kvh):
        out = finish(acc_ref.at[h], l_ref.at[h])
        o_ref[0, :, h * group:(h + 1) * group, :] = out.reshape(
            TQ, group, hd).astype(o_ref.dtype)


def gqa_scratch(TQ: int, n_kvh: int, group: int, hd: int, pb: int,
                page: int, kv_dtype):
    rows = TQ * group
    buf = (2, pb, page, n_kvh, hd)
    return [
        pltpu.VMEM(buf, kv_dtype),                       # kbuf
        pltpu.VMEM(buf, kv_dtype),                       # vbuf
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((n_kvh, rows, LANES), jnp.float32),   # m
        pltpu.VMEM((n_kvh, rows, LANES), jnp.float32),   # l
        pltpu.VMEM((n_kvh, rows, hd), jnp.float32),      # acc
    ], [nbytes(buf, kv_dtype)] * 2 + [
        2 * nbytes((n_kvh, rows, LANES), jnp.float32),
        nbytes((n_kvh, rows, hd), jnp.float32)]


def _decode_kernel(table_ref, len_ref, layer_ref, q_ref, k_pool, v_pool,
                   o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *,
                   scale: float, page: int, n_kvh: int, group: int,
                   window: Optional[int]):
    b = pl.program_id(0)
    seq_len = len_ref[b]
    gqa_attend(table_ref, layer_ref[0], b, q_ref, k_pool, v_pool, kbuf,
               vbuf, sems,
               m_ref, l_ref, acc_ref, o_ref, q_start=seq_len - 1,
               kv_len=seq_len, scale=scale, window=window, page=page,
               n_kvh=n_kvh, group=group)


def paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens, *,
                           scale: float = None,
                           window: Optional[int] = None,
                           layer=None, interpret: bool = False):
    """q: (B, H, hd); k/v_pages: (n_pages, page, KVH, hd), or a
    (layers, n_pages, page, KVH, hd) stack read at index ``layer``;
    block_table: (B, max_pages) int32; seq_lens: (B,) int32 (the query
    sits at position seq_len - 1); window: sliding-window size in tokens
    (None = full causal)."""
    (k_pages, v_pages), layer = stack_layers((k_pages, v_pages), layer)
    B, H, hd = q.shape
    page, KVH = k_pages.shape[2:4]
    max_pages = block_table.shape[1]
    assert H % KVH == 0
    group = H // KVH
    scale = hd ** -0.5 if scale is None else scale
    pb = pages_per_block(page, max_pages)
    scratch, scratch_bytes = gqa_scratch(1, KVH, group, hd, pb, page,
                                         k_pages.dtype)

    kernel = functools.partial(
        _decode_kernel, scale=scale, page=page, n_kvh=KVH, group=group,
        window=window)
    q_block = pl.BlockSpec((1, 1, H, hd), lambda b, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,             # block_table, seq_lens, layer
        grid=(B,),
        in_specs=[
            q_block,
            pl.BlockSpec(memory_space=pltpu.HBM),     # pages stay in HBM
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=q_block,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit(
                *scratch_bytes, 4 * nbytes((1, H, hd), q.dtype))),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_table, seq_lens, layer, q[:, None], k_pages, v_pages)
    return out[:, 0]
