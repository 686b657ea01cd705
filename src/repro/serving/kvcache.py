"""KV-cache memory management: the paged, device-resident serving cache.

``PageAllocator`` is the logical page accountant (PagedAttention-style
free list + per-request page tables) — kept standalone so the planner,
the best-effort preemption tier, and property tests can reason about
memory without touching a device.

``PagedKVManager`` extends it into the single physical manager the engine
uses: it owns the per-layer page pools (models/transformer.py
``init_paged_cache``), the device block tables that address them, the
per-sequence lane state (SSM conv/ssd rows, which are O(1) per request
and therefore slot- rather than page-indexed), and the per-sequence
lengths.  Allocation / release / preemption keep the host free list and
the device block tables in lockstep; speculative-decode rollback is a
pure length decrement (``truncate``) — pages stay mapped, later tokens
simply overwrite them.

Shared-prefix pages (refcount / copy-on-write contract)
-------------------------------------------------------
With ``share_prefix=True`` the pool is prefix-shared across requests
(multi-stage agentic workloads resend a common system prompt on every
request, §2.1 scenarios):

* Every physical page carries a **refcount**: the number of request block
  tables it is mapped into.  The shared budget and ``used_pages`` count a
  page exactly once, while it has refcount >= 1; the budget is credited
  only when the refcount returns to zero — never per-table — so sharing
  can never double-count (or double-credit) the cluster budget.
* A **prefix index** maps a page-granularity token-chain hash
  (``h_i = hash(h_{i-1}, tokens[i*ps:(i+1)*ps])`` from position 0) to the
  page holding that chain's KV.  Pages are *published* into the index by
  ``register_prefix`` only once fully written by a prefill (decode-only
  pages are never published: speculative rollback may rewrite them).
  Published pages are immutable; positions and tokens fully determine
  their content, so any request whose leading tokens match the chain may
  map them.  Chain keys are 64-bit hash chains, but matches are never
  trusted on the hash alone: each published page stores its exact chunk
  tokens (``page_tokens``) and ``admit``/``resume``/``probe_prefix``
  verify them per page, so a hash collision degrades to a cache miss
  instead of serving another prompt's KV.
* ``admit``/``resume`` match the longest published chain (capped at
  ``len(tokens) - 1`` so at least one token remains to prefill — the
  completion sample needs a real forward) and map those pages into the
  new request's block table with refcount bumps; only the residual pages
  are freshly allocated.  A preempted victim's published pages survive
  preemption in the cached pool, so its recompute replay re-shares them.
* **Token-level partial-page matching** (``token_level=True``): when a
  prompt diverges *mid-page*, the full-page chain stops at the boundary
  but the request need not forfeit the matched head of the boundary
  page.  A parent index (``children``: chain hash -> published pages
  whose chunk extends that chain) finds candidate boundary pages; the
  longest token-verified common head wins, the donor page is CoW-copied
  into a fresh exclusively-owned page (the jitted donated scatter of
  ``_copy_pages``; position-identical content, so streams stay
  bit-identical), and only the head tokens count toward the hit.  The
  tail of the copied page holds donor garbage that the residual prefill
  overwrites before anything can attend to it (attention never reads
  past the write frontier).  The head page is private from birth —
  refcount 1, unpublished — so ``check_writable`` accepts the residual
  chunk that starts mid-page on it.  Matching stays verification-first:
  candidates are compared token-by-token, so a chain-hash collision
  degrades to a miss at token granularity too.
* **Copy-on-write**: ``ensure_writable`` is the write barrier the engine
  invokes before any KV write.  A write touching a page with refcount > 1
  device-copies the page into a fresh one and remaps this request's block
  table (the other owners keep the original); a write touching an
  exclusively-owned but published page simply unpublishes it (its content
  is about to change).  Chains broken by unpublishing leave downstream
  entries unreachable until re-registered or LRU-evicted — never stale.
* ``release``/``preempt`` drop one reference per page.  A zero-refcount
  *published* page is not freed: it moves to an **LRU cached pool**
  (content intact, still matchable).  Allocation draws from the free list
  first and then evicts cached pages oldest-released-first, unpublishing
  them.  ``free_pages`` therefore counts free + cached (both allocatable
  now), and an idle pool with warm cache still reports
  ``used_pages == 0``.

Hierarchical KV: the host spill tier (``host_spill_pages > 0``)
---------------------------------------------------------------
Without it, LRU eviction is final — the prefix cache dies at HBM
capacity.  With a host tier, a chain's life cycle gains one more state:

* **Spill.**  When ``_grab_pages`` evicts a zero-refcount cached page,
  its contents are device→host copied into a pinned host entry and the
  chain-hash index entry is retagged SPILLED (``host_index``, an LRU
  with its own page budget) instead of vanishing.  A chain hash lives in
  the device index OR the host index, never both.
* **Prefetch.**  A ``probe_prefix``/``admit``/``resume`` hit that walks
  into spilled entries grabs one fresh device page per entry, re-publishes
  it under the chain hash (removing the host entry), splices it into the
  block table — and DEFERS the H2D copy (``_pending_prefetch``).
  ``flush_prefetch`` later executes all queued copies as one jitted
  donated scatter; JAX async dispatch overlaps the transfer with the
  engine's host-side residual-prefill planning, and the functional pool
  update gives every subsequent device program a data dependency on the
  prefetched content, so nothing can read a stale page.
* **Honest probes.**  ``prefix_discounts`` charges each spilled entry one
  grabbable page (physical AND budget) — exactly what ``_share_pages``
  pays to deliver it — and reports the spilled-page count so the planner
  can charge an H2D prefetch-latency term against tight TTFT deadlines.
* **Transfer.**  ``export_chain``/``install_host_chain`` move whole
  published chains between managers through the host tier (cluster-level
  proactive placement and drain-time spill-to-survivors);
  ``chain_hits`` counts per-root-chain probe popularity to drive it.

Greedy streams are bit-identical with the host tier on or off: a
prefetched page holds exactly the bytes the evicted page held, at the
same positions.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.transformer import MLA_KINDS, init_paged_cache


def _copy_bucket(n: int, buckets=(1, 2, 4, 8)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 7) // 8) * 8


@dataclasses.dataclass
class _HostEntry:
    """One spilled page in the host tier: the chain metadata needed to
    re-verify a match (``parent`` hash + exact ``chunk`` tokens) and the
    page contents as per-segment host (numpy) arrays aligned with the
    manager's pool segments (``()`` placeholder for unpaged segments)."""
    parent: Optional[int]
    chunk: tuple
    data: list


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def _host_load_prog(pools, axes, di, vals):
    """Scatter host page contents ``vals`` onto device pages ``di`` in
    every paged pool leaf — the H2D prefetch counterpart of
    ``_copy_pages_prog``.  ``vals[seg]`` leaves are stacked on the page
    axis (``axes[seg]``); the pool argument is DONATED so XLA writes the
    few pages in place.  Padding repeats the last real (page, value)
    pair: a duplicate scatter index rewriting the same value stays
    deterministic."""
    out = []
    for pool, ax, v in zip(pools, axes, vals):
        if ax is None:
            out.append(pool)
            continue

        def ld(leaf, x, ax=ax):
            if ax == 0:
                return leaf.at[di].set(x.astype(leaf.dtype))
            return leaf.at[:, di].set(x.astype(leaf.dtype))
        out.append(jax.tree.map(ld, pool, v))
    return out


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def _copy_pages_prog(pools, axes, si, di):
    """Device copy pages ``si`` onto pages ``di`` in every paged pool leaf
    (``axes[seg] is None`` skips SSM lane state, which is not paged).  The
    pool argument is DONATED — XLA scatters the few pages in place instead
    of materializing a fresh full-size pool per leaf.  Module-level (axes
    are static) so every manager with the same pool shapes shares one
    compilation per copy bucket."""
    out = []
    for pool, ax in zip(pools, axes):
        if ax is None:
            out.append(pool)
            continue

        def cp(leaf, ax=ax):
            if ax == 0:
                return leaf.at[di].set(leaf[si])
            return leaf.at[:, di].set(leaf[:, si])
        out.append(jax.tree.map(cp, pool))
    return out


def kv_page_bytes(cfg: ModelConfig, page_size: int = 16,
                  dtype=jnp.float32) -> int:
    """HBM bytes one KV page costs for ``cfg``, summed over all layers.

    This is what a page *physically* occupies, so two models sharing one
    ``SharedPageBudget`` (e.g. a target and its draft) can be charged in
    comparable units: a draft page is cheaper than a target page by the
    ratio of their per-page bytes.  SSM lane state is not paged and
    contributes nothing.
    """
    itemsize = jnp.dtype(dtype).itemsize
    total = 0
    for kind, n in cfg.segments():
        if kind == "ssm":
            continue
        if kind in MLA_KINDS:
            per_tok = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        else:   # attention: K and V planes
            per_tok = 2 * cfg.n_kv_heads * cfg.head_dim
        total += n * page_size * per_tok * itemsize
    return total


class SharedPageBudget:
    """Cluster-wide KV page budget shared by several PagedKVManagers.

    Each replica owns its physical page pool, but every allocation also
    draws on this logical budget, so a multi-replica cluster can bound its
    aggregate KV footprint below the sum of the per-replica pools (§4.2
    multi-replica serving against one memory budget).  Conservation
    invariant: ``used`` always equals the sum of ``used_pages`` over the
    attached managers — with prefix sharing, a page mapped into several
    block tables is counted once (reserved when its refcount leaves zero,
    credited when it returns to zero).
    """

    def __init__(self, total_pages: int):
        self.total_pages = total_pages
        self.used = 0

    @property
    def available(self) -> int:
        return self.total_pages - self.used

    def reserve(self, n_pages: int) -> bool:
        if n_pages > self.available:
            return False
        self.used += n_pages
        return True

    def release(self, n_pages: int) -> None:
        self.used -= n_pages
        assert self.used >= 0, "shared budget released more than reserved"


class PageAllocator:
    def __init__(self, total_pages: int, page_size: int = 16,
                 budget: Optional[SharedPageBudget] = None):
        self.total_pages = total_pages
        self.page_size = page_size
        self.budget = budget
        self.free = list(range(total_pages - 1, -1, -1))
        self.tables: dict[int, list[int]] = {}

    def pages_needed(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.page_size))

    def can_allocate(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= self.free_pages

    def allocate(self, rid: int, n_tokens: int) -> Optional[list[int]]:
        need = self.pages_needed(n_tokens)
        if need > len(self.free):
            return None
        if self.budget is not None and not self.budget.reserve(need):
            return None
        pages = [self.free.pop() for _ in range(need)]
        self.tables.setdefault(rid, []).extend(pages)
        return pages

    def extend(self, rid: int, new_total_tokens: int) -> bool:
        have = len(self.tables.get(rid, []))
        need = self.pages_needed(new_total_tokens)
        if need <= have:
            return True
        extra = need - have
        if extra > len(self.free):
            return False
        if self.budget is not None and not self.budget.reserve(extra):
            return False
        self.tables.setdefault(rid, []).extend(
            self.free.pop() for _ in range(extra))
        return True

    def release(self, rid: int) -> int:
        pages = self.tables.pop(rid, [])
        self.free.extend(reversed(pages))
        if self.budget is not None:
            self.budget.release(len(pages))
        return len(pages)

    @property
    def used_pages(self) -> int:
        return self.total_pages - len(self.free)

    @property
    def free_pages(self) -> int:
        """Pages allocatable right now: the local free list, further capped
        by what remains of the shared cluster budget."""
        if self.budget is None:
            return len(self.free)
        return min(len(self.free), self.budget.available)


class PagedKVManager(PageAllocator):
    """Unified logical + physical KV manager (PageAllocator ∪ SlotCache).

    Device state:
      * ``pools``        — per-segment cache pytree: page pools for
                           attention/MLA segments, (max_seqs, ...) lane
                           rows for SSM segments,
      * ``block_tables`` — (max_seqs, max_pages_per_seq) int32, row s maps
                           sequence-slot s's logical pages to pool pages.
    Host mirrors: ``seq_len`` (np.int64 per slot), ``seq_of`` (rid→slot),
    per-page ``refcount``, the prefix index + LRU cached pool (module
    docstring), and the inherited free list / page tables.

    Prefix sharing is disabled for SSM-bearing models: skipping a cached
    prefill chunk would skip the (unpaged, lane-resident) SSM state
    updates it performs, so a hit cannot be made exact there.
    """

    def __init__(self, cfg: ModelConfig, *, total_pages: int,
                 page_size: int = 16, max_seqs: int = 8,
                 max_len: int = 512, dtype=jnp.float32,
                 budget: Optional[SharedPageBudget] = None,
                 share_prefix: bool = False, token_level: bool = True,
                 host_spill_pages: int = 0, h2d_gbps: float = 16.0):
        super().__init__(total_pages, page_size, budget=budget)
        self.cfg = cfg
        self.dtype = dtype
        self.max_seqs = max_seqs
        self.max_len = max_len
        self.max_pages_per_seq = max(1, math.ceil(max_len / page_size))
        self.pools = init_paged_cache(cfg, total_pages, page_size,
                                      max_seqs, dtype)
        self.block_tables = jnp.zeros((max_seqs, self.max_pages_per_seq),
                                      jnp.int32)
        self.seq_len = np.zeros((max_seqs,), np.int64)
        self.free_seqs = list(range(max_seqs - 1, -1, -1))
        self.seq_of: dict[int, int] = {}
        # ---- prefix sharing state (module docstring) ----
        self.share_prefix = share_prefix and not any(
            kind == "ssm" for kind, _ in cfg.segments())
        self.token_level = token_level        # partial-page head matching
        self.refcount = np.zeros((total_pages,), np.int32)
        self.prefix_index: dict[int, int] = {}       # chain hash -> page
        self.page_key: dict[int, int] = {}           # page -> chain hash
        self.page_tokens: dict[int, tuple] = {}      # page -> exact chunk
        # parent links for token-level boundary matching: page -> chain
        # hash BEFORE its chunk, and the inverse multi-map
        self.page_parent: dict[int, Optional[int]] = {}
        self.children: dict[Optional[int], set[int]] = {}
        self.cached: OrderedDict[int, int] = OrderedDict()  # LRU, zero-ref
        # per-rid registration cursor: (full pages processed, chain hash
        # there) so repeated register_prefix calls hash incrementally
        self._reg_state: dict[int, tuple[int, Optional[int]]] = {}
        # ---- host spill tier (module docstring, "Hierarchical KV") ----
        # entries live in the device index OR here, never both; the tier
        # is sharing-scoped (no sharing -> nothing publishable to spill)
        self.host_spill_pages = host_spill_pages if self.share_prefix else 0
        self.h2d_gbps = h2d_gbps
        self.host_index: OrderedDict[int, _HostEntry] = OrderedDict()
        # explicit credit-once mirror of the host budget (the property
        # harness asserts host_used == len(host_index), mirroring the
        # SharedPageBudget conservation invariant on the device side)
        self.host_used = 0
        # queued H2D copies: (device page, host entry) — flushed as one
        # donated scatter by flush_prefetch (engine: top of execute())
        self._pending_prefetch: list[tuple[int, _HostEntry]] = []
        # per-root-chain probe/hit popularity (first-page chain hash) —
        # the cluster's proactive-placement signal
        self.chain_hits: dict[int, int] = {}
        self.cow_copies = 0
        self.pages_grabbed = 0
        self.prefix_evictions = 0
        self.spilled_pages = 0         # device pages spilled to host
        self.prefetched_pages = 0      # host entries prefetched to device
        self.host_evictions = 0        # host-tier LRU evictions (final)
        self.spilled_hit_tokens = 0    # hit tokens delivered via prefetch
        self.prefetch_flushes = 0      # jitted H2D scatter calls
        self.partial_head_copies = 0   # boundary pages CoW'd for a head hit
        self.partial_hit_tokens = 0    # hit tokens beyond full-page chains
        # head tokens mapped by the LAST _share_pages, committed to
        # partial_hit_tokens only once the admission sticks (a bounced
        # admit would otherwise leave partial_hit_tokens exceeding the
        # engine's prefix_hit_tokens, its superset)
        self._partial_pending = 0

    # ----------------------- mesh placement ----------------------------- #
    def place(self, mesh, plan) -> None:
        """Shard the at-rest device state over a serving mesh slice:
        attention page pools split on the kv-head axis, SSM lane rows on
        the slot axis, MLA latent pools and block tables replicated
        (distributed/sharding.serving_cache_specs).  Logical accounting
        (free lists, refcounts, prefix index) is host-side and unchanged —
        one allocator drives every shard."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding as shd
        specs = shd.serving_cache_specs(self.pools, self.cfg, plan,
                                        lane_view=False)
        self.pools = jax.device_put(
            self.pools,
            jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                         is_leaf=lambda x: isinstance(x, P)))
        self.block_tables = jax.device_put(self.block_tables,
                                           NamedSharding(mesh, P()))

    def place_on(self, device) -> None:
        """Commit the at-rest device state (pools, block tables) to one
        device: a one-chip replica of a multi-chip host."""
        self.pools = jax.device_put(self.pools, device)
        self.block_tables = jax.device_put(self.block_tables, device)

    # ------------------------ physical page ops ------------------------- #
    @property
    def used_pages(self) -> int:
        """Pages some live request holds (refcount >= 1) — cached
        zero-refcount pages are reclaimable and do not count."""
        return self.total_pages - len(self.free) - len(self.cached)

    @property
    def free_pages(self) -> int:
        avail = len(self.free) + len(self.cached)
        if self.budget is None:
            return avail
        return min(avail, self.budget.available)

    def _grab_pages(self, n: int) -> Optional[list[int]]:
        """Take n physical pages: free list first, then LRU eviction of
        zero-refcount cached pages.  An evicted page spills to the host
        tier (when enabled) before being unpublished, so the chain stays
        matchable.  Reserves the shared budget; None (nothing taken) if
        pages or budget are short."""
        if n <= 0:
            return []
        if n > len(self.free) + len(self.cached):
            return None
        if self.budget is not None and not self.budget.reserve(n):
            return None
        out = []
        for _ in range(n):
            if self.free:
                p = self.free.pop()
            else:
                p, key = self.cached.popitem(last=False)   # LRU victim
                self._spill(p, key)
                self._unpublish(p)
                self.prefix_evictions += 1
            self.refcount[p] = 1
            out.append(p)
        self.pages_grabbed += n
        if self._pending_prefetch:
            # a re-grabbed page must not receive a stale queued H2D copy
            # (its chain data was already re-spilled from the queue above)
            outset = set(out)
            self._pending_prefetch = [(q, e) for q, e in
                                      self._pending_prefetch
                                      if q not in outset]
        return out

    def _unref(self, p: int) -> int:
        """Drop one reference to page p.  Returns 1 when the page became
        physically reclaimable (refcount hit zero) — the only moment the
        shared budget is credited, so shared pages can never double-credit
        it.  Published pages retire to the LRU cached pool instead of the
        free list (content stays matchable)."""
        self.refcount[p] -= 1
        assert self.refcount[p] >= 0, f"page {p} refcount underflow"
        if self.refcount[p] > 0:
            return 0
        if self.budget is not None:
            self.budget.release(1)
        key = self.page_key.get(p)
        if key is not None:
            self.cached[p] = key
        else:
            self.free.append(p)
        return 1

    def _drop_pages(self, rid: int) -> int:
        """Unmap all of rid's pages (keep the rid entry and slot);
        returns pages physically freed (refcount hit zero)."""
        n = 0
        for p in reversed(self.tables.get(rid, [])):
            n += self._unref(p)
        self.tables[rid] = []
        self._reg_state.pop(rid, None)
        s = self.seq_of.get(rid)
        if s is not None:
            self.block_tables = self.block_tables.at[s].set(0)
            self.seq_len[s] = 0
        return n

    # --------------------------- seq slots ----------------------------- #
    def acquire(self, rid: int) -> Optional[int]:
        if rid in self.seq_of:
            return self.seq_of[rid]
        if not self.free_seqs:
            return None
        s = self.free_seqs.pop()
        self.seq_of[rid] = s
        self.seq_len[s] = 0
        self.block_tables = self.block_tables.at[s].set(0)
        return s

    def admit(self, rid: int, expected_total: int, tokens=None) -> bool:
        """Admission = a sequence slot + pages for the expected context.

        ``expected_total`` is the request's full expected memory demand
        (the paper's admission budget) and is reserved in full even when
        it exceeds the per-sequence mappable window (max_len) — the
        surplus pages are a deliberate reservation against the shared
        pool, exactly like the seed's logical allocator, not a leak.

        With ``tokens`` (the request's prompt) and prefix sharing on, the
        longest published chain is mapped in first with refcount bumps;
        only the residual demand draws fresh pages, and ``length(rid)``
        reports the hit so the engine can skip the cached chunk."""
        fresh_slot = rid not in self.seq_of
        if self.acquire(rid) is None:
            return False
        hit = 0
        if self.share_prefix and tokens is not None:
            hit = self._share_pages(rid, tokens)
        if not self.extend(rid, expected_total):
            self._partial_pending = 0      # the mapped hit is dropped too
            self._drop_pages(rid)
            if fresh_slot:
                # decline leaves no trace: a bounced request may never
                # come back to this manager
                self.tables.pop(rid, None)
                self.free_seqs.append(self.seq_of.pop(rid))
            return False
        self.partial_hit_tokens += self._partial_pending
        self._partial_pending = 0
        self.seq_len[self.seq_of[rid]] = hit
        return True

    def resume(self, rid: int, expected_total: int,
               tokens=None) -> Optional[int]:
        """Re-reserve pages for a preempted request's recompute context
        (``preempt`` kept its slot and emptied its table), re-sharing any
        still-published prefix of ``tokens`` (its replay stream).  Returns
        the hit length, or None while the pool is short — in which case
        nothing stays mapped, so the retry starts clean."""
        if rid not in self.seq_of:
            return None
        hit = 0
        if self.share_prefix and tokens is not None \
                and not self.tables.get(rid):
            hit = self._share_pages(rid, tokens)
        if not self.extend(rid, expected_total):
            self._partial_pending = 0      # the mapped hit is dropped too
            if hit:
                self._drop_pages(rid)
            return None
        self.partial_hit_tokens += self._partial_pending
        self._partial_pending = 0
        self.seq_len[self.seq_of[rid]] = hit
        return hit

    # ------------------ page ops (device table in lockstep) ------------ #
    def _map_pages(self, rid: int, start: int, pages: list[int]) -> None:
        s = self.seq_of.get(rid)
        if s is None or start >= self.max_pages_per_seq or not pages:
            return
        end = min(start + len(pages), self.max_pages_per_seq)
        self.block_tables = self.block_tables.at[s, start:end].set(
            jnp.asarray(pages[:end - start], jnp.int32))

    def allocate(self, rid: int, n_tokens: int) -> Optional[list[int]]:
        have = len(self.tables.get(rid, []))
        pages = self._grab_pages(self.pages_needed(n_tokens))
        if pages is None:
            return None
        self.tables.setdefault(rid, []).extend(pages)
        self._map_pages(rid, have, pages)
        return pages

    def extend(self, rid: int, new_total_tokens: int) -> bool:
        have = len(self.tables.get(rid, []))
        need = self.pages_needed(new_total_tokens)
        if need <= have:
            return True
        pages = self._grab_pages(need - have)
        if pages is None:
            return False
        self.tables.setdefault(rid, []).extend(pages)
        self._map_pages(rid, have, pages)
        return True

    def release(self, rid: int) -> int:
        n = self._drop_pages(rid)
        self.tables.pop(rid, None)
        s = self.seq_of.pop(rid, None)
        if s is not None:
            self.free_seqs.append(s)
        return n

    def preempt(self, rid: int) -> int:
        """Victimize a request: drop its page references (and, for pages
        nobody else shares, their budget) but keep its sequence slot so it
        can be re-prefilled after re-admission.  Its published pages
        retire to the cached pool, so the recompute replay re-shares them.
        Returns pages physically freed (reclaimable now)."""
        return self._drop_pages(rid)

    def truncate(self, rid: int, n_tokens: int) -> None:
        """Roll back the last n cache positions (spec-decode rejection):
        a pure length decrement — the pages stay mapped."""
        self.seq_len[self.seq_of[rid]] -= n_tokens

    def length(self, rid: int) -> int:
        return int(self.seq_len[self.seq_of[rid]])

    def token_capacity(self, rid: int) -> int:
        """Max context this request could reach right now: its mapped
        pages plus the whole free list, capped by the block-table width."""
        have = len(self.tables.get(rid, []))
        return min(self.max_len, (have + self.free_pages) * self.page_size)

    # ------------------------- host spill tier -------------------------- #
    def _paged_axes(self) -> tuple:
        """Per-segment page axis of the pool leaves (None = unpaged SSM
        lane state; 1 when the segment spans n>1 layers)."""
        return tuple(None if kind == "ssm" else (1 if n > 1 else 0)
                     for kind, n in self.cfg.segments())

    def _page_to_host(self, p: int) -> list:
        """Device→host copy of page ``p``'s contents, one numpy pytree per
        paged segment (``()`` for unpaged segments)."""
        out = []
        for pool, ax in zip(self.pools, self._paged_axes()):
            if ax is None:
                out.append(())
            elif ax == 0:
                out.append(jax.tree.map(
                    lambda leaf: np.asarray(leaf[p]), pool))
            else:
                out.append(jax.tree.map(
                    lambda leaf: np.asarray(leaf[:, p]), pool))
        return out

    def _spill(self, p: int, key: int) -> None:
        """Retag an LRU-evicted published page as SPILLED: its contents
        move to a host entry under the same chain hash, so the chain stays
        matchable after the device page is reallocated.  A page whose own
        H2D prefetch is still queued spills from the queued host copy (the
        device page may not hold the bytes yet)."""
        if self.host_spill_pages <= 0:
            return
        chunk = self.page_tokens.get(p)
        if chunk is None:
            return
        data = None
        for q, e in self._pending_prefetch:
            if q == p:
                data = e.data
                break
        if data is None:
            data = self._page_to_host(p)
        self._host_insert(key, _HostEntry(self.page_parent.get(p),
                                          chunk, data))
        self.spilled_pages += 1

    def _host_insert(self, key: int, entry: _HostEntry) -> bool:
        """Insert a host entry under its own LRU budget, evicting the
        oldest entries first (a host eviction is final)."""
        if self.host_spill_pages <= 0:
            return False
        if key in self.host_index:
            self.host_index.move_to_end(key)
            return False
        while self.host_used >= self.host_spill_pages:
            self.host_index.popitem(last=False)
            self.host_used -= 1
            self.host_evictions += 1
        self.host_index[key] = entry
        self.host_used += 1
        return True

    def _prefetch_page(self, h: int, parent: Optional[int],
                       chunk: tuple) -> Optional[int]:
        """Move a spilled chain entry host→device: grab one fresh device
        page, re-publish it under the chain hash (``_publish`` removes the
        host entry — a chain is never device-published and spilled at
        once), and queue the H2D copy for ``flush_prefetch``.  None when
        pages or budget are short — the hit truncates there, exactly as
        ``prefix_discounts`` promised."""
        entry = self.host_index.get(h)
        if entry is None:
            return None
        fresh = self._grab_pages(1)
        if fresh is None:
            return None
        q = fresh[0]
        self._pending_prefetch.append((q, entry))
        self._publish(q, h, parent, chunk)
        self.prefetched_pages += 1
        self.spilled_hit_tokens += len(chunk)
        return q

    def flush_prefetch(self) -> int:
        """Execute every queued host→device page copy as ONE jitted
        donated scatter; returns pages copied.  The copy is deferred from
        the admit/resume that queued it: the engine flushes at the top of
        ``execute()``, JAX async dispatch overlaps the transfer with the
        host-side residual-prefill grouping, and the functional pool
        update gives every later device program a data dependency on the
        prefetched content — the residual prefill is never blocked on the
        H2D copy, and can never read a stale page."""
        if not self._pending_prefetch:
            return 0
        pend, self._pending_prefetch = self._pending_prefetch, []
        axes = self._paged_axes()
        B = _copy_bucket(len(pend))
        pend_p = pend + [pend[-1]] * (B - len(pend))
        di = jnp.asarray([q for q, _ in pend_p], jnp.int32)
        vals = []
        for i, ax in enumerate(axes):
            if ax is None:
                vals.append(())
                continue
            vals.append(jax.tree.map(
                lambda *xs, ax=ax: np.stack(xs, axis=ax),
                *[e.data[i] for _, e in pend_p]))
        self.pools = _host_load_prog(self.pools, axes, di, vals)
        self.prefetch_flushes += 1
        return len(pend)

    def prefetch_seconds(self, n_pages: int) -> float:
        """Modeled H2D latency of prefetching ``n_pages`` spilled pages —
        the term the DP planner charges against a spilled hit's TTFT
        deadline so tight-class admission stays honest."""
        if n_pages <= 0:
            return 0.0
        return (n_pages * kv_page_bytes(self.cfg, self.page_size, self.dtype)
                / (self.h2d_gbps * 1e9))

    # -------------------- cross-manager chain transfer ------------------ #
    def root_chains(self) -> list[int]:
        """Chain hashes of every resident first-page entry (device or
        host) — the exportable chain roots."""
        roots = [self.page_key[p] for p in self.children.get(None, ())]
        roots += [h for h, e in self.host_index.items() if e.parent is None]
        return roots

    def export_chain(self, h: int) -> list[tuple]:
        """Export the published chain rooted at hash ``h`` as host-tier
        entries ``(hash, parent, chunk, data)``, walking device and host
        entries alike (D2H-copying device pages).  Linear chains only: a
        branching chain exports its smallest-page-id branch, for
        determinism."""
        self.flush_prefetch()      # device reads below must see content
        out: list[tuple] = []
        while h is not None and len(out) < self.max_pages_per_seq:
            p = self.prefix_index.get(h)
            if p is not None:
                out.append((h, self.page_parent.get(p),
                            self.page_tokens.get(p), self._page_to_host(p)))
            elif h in self.host_index:
                e = self.host_index[h]
                out.append((h, e.parent, e.chunk, e.data))
            else:
                break
            nxt = None
            kids = self.children.get(h)
            if kids:
                nxt = self.page_key[min(kids)]
            else:
                for hh, e in self.host_index.items():
                    if e.parent == h:
                        nxt = hh
                        break
            h = nxt
        return out

    def install_host_chain(self, entries: list[tuple]) -> int:
        """Install exported chain entries into this manager's HOST tier
        (proactive placement / drain-time spill-to-survivors).  Hashes
        already resident — device-published or spilled — are skipped, so
        installs are idempotent and never violate the never-both
        invariant.  Returns entries installed."""
        if self.host_spill_pages <= 0 or not self.share_prefix:
            return 0
        n = 0
        for h, parent, chunk, data in entries:
            if h in self.prefix_index or h in self.host_index \
                    or chunk is None:
                continue
            if self._host_insert(h, _HostEntry(parent, tuple(chunk), data)):
                n += 1
        return n

    # ------------------------- prefix sharing --------------------------- #
    @staticmethod
    def _chain(parent: Optional[int], chunk) -> int:
        return hash((parent, tuple(int(t) for t in chunk)))

    def probe_prefix(self, tokens) -> int:
        """Longest published prefix (in tokens) ``_share_pages`` would
        actually map for this stream right now, capped at
        ``len(tokens) - 1``.  Read-only: the DP planner's cached-prefix
        discount and the cluster's prefix-affinity routing probe with this
        before any pages move.  Mirrors ``_share_pages``' budget
        truncation — reviving a cached (zero-ref) page costs one budget
        page, and a partial-page head hit costs one freshly grabbed page
        (physical AND budget) — so a starved replica reports only the hit
        it can deliver (an optimistic probe would admit tight-TTFT
        requests on a residual the engine then can't grant)."""
        return self.prefix_discounts(tokens)[0]

    def live_prefix_pages(self, tokens, exclude_pages=None) -> int:
        """Matched prefix pages currently mapped by other requests.  These
        cost no free-pool capacity to share; cached (zero-ref) matches DO
        — they already count inside ``free_pages`` — so admission-demand
        discounts must use this, not the full hit.  A partial-page head
        never counts: its CoW copy consumes a fresh page.
        ``exclude_pages`` drops pages the caller already counts as
        reclaimable supply (e.g. best-effort-resident pages), so one page
        never discounts demand and inflates supply at once."""
        return self.prefix_discounts(tokens, exclude_pages)[1]

    def prefix_discounts(self, tokens,
                         exclude_pages=None) -> tuple[int, int, int]:
        """One chain walk returning ``(probe hit tokens, live pages,
        spilled pages)`` — the planner needs all three every tick, and
        walking/hash-verifying the chain twice would double the host-side
        cost for long prompts.  ``spilled`` counts the host-tier entries
        inside the hit, each of which costs one fresh device page to
        deliver (mirrored below) and one page of H2D transfer the planner
        charges as a prefetch-latency deadline term."""
        matches, hit, partial = self._match_pages(tokens)
        live = int(sum(1 for m in matches if m[0] is not None
                       and self.refcount[m[0]] > 0
                       and (exclude_pages is None
                            or m[0] not in exclude_pages)))
        if not matches and partial is None:
            return 0, live, 0
        avail = self.budget.available if self.budget is not None else None
        phys = len(self.free) + len(self.cached)
        usable = 0
        spilled = 0
        for p, _, _, _ in matches:
            if p is not None and self.refcount[p] > 0:
                usable += 1
            elif p is not None:
                # cached revival: one budget page; the page leaves the pool
                if avail is None or avail > 0:
                    if avail is not None:
                        avail -= 1
                    phys -= 1
                    usable += 1
                else:
                    partial = None   # _share_pages truncates the same way
                    break
            else:
                # spilled entry: prefetch needs one freshly grabbed device
                # page — physical AND budget (_prefetch_page's grab)
                if phys > 0 and (avail is None or avail > 0):
                    if avail is not None:
                        avail -= 1
                    phys -= 1
                    usable += 1
                    spilled += 1
                else:
                    partial = None
                    break
        out = min(hit, usable * self.page_size)
        # the boundary head needs one grabbable page: free/cached beyond
        # the revivals above, plus one budget page (_cow_head's grab)
        if partial is not None and out == hit and phys > 0 \
                and (avail is None or avail > 0):
            out += partial[1]
        return out, live, spilled

    def _match_pages(self, tokens) -> tuple[list[tuple], int,
                                            Optional[tuple[int, int]]]:
        """(matches, hit_tokens, partial) of the longest published chain
        for ``tokens``, walking the device index and the host spill tier
        as ONE chain.  Each match is ``(page_or_None, chain_hash, parent,
        chunk)`` — page is None for a spilled (host-resident) link, which
        ``_share_pages`` delivers via ``_prefetch_page``.  ``hit`` is the
        matched token count capped at ``len(tokens) - 1`` (when the cap
        bites mid-chain, the last page is consumed partially and its
        overwrite goes through CoW — ``partial`` is None there).
        ``partial = (donor_page, head_len)`` extends an uncapped chain
        with a token-verified head of a published boundary page."""
        if not self.share_prefix or tokens is None or len(tokens) < 2:
            return [], 0, None
        ps = self.page_size
        h, matches = None, []
        for i in range(len(tokens) // ps):
            chunk = tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])
            nh = self._chain(h, chunk)
            p = self.prefix_index.get(nh)
            # hash match alone is not proof: verify the entry's exact
            # tokens so a 64-bit chain collision can never map another
            # prompt's KV (it degrades to a miss instead)
            if p is not None and self.page_tokens.get(p) == chunk:
                matches.append((p, nh, h, chunk))
            else:
                he = self.host_index.get(nh)
                if he is None or he.chunk != chunk:
                    break
                matches.append((None, nh, h, chunk))
            h = nh
        if matches:
            # root-chain popularity feeds the cluster's proactive
            # placement pass (hot system prompts → under-loaded replicas)
            root = matches[0][1]
            self.chain_hits[root] = self.chain_hits.get(root, 0) + 1
        hit = min(len(matches) * ps, len(tokens) - 1)
        if hit < len(matches) * ps:
            return (matches[:self.pages_needed(hit) if hit else 0],
                    hit, None)
        return matches, hit, self._match_head(h, tokens, hit)

    def _match_head(self, parent: Optional[int], tokens,
                    start: int) -> Optional[tuple[int, int]]:
        """Longest token-verified head of a published boundary page that
        extends chain ``parent`` past position ``start`` — the token-level
        refinement of the page-granular chain walk.  Candidates come from
        the ``children`` parent index (pages published directly after this
        chain), are compared token-by-token (a colliding hash can only
        ever degrade to a shorter verified head, never a wrong one), and
        the head stays under the ``len(tokens) - 1`` completion cap.
        Smallest page id breaks length ties, for determinism."""
        if not self.token_level:
            return None
        room = min(len(tokens) - 1 - start, self.page_size)
        if room <= 0:
            return None
        nxt = [int(t) for t in tokens[start:start + room]]
        best = None
        for p in sorted(self.children.get(parent, ())):
            chunk = self.page_tokens.get(p)
            if not chunk:
                continue
            m = 0
            for a, b in zip(chunk, nxt):
                if a != b:
                    break
                m += 1
            if m > 0 and (best is None or m > best[1]):
                best = (p, m)
        return best

    def _share_pages(self, rid: int, tokens) -> int:
        """Map the longest published chain into rid's (empty) block table
        with refcount bumps.  Reviving a cached (zero-ref) page re-reserves
        one budget page; a failed reservation truncates the hit there.  A
        partial-page boundary match appends a CoW copy of the donor's head
        (a fresh, private, unpublished page) and counts only the verified
        head tokens."""
        self._partial_pending = 0
        matches, hit, partial = self._match_pages(tokens)
        taken: list[int] = []
        for p, nh, parent, chunk in matches:
            if p is None:
                # spilled link: queue an async H2D prefetch into a fresh
                # device page (republished immediately; data lands at the
                # next flush_prefetch(), before any device program reads)
                q = self._prefetch_page(nh, parent, chunk)
                if q is None:   # device pages or budget short: truncate
                    break
                taken.append(q)   # _grab_pages already set refcount = 1
                continue
            if self.refcount[p] > 0:
                self.refcount[p] += 1
            elif self.budget is None or self.budget.reserve(1):
                self.cached.pop(p)
                self.refcount[p] = 1
            else:
                break
            taken.append(p)
        if len(taken) < len(matches):
            hit = min(hit, len(taken) * self.page_size)
            partial = None
        if partial is not None:
            head = self._cow_head(partial[0])
            if head is not None:
                taken.append(head)
                hit += partial[1]
                self._partial_pending = partial[1]
        if not taken:
            return 0
        self.tables.setdefault(rid, []).extend(taken)
        self._map_pages(rid, 0, taken)
        return hit

    def _cow_head(self, donor: int) -> Optional[int]:
        """Copy the published donor page into a fresh exclusively-owned
        page (refcount 1, unpublished) so its matched token head can seed
        a new request's boundary page; None when pages or budget are
        short.  ``_grab_pages`` may evict the donor itself (a zero-ref
        cached page at the LRU end): its content is already in place, so
        the device copy is skipped."""
        fresh = self._grab_pages(1)
        if fresh is None:
            return None
        q = fresh[0]
        if q != donor:
            self._copy_pages([donor], [q])
        self.partial_head_copies += 1
        return q

    def register_prefix(self, rid: int, tokens) -> None:
        """Publish rid's full, final pages into the prefix index.  Call
        only after prefill writes (`tokens` = the exact cache content):
        decode-tail pages stay private, since speculative rollback may
        rewrite them.  Chains already published (by any page) are kept —
        duplicates are deduped toward the first publisher.  A per-rid
        cursor resumes the chain hash where the last call stopped, so a
        request prefilled in many chunks hashes each page once (the
        cursor resets with the table on preempt/release)."""
        if not self.share_prefix:
            return
        pages = self.tables.get(rid, [])
        ps = self.page_size
        done, h = self._reg_state.get(rid, (0, None))
        n_full = min(len(tokens) // ps, len(pages))
        for i in range(done, n_full):
            chunk = tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])
            parent = h
            h = self._chain(h, chunk)
            p = pages[i]
            if h in self.prefix_index or p in self.page_key:
                continue
            self._publish(p, h, parent, chunk)
        if n_full > done:
            self._reg_state[rid] = (n_full, h)

    def _publish(self, p: int, h: int, parent: Optional[int],
                 chunk: tuple) -> None:
        """Insert page p into the prefix index under chain hash ``h``,
        recording its parent link so token-level boundary matching can
        enumerate the chain's published extensions.  A host-tier entry
        for the same chain is dropped: a chain is never simultaneously
        device-published and spilled (the device copy is authoritative
        and the host bytes are now redundant)."""
        self.prefix_index[h] = p
        self.page_key[p] = h
        self.page_tokens[p] = chunk
        self.page_parent[p] = parent
        self.children.setdefault(parent, set()).add(p)
        if self.host_index.pop(h, None) is not None:
            self.host_used -= 1

    def _unpublish(self, p: int) -> None:
        """Remove page p from the prefix index (CoW overwrite or LRU
        eviction), including its parent/children links.  No-op for
        unpublished pages."""
        key = self.page_key.pop(p, None)
        if key is None:
            return
        del self.prefix_index[key]
        self.page_tokens.pop(p, None)
        parent = self.page_parent.pop(p, None)
        kids = self.children.get(parent)
        if kids is not None:
            kids.discard(p)
            if not kids:
                del self.children[parent]

    def ensure_writable(self, rid: int, start_tok: int,
                        n_tokens: int) -> None:
        """Copy-on-write barrier: before rid writes cache positions
        ``[start_tok, start_tok + n_tokens)``, make every touched page
        exclusively owned and unpublished.  Shared pages are device-copied
        into fresh pages and rid's block table is remapped (other owners
        keep the original); an exclusively-owned published page is just
        unpublished (its content is about to change).  Transactional: all
        copy targets are grabbed up front, so the RuntimeError raised when
        they cannot be leaves no state mutated and the barrier can simply
        be retried after the caller frees pages."""
        if not self.share_prefix or n_tokens <= 0:
            return
        pages = self.tables.get(rid, [])
        ps = self.page_size
        first = start_tok // ps
        last = min((start_tok + n_tokens - 1) // ps, len(pages) - 1)
        idx = [i for i in range(first, last + 1)
               if self.refcount[pages[i]] > 1]
        fresh = self._grab_pages(len(idx)) if idx else []
        if fresh is None:
            raise RuntimeError(
                f"request {rid}: out of KV pages for copy-on-write")
        for i in range(first, last + 1):
            p = pages[i]
            if self.refcount[p] <= 1 and p in self.page_key:
                self._unpublish(p)
        src, dst = [], []
        for i, q in zip(idx, fresh):
            p = pages[i]
            self.refcount[p] -= 1            # still shared by the others
            pages[i] = q
            src.append(p)
            dst.append(q)
        if not src:
            return
        self._copy_pages(src, dst)
        s = self.seq_of.get(rid)
        if s is not None:
            cols = [i for i in idx if i < self.max_pages_per_seq]
            if cols:
                vals = [pages[i] for i in cols]
                self.block_tables = self.block_tables.at[
                    s, jnp.asarray(cols, jnp.int32)].set(
                    jnp.asarray(vals, jnp.int32))
        self.cow_copies += len(src)

    def check_writable(self, rid: int, start_tok: int,
                       n_tokens: int) -> list[int]:
        """The write-set handoff to the fused prefill kernel: returns the
        pages covering cache positions ``[start_tok, start_tok+n_tokens)``
        after asserting every one passed the ``ensure_writable`` barrier
        (exclusively owned, unpublished).  ``start_tok`` may fall mid-page
        — a token-level partial hit (or the ``len - 1`` cap) leaves the
        residual chunk starting inside the boundary page, which by then is
        a CoW'd head this request owns exclusively, so the same assertions
        cover it.  The kernel writes these pages in-kernel with no further
        checks, so a violation here would break the bit-identical sharing
        guarantee — fail loudly instead."""
        pages = self.tables.get(rid, [])
        ps = self.page_size
        first = start_tok // ps
        last = min((start_tok + n_tokens - 1) // ps, len(pages) - 1)
        out = [pages[i] for i in range(first, last + 1)] if n_tokens > 0 \
            else []
        if self.share_prefix:
            for p in out:
                assert self.refcount[p] == 1, \
                    f"page {p} of rid {rid} still shared at write time"
                assert p not in self.page_key, \
                    f"page {p} of rid {rid} still published at write time"
        return out

    def _copy_pages(self, src: list[int], dst: list[int]) -> None:
        """Device copy src pages onto dst pages via the module-level
        jitted program (``_copy_pages_prog``; shared across managers).
        Copy counts are bucketed — padded by repeating the last real
        (src, dst) pair, which rewrites the same value and so stays
        deterministic under duplicate scatter indices — so CoW batch
        sizes share compilations.  Pending prefetches flush first: a CoW
        source may be a prefetched page whose H2D copy is still queued."""
        self.flush_prefetch()
        axes = self._paged_axes()
        B = _copy_bucket(len(src))
        pad = B - len(src)
        si = jnp.asarray(src + [src[-1]] * pad, jnp.int32)
        di = jnp.asarray(dst + [dst[-1]] * pad, jnp.int32)
        self.pools = _copy_pages_prog(self.pools, axes, si, di)

    # ------------------------ device-facing views ----------------------- #
    def table_rows(self, slots) -> jnp.ndarray:
        """(len(slots), max_pages_per_seq) block-table rows."""
        return jnp.take(self.block_tables, jnp.asarray(slots, jnp.int32),
                        axis=0)

    def lane_cache(self, slots):
        """Per-call cache pytree: page pools pass through whole (they are
        global, addressed by block tables); SSM lane state is gathered to
        one row per batch lane.  Flushes pending prefetches so the view
        never exposes a page whose H2D copy is still queued."""
        self.flush_prefetch()
        idx = jnp.asarray(slots, jnp.int32)
        out = []
        for pool, (kind, n) in zip(self.pools, self.cfg.segments()):
            if kind == "ssm":
                ax = 1 if n > 1 else 0
                out.append(jax.tree.map(
                    lambda c, ax=ax: jnp.take(c, idx, axis=ax), pool))
            else:
                out.append(pool)
        return out

    def absorb(self, slots, new_cache) -> None:
        """Store a model call's updated cache: pools replace wholesale
        (functionally updated in place), lane rows scatter back."""
        idx = jnp.asarray(slots, jnp.int32)
        n_live = len(slots)
        pools = []
        for pool, new, (kind, n) in zip(self.pools, new_cache,
                                        self.cfg.segments()):
            if kind == "ssm":
                ax = 1 if n > 1 else 0

                def put(c, s, ax=ax):
                    s = jnp.take(s, jnp.arange(n_live), axis=ax)
                    return (c.at[idx].set(s) if ax == 0
                            else c.at[:, idx].set(s))
                pools.append(jax.tree.map(put, pool, new))
            else:
                pools.append(new)
        self.pools = pools

    def lane_select_axes(self):
        """Pytree (aligned with a lane_cache) of the lane axis for each
        SSM leaf, or -1 for paged-pool leaves — used by the engine's
        decode scan to freeze inactive lanes' state."""
        out = []
        for pool, (kind, n) in zip(self.pools, self.cfg.segments()):
            ax = (1 if n > 1 else 0) if kind == "ssm" else -1
            out.append(jax.tree.map(lambda _, ax=ax: ax, pool))
        return out
