"""Continuous-batching execution engine — the stateless BatchForward of
paper Algorithm 3 made concrete in JAX, on a paged, device-resident
runtime.

The engine executes planner ``Batch`` objects (Eqn. 1 entries):
  * PREFILL entries process the next chunk of the request's pending context
    (chunked prefill: any split the planner chose), padded to bucket sizes
    to bound recompilation.  KV lands directly in the page pools — there is
    no per-request cache slot to gather or scatter.
  * DECODE entries emit tokens autoregressively.  All requested steps for
    a batch group run as ONE jitted ``lax.scan`` on device — sampling, EOS
    masking, position advance and page writes included — and only the
    final (B, n_steps) token matrix crosses back to the host.  With
    ``spec_step > 0`` and an attached draft model, decoding goes through
    the speculative draft+verify executor (serving/spec_decode.py).

Memory is owned by ``PagedKVManager`` (serving/kvcache.py): one manager
for logical page accounting (admission / preemption) AND the physical
per-layer page pools + device block tables the model reads through.
Engine capacity is bounded by pages, not by max_slots × max_len slabs.

``counters`` tracks jitted device computations (prefill_calls,
decode_calls, decode_tokens) so benchmarks/overhead.py can assert the
one-device-call-per-decode-group invariant.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import Batch
from repro.core.slo import StageKind
from repro.models.config import ModelConfig
from repro.models.transformer import logits_fn, model_forward
from repro.serving.kvcache import PagedKVManager
from repro.serving.sampling import sample


_NULL_CTX = contextlib.nullcontext()    # reusable: nullcontext is stateless


def _bucket(n: int, buckets=(8, 16, 32, 64, 128, 256, 512, 1024, 2048)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 2047) // 2048) * 2048


def _env_flag(name: str, default: bool) -> bool:
    """CI sharing-matrix override: flips an EngineConfig DEFAULT from the
    environment (read per instantiation, so monkeypatching works).  Tests
    that assert sharing behavior pass the field explicitly and are
    unaffected."""
    v = os.environ.get(name)
    return default if v is None else v.lower() not in ("0", "false", "off")


def _env_int(name: str, default: int) -> int:
    """Integer-valued env override, same read-per-instantiation contract
    as ``_env_flag``."""
    v = os.environ.get(name)
    return default if v is None else int(v)


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 8                # max concurrent sequences
    max_len: int = 512                # per-sequence context cap (table width)
    page_size: int = 16
    total_pages: int = 1024
    dtype: object = jnp.float32
    temperature: float = 0.0
    seed: int = 0
    # Prefix sharing: published full pages are mapped into later requests
    # with matching leading tokens (refcount + copy-on-write, kvcache.py).
    # Auto-disabled for SSM-bearing models and per-request when encoder
    # conditioning makes prompt KV depend on more than the token stream.
    share_prefix: bool = dataclasses.field(
        default_factory=lambda: _env_flag("REPRO_SHARE_PREFIX", True))
    # Token-level partial-page matching: a prompt diverging mid-page still
    # reuses the verified head of the boundary page via a CoW'd copy
    # (kvcache.py module docstring).  False = page-granular hits only.
    token_level_prefix: bool = dataclasses.field(
        default_factory=lambda: _env_flag("REPRO_TOKEN_LEVEL_PREFIX", True))
    # Prefix-aware admission: shave the driver's up-front expected_total
    # reservation by the probed cached-prefix hit, so requests whose
    # prompt is mostly resident admit under page pressure that a
    # full-demand reservation would decline.  Decode growth past the
    # shaved reservation extends on demand (with the usual best-effort
    # preemption pressure valve) — more admissions, some thrash risk.
    prefix_aware_admission: bool = False
    # Hierarchical KV: host-RAM spill tier for the prefix cache.  > 0
    # makes LRU-evicted published chains spill to pinned host buffers
    # (that many pages of host budget) and prefetch back async on a hit
    # (kvcache.py "Hierarchical KV").  REPRO_HOST_SPILL=1 turns it on at
    # the default budget; REPRO_HOST_SPILL_PAGES sets an explicit one.
    host_spill_pages: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "REPRO_HOST_SPILL_PAGES",
            256 if _env_flag("REPRO_HOST_SPILL", False) else 0))
    # Modeled host→device bandwidth for the spilled-hit prefetch-latency
    # admission term (PagedKVManager.prefetch_seconds).
    h2d_gbps: float = 16.0
    # Mesh-sharded serving: a jax.sharding.Mesh makes this engine execute
    # its three jitted programs under shard_map over ``shard_axes`` —
    # head-sharded GQA attention, expert-parallel MoE, column-sharded
    # dense FFN, lane-sharded at-rest SSM state (what actually shards is
    # divisibility-gated per model; see distributed/sharding.
    # serving_shard_plan).  None = single-device (unchanged path).
    mesh: object = None
    shard_axes: str = "model"
    # Single-device placement: a jax Device commits this engine's params,
    # page pools, block tables and every per-call input to that device, so
    # several one-chip replicas in one process each run on their own chip.
    # None = JAX's default device.  Exclusive with ``mesh``.
    device: object = None


@dataclasses.dataclass
class RequestCtx:
    rid: int
    prompt: list
    pending: list            # tokens not yet prefilled (prompt or tool ctx)
    generated: list
    eos: Optional[int] = None
    done: bool = False
    enc_states: Optional[object] = None   # VLM / enc-dec conditioning
    # Preemption bookkeeping: ``history`` mirrors the KV cache content (the
    # exact tokens whose embeddings the pages hold), so a preempted request
    # can re-prefill it verbatim.  ``replay`` counts pending tokens that are
    # recompute work (not fresh request progress); ``recompute`` suppresses
    # the one prefill-completion emission that would re-sample an already
    # emitted token.
    history: list = dataclasses.field(default_factory=list)
    replay: int = 0
    recompute: bool = False


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig = None,
                 draft: Optional[tuple] = None, kv_budget=None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg or EngineConfig()
        self.kv = PagedKVManager(cfg, total_pages=self.ecfg.total_pages,
                                 page_size=self.ecfg.page_size,
                                 max_seqs=self.ecfg.max_slots,
                                 max_len=self.ecfg.max_len,
                                 dtype=self.ecfg.dtype,
                                 budget=kv_budget,
                                 share_prefix=self.ecfg.share_prefix,
                                 token_level=self.ecfg.token_level_prefix,
                                 host_spill_pages=self.ecfg.host_spill_pages,
                                 h2d_gbps=self.ecfg.h2d_gbps)
        self.reqs: dict[int, RequestCtx] = {}
        self.key = jax.random.PRNGKey(self.ecfg.seed)
        self._moe_cf = (float(cfg.moe.n_experts) / cfg.moe.top_k
                        if cfg.moe else None)
        # Mesh-sharded serving: place params + at-rest pools per the
        # serving shard plan and wrap the three jitted programs in
        # shard_map.  The plan is read by model_forward (shard=...) at
        # trace time, so the one-scan-per-decode-group and one-host-sync
        # contracts hold per shard by construction.
        self.mesh = self.ecfg.mesh
        self.device = self.ecfg.device
        if self.mesh is not None and self.device is not None:
            raise ValueError("EngineConfig: set mesh or device, not both")
        self._shard_plan = None
        if self.device is not None:
            self.params = jax.device_put(self.params, self.device)
            self.kv.place_on(self.device)
        if self.mesh is not None:
            from repro.distributed import sharding as shd
            self._shard_plan = shd.serving_shard_plan(
                cfg, self.mesh, self.ecfg.shard_axes,
                max_seqs=self.ecfg.max_slots)
            self.params = jax.device_put(
                self.params, shd.tree_named(
                    self.mesh, shd.serving_param_specs(
                        self.params, cfg, self._shard_plan)))
            self.kv.place(self.mesh, self._shard_plan)
        # cache args are donated: PagedKVManager.absorb replaces the pools
        # right after each call, so XLA may update pages in place instead
        # of copying the whole KV budget per step
        if self.mesh is None:
            self._prefill = jax.jit(self._prefill_forward,
                                    donate_argnums=(2,))
            self._decode = jax.jit(self._decode_scan, donate_argnums=(1,),
                                   static_argnames=("n_steps",))
            self._verify = jax.jit(self._verify_forward, donate_argnums=(2,))
        else:
            self._build_sharded_programs()
        self.counters = {"prefill_calls": 0, "decode_calls": 0,
                         "decode_tokens": 0,
                         # decode scans: lanes x steps run (padded and
                         # finished lanes included), and live lanes
                         "decode_lane_steps": 0, "decode_live_lanes": 0,
                         "spec_draft_calls": 0,
                         "spec_verify_calls": 0, "preemptions": 0,
                         "prefix_hit_tokens": 0,
                         # paged-KV ops inside freshly TRACED prefill
                         # programs (attention.OP_STATS deltas; cached
                         # compilations add 0): the fused kernel turns
                         # 2 scatters + 1 attention per layer into one op
                         "prefill_scatter_ops": 0, "prefill_attn_ops": 0,
                         "prefill_fused_ops": 0,
                         # same audit for freshly traced VERIFY programs
                         # (spec-decode multi-token target pass)
                         "verify_scatter_ops": 0, "verify_attn_ops": 0,
                         "verify_fused_ops": 0,
                         # speculation outcome totals feeding the per-class
                         # acceptance EWMA (core.spec_planner)
                         "spec_accepted_tokens": 0,
                         "spec_drafted_tokens": 0}
        # fresh request-level progress granted by the last admission's
        # prefix hit (hit tokens beyond preemption replay) — the driver
        # advances the request by this right after add/restore/readmit
        self.last_hit_fresh = 0
        # fresh (non-replay) prefill tokens consumed per rid in the last
        # execute() call — the frontend's source of truth for request-level
        # prefill progress (recompute prefill after preemption is engine
        # work, not request progress)
        self.last_prefill_progress: dict[int, int] = {}
        # (accepted, drafted) per rid from the last execute() call's verify
        # steps — the frontend feeds these into its per-SLO-class
        # AcceptanceEstimator after each batch
        self.last_spec_stats: dict[int, tuple[int, int]] = {}
        # optional StepTracer (telemetry): when set, execute() times its
        # phases in repro.engine.* spans (intake, then prepare / dispatch
        # / wait / post per prefill group and decode call)
        self.tracer = None
        # speculative decoding: (draft_cfg, draft_params)
        self.spec = None
        if draft is not None:
            from repro.serving.spec_decode import SpecDecoder
            self.spec = SpecDecoder(self, draft[0], draft[1])

    # ------------------------- jitted programs -------------------------- #
    def _build_sharded_programs(self):
        """Wrap the three jitted programs in shard_map over the serving
        mesh.  Params / pools arrive pre-placed (NamedShardings matching
        these specs), so jit inserts no resharding; everything else —
        tokens, positions, block tables, RNG keys, emitted tokens — is
        replicated, which keeps sampling identical on every shard and the
        single host sync per group intact.  check_vma=False: replication
        of the outputs is by construction (identical math per shard), not
        statically inferrable through pallas/scatter ops."""
        import functools

        from jax.sharding import PartitionSpec as P

        from repro.distributed import sharding as shd

        plan = self._shard_plan
        pspec = shd.serving_param_specs(self.params, self.cfg, plan)
        cspec = shd.serving_cache_specs(self.kv.pools, self.cfg, plan,
                                        lane_view=True)
        rep = P()
        smap = functools.partial(jax.shard_map, mesh=self.mesh,
                                 check_vma=False)
        self._prefill = jax.jit(
            smap(self._prefill_forward,
                 in_specs=(pspec, rep, cspec, rep, rep, rep, rep, rep),
                 out_specs=(rep, cspec)),
            donate_argnums=(2,))
        self._verify = jax.jit(
            smap(self._verify_forward,
                 in_specs=(pspec, rep, cspec, rep, rep, rep, rep),
                 out_specs=(rep, cspec)),
            donate_argnums=(2,))

        def _decode_sharded(params, cache, tokens0, pos0, steps, eos, bt,
                            enc_states, key, *, n_steps):
            fn = smap(functools.partial(self._decode_scan, n_steps=n_steps),
                      in_specs=(pspec, cspec, rep, rep, rep, rep, rep,
                                rep, rep),
                      out_specs=(cspec, rep, rep, rep))
            return fn(params, cache, tokens0, pos0, steps, eos, bt,
                      enc_states, key)

        self._decode = jax.jit(_decode_sharded, donate_argnums=(1,),
                               static_argnames=("n_steps",))

    def _prefill_forward(self, params, tokens, cache, pos0, true_len, bt,
                         enc_states, keys):
        """One lane-batched chunk group: each lane writes its chunk's KV
        into its own pages (per-lane block tables) and samples the token at
        its last REAL position (true_len-1 of the padded chunk) — only that
        position's hidden state reaches the vocabulary projection, so the
        step never holds (lanes x chunk x vocab) logits.  Padded lanes
        carry true_len 0: their writes drop and output is ignored."""
        h, cache, _ = model_forward(params, self.cfg, tokens, cache=cache,
                                    pos0=pos0, enc_states=enc_states,
                                    moe_cf=self._moe_cf, block_tables=bt,
                                    chunk_len=true_len,
                                    shard=self._shard_plan)
        idx = jnp.maximum(true_len - 1, 0)
        h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
        last = logits_fn(params, self.cfg, h_last)[:, 0]
        toks = jax.vmap(
            lambda lg, k: sample(lg, k, self.ecfg.temperature))(last, keys)
        return toks, cache

    def _verify_forward(self, params, tokens, cache, pos0, true_len, bt,
                        enc_states):
        """Spec-decode verify: one pass over [last, drafts...]; returns the
        greedy target token at every position (host picks the accepted
        prefix)."""
        h, cache, _ = model_forward(params, self.cfg, tokens, cache=cache,
                                    pos0=pos0, enc_states=enc_states,
                                    moe_cf=self._moe_cf, block_tables=bt,
                                    chunk_len=true_len, verify=True,
                                    shard=self._shard_plan)
        logits = logits_fn(params, self.cfg, h)
        return jnp.argmax(logits[0], axis=-1).astype(jnp.int32), cache

    def _decode_scan(self, params, cache, tokens0, pos0, steps, eos, bt,
                     enc_states, key, *, n_steps):
        """All n_steps decode steps for a batch group in one device
        program.  Per-lane step budgets (``steps``) and EOS stop lanes
        early; frozen lanes emit -1 and neither write KV nor advance."""
        lane_axes = self.kv.lane_select_axes()

        def step(carry, i):
            cache, tok, pos, done, key = carry
            active = (~done) & (i < steps)
            h, new_cache, _ = model_forward(
                params, self.cfg, tok[:, None], cache=cache, pos0=pos,
                enc_states=enc_states, moe_cf=self._moe_cf,
                block_tables=bt, chunk_len=active.astype(jnp.int32),
                shard=self._shard_plan)
            logits = logits_fn(params, self.cfg, h)[:, -1]
            key, sk = jax.random.split(key)
            nxt = sample(logits, sk, self.ecfg.temperature)

            def sel(old, new, ax):
                if ax < 0:            # page pool: writes already masked
                    return new
                shape = [1] * new.ndim
                shape[ax] = active.shape[0]
                return jnp.where(active.reshape(shape), new, old)

            cache = jax.tree.map(sel, cache, new_cache, lane_axes)
            emit = jnp.where(active, nxt, -1)
            tok = jnp.where(active, nxt, tok)
            pos = pos + active.astype(pos.dtype)
            done = done | (active & (nxt == eos))
            return (cache, tok, pos, done, key), emit

        carry0 = (cache, tokens0, pos0,
                  jnp.zeros(tokens0.shape, bool), key)
        (cache, _, pos, done, _), emitted = jax.lax.scan(
            step, carry0, jnp.arange(n_steps))
        return cache, emitted.T, pos, done                # emitted: (B, S)

    def _put(self, x):
        """A host value as a per-call device input, committed to this
        engine's device when it has one."""
        if self.device is None:
            return jnp.asarray(x)
        return jax.device_put(x, self.device)

    # ------------------------------------------------------------------ #
    def _share_tokens(self, tokens, enc_states):
        """Prefix-sharing key for an admission, or None when sharing is
        off or unsound for this request (encoder conditioning means the
        prompt KV depends on more than the token stream)."""
        if not self.ecfg.share_prefix or enc_states is not None:
            return None
        return tokens

    def _consume_hit(self, ctx: RequestCtx, hit: int) -> int:
        """Apply an admission-time prefix hit: the cache already holds
        ``hit`` leading pending tokens, so they move to ``history``
        (KV-content mirror) without a prefill.  Returns the fresh
        request-level progress (hit beyond preemption replay)."""
        self.last_hit_fresh = 0
        if hit <= 0:
            return 0
        ctx.history.extend(ctx.pending[:hit])
        ctx.pending = ctx.pending[hit:]
        replayed = min(hit, ctx.replay)
        ctx.replay -= replayed
        self.counters["prefix_hit_tokens"] += hit
        self.last_hit_fresh = hit - replayed
        return self.last_hit_fresh

    def add_request(self, rid: int, prompt: list, expected_total: int,
                    enc_states=None) -> bool:
        """Admit a request: a sequence slot + pages for the expected
        context.  ``expected_total`` may over-reserve pages (a budget
        hint), but a prompt that cannot fit the per-sequence context cap
        is rejected here rather than crashing mid-prefill.  With prefix
        sharing, cached leading pages are mapped in (``counters[
        "prefix_hit_tokens"]``) and their tokens never re-prefill."""
        if len(prompt) > self.ecfg.max_len:
            return False
        if not self.kv.admit(rid, expected_total,
                             tokens=self._share_tokens(prompt, enc_states)):
            return False
        ctx = RequestCtx(rid=rid, prompt=list(prompt),
                         pending=list(prompt), generated=[],
                         enc_states=enc_states)
        self.reqs[rid] = ctx
        self._consume_hit(ctx, self.kv.length(rid))
        return True

    def finish(self, rid: int) -> None:
        self.kv.release(rid)
        if self.spec is not None:
            self.spec.release(rid)
        self.reqs.pop(rid, None)

    # --------------------- preemption / re-admission -------------------- #
    def preempt(self, rid: int) -> int:
        """Victimize a request (§4.1): free its device pages NOW while
        keeping the request context and its sequence slot.  The discarded
        KV is reconstructed later by re-prefilling ``history`` — the exact
        tokens the cache held — so a request preempted mid-decode resumes
        with an identical greedy token stream.  Returns pages freed."""
        ctx = self.reqs.get(rid)
        if ctx is None:
            return 0
        freed = self.kv.preempt(rid)
        if self.spec is not None:
            self.spec.release(rid)      # draft cache re-syncs on resume
        ctx.recompute = ctx.recompute or (bool(ctx.generated)
                                          and not ctx.pending)
        ctx.replay += len(ctx.history)
        ctx.pending = ctx.history + ctx.pending
        ctx.history = []
        self.counters["preemptions"] += 1
        return freed

    def readmit(self, rid: int, expected_total: int) -> bool:
        """Re-reserve pages for a preempted request's recompute context
        (``preempt`` kept its slot); False while the pool is still short.
        Published pages of the victim's own history survive preemption in
        the cached pool, so the replay typically re-shares them and only
        the residual is re-prefilled."""
        if rid not in self.reqs or rid not in self.kv.seq_of:
            return False
        ctx = self.reqs[rid]
        hit = self.kv.resume(rid, expected_total,
                             tokens=self._share_tokens(ctx.pending,
                                                       ctx.enc_states))
        if hit is None:
            return False
        self._consume_hit(ctx, hit)
        return True

    def drop(self, rid: int):
        """Fully evict a request — pages AND sequence slot — returning its
        context so the frontend can stash it and ``restore`` it later
        (slot-pressure eviction of preempted best-effort victims)."""
        self.kv.release(rid)
        if self.spec is not None:
            self.spec.release(rid)
        return self.reqs.pop(rid, None)

    def restore(self, rid: int, ctx: RequestCtx,
                expected_total: int) -> bool:
        """Re-admit a context evicted by ``drop``: a fresh slot + pages for
        its recompute prefill; generated tokens and replay accounting carry
        over so the stream continues where it left off."""
        if len(ctx.pending) > self.ecfg.max_len:
            return False
        if not self.kv.admit(rid, expected_total,
                             tokens=self._share_tokens(ctx.pending,
                                                       ctx.enc_states)):
            return False
        self.reqs[rid] = ctx
        self._consume_hit(ctx, self.kv.length(rid))
        return True

    def context_len(self, rid: int) -> int:
        return self.kv.length(rid)

    def rollback(self, rid: int, n_tokens: int) -> None:
        """Discard the last n cache positions (spec-decode rejection) —
        with paged KV this is a block-table length decrement."""
        if n_tokens:
            self.kv.truncate(rid, n_tokens)

    def _reserve(self, rid: int, new_total: int, on_pressure=None) -> None:
        if new_total > self.ecfg.max_len:
            raise RuntimeError(
                f"request {rid}: context {new_total} exceeds max_len "
                f"{self.ecfg.max_len}")
        if self.kv.extend(rid, new_total):
            return
        if on_pressure is not None:
            # page exhaustion: let the frontend preempt best-effort
            # victims (frees real device pages), then retry once
            short = (self.kv.pages_needed(new_total)
                     - len(self.kv.tables.get(rid, []))
                     - self.kv.free_pages)
            on_pressure(max(short, 1))
            if self.kv.extend(rid, new_total):
                return
        raise RuntimeError(f"request {rid}: out of KV pages")

    def _cow_barrier(self, rid: int, start: int, n: int,
                     on_pressure=None) -> None:
        """Copy-on-write write barrier with the same pressure escape hatch
        as ``_reserve``: a CoW copy that cannot grab a target page asks the
        frontend to preempt best-effort victims, then retries once."""
        try:
            self.kv.ensure_writable(rid, start, n)
        except RuntimeError:
            if on_pressure is None:
                raise
            on_pressure(1)
            self.kv.ensure_writable(rid, start, n)

    # ------------------------------------------------------------------ #
    def execute(self, batch: Batch, on_pressure=None) -> dict[int, list]:
        """Run one planner batch; returns {rid: emitted tokens}.

        ``on_pressure(pages_short)`` is an optional callback fired when a
        page reservation cannot be satisfied; the frontend uses it to
        preempt best-effort victims (freeing real device pages) before the
        engine retries — failing that, prefill raises and decode caps its
        step budget, exactly as without the callback."""
        emitted: dict[int, list] = {}
        self.last_prefill_progress = {}
        self.last_spec_stats = {}
        with self._tspan("repro.engine.intake"):
            # Overlap point for the host spill tier: admissions queued H2D
            # prefetches; dispatch them all now as one async device copy,
            # then do the host-side prefill grouping while the transfer is
            # in flight (the functional pool update gives the prefill
            # programs a data dependency on the prefetched content — never
            # a stale read).
            self.kv.flush_prefetch()
            prefills = []
            decode_rids = []
            for e in batch.entries:
                if e.rid not in self.reqs:
                    continue
                if e.kind == StageKind.PREFILL:
                    prefills.append((e.rid, e.n_tokens))
                else:
                    decode_rids.append((e.rid, e.n_tokens))
            groups = self._group_prefills(prefills, on_pressure)
        for group in groups:
            for rid, toks in self._prefill_group(*group).items():
                emitted.setdefault(rid, []).extend(toks)
        if decode_rids:
            if batch.spec_step > 0 and self.spec is not None:
                with self._tspan("repro.engine.verify"):
                    for rid, n in decode_rids:
                        emitted.setdefault(rid, []).extend(
                            self.spec.decode(rid, n, on_pressure))
            else:
                out = self._decode_batched(dict(decode_rids), on_pressure)
                for rid, toks in out.items():
                    emitted.setdefault(rid, []).extend(toks)
        return emitted

    def _tspan(self, name: str, **attrs):
        if self.tracer is None:
            return _NULL_CTX
        return self.tracer.span(name, **attrs)

    # ------------------------------------------------------------------ #
    def _group_prefills(self, entries, on_pressure=None):
        """Two-phase chunk intake: reserve pages for EVERY chunk first (a
        failed reservation raises before any pending tokens are consumed,
        keeping every prompt retryable), then consume the chunks and group
        same-bucket ones for lane-batched execution."""
        recs = []
        for rid, n in entries:
            ctx = self.reqs[rid]
            L = min(n, len(ctx.pending))
            if L <= 0:
                continue
            pos = self.kv.length(rid)
            self._reserve(rid, pos + L, on_pressure)
            # CoW before pending is consumed: a failed copy leaves every
            # prompt retryable, and the chunk below writes into pages this
            # request owns exclusively — check_writable re-asserts the
            # contract the fused prefill kernel relies on (its in-kernel
            # page writes must never touch a shared or published page)
            self._cow_barrier(rid, pos, L, on_pressure)
            self.kv.check_writable(rid, pos, L)
            recs.append((rid, ctx.pending[:L], pos))
        for rid, chunk, _ in recs:
            self.reqs[rid].pending = self.reqs[rid].pending[len(chunk):]
        groups: dict = {}
        for rec in recs:
            rid, chunk, _ = rec
            key = (_bucket(len(chunk)),
                   self.reqs[rid].enc_states is not None)
            groups.setdefault(key, []).append(rec)
        out = []
        for (Lp, _), g in groups.items():
            for i in range(0, len(g), 8):       # cap lane fan-out per call
                out.append((Lp, g[i:i + 8]))
        return out

    def _prefill_group(self, Lp: int, recs) -> dict[int, list]:
        """One lane-batched prefill forward for same-bucket chunks from
        different requests (per-lane block tables address each request's
        own pages): ONE jitted device call for the whole group."""
        with self._tspan("repro.engine.prefill.prepare"):
            rids = [rid for rid, _, _ in recs]
            slots = [self.kv.seq_of[r] for r in rids]
            B = _bucket(len(recs), (1, 2, 4, 8))
            pad = B - len(recs)
            slots_p = slots + [slots[0]] * pad
            toks = np.zeros((B, Lp), np.int32)
            true_len = np.zeros((B,), np.int32)
            pos0 = np.zeros((B,), np.int32)
            keys = []
            for i, (rid, chunk, pos) in enumerate(recs):
                toks[i, :len(chunk)] = chunk
                true_len[i] = len(chunk)
                pos0[i] = pos
                ctx = self.reqs[rid]
                if ctx.pending or ctx.recompute:
                    # the sampled token will be discarded: don't advance
                    # the RNG stream — temperature>0 output must not depend
                    # on how the planner split the prefill (or on
                    # preemption replay)
                    keys.append(jax.random.PRNGKey(0))
                else:
                    self.key, sk = jax.random.split(self.key)
                    keys.append(sk)
            keys += [jax.random.PRNGKey(0)] * pad
            args = (self.params, self._put(toks), self.kv.lane_cache(slots_p),
                    self._put(pos0), self._put(true_len),
                    self.kv.table_rows(slots_p), self._gather_enc(rids, B),
                    self._put(jnp.stack(keys)))
        from repro.models import attention as _attn
        with self._tspan("repro.engine.prefill.dispatch"):
            ops0 = dict(_attn.OP_STATS)
            tok, cache = self._prefill(*args)
            self.counters["prefill_scatter_ops"] += (
                _attn.OP_STATS["paged_write"] - ops0["paged_write"])
            self.counters["prefill_attn_ops"] += (
                _attn.OP_STATS["prefill_attn"] - ops0["prefill_attn"])
            self.counters["prefill_fused_ops"] += (
                _attn.OP_STATS["fused_prefill"] - ops0["fused_prefill"])
            self.counters["prefill_calls"] += 1
        with self._tspan("repro.engine.prefill.wait"):
            tok_h = np.asarray(tok)
        with self._tspan("repro.engine.prefill.post"):
            self.kv.absorb(slots, cache)
            out: dict[int, list] = {}
            for i, (rid, chunk, _) in enumerate(recs):
                ctx = self.reqs[rid]
                self.kv.seq_len[slots[i]] += len(chunk)
                replayed = min(len(chunk), ctx.replay)
                ctx.replay -= replayed
                self.last_prefill_progress[rid] = len(chunk) - replayed
                ctx.history.extend(chunk)
                # publish completed prompt pages for later same-prefix
                # requests; decode pages stay private (rollback may rewrite)
                self.kv.register_prefix(rid, ctx.history)
                if not ctx.pending:
                    if ctx.recompute:
                        # recompute after preemption: the cache is restored
                        # exactly; the next decode input is the last
                        # generated token, so this re-sampled emission is
                        # discarded
                        ctx.recompute = False
                    else:
                        # prefill complete: the last position's logits
                        # yield the first output token (TTFT =
                        # time-to-FIRST-token)
                        t = int(tok_h[i])
                        ctx.generated.append(t)
                        out[rid] = [t]
        return out

    # ------------------------------------------------------------------ #
    def _decode_batched(self, steps_of, on_pressure=None) -> dict[int, list]:
        """steps_of: {rid: n_steps} or list of rids (1 step each).  One
        jitted device computation for the whole group."""
        if not isinstance(steps_of, dict):
            steps_of = {r: 1 for r in steps_of}
        out = {r: [] for r in steps_of}
        live = [r for r in steps_of
                if r in self.reqs and not self.reqs[r].done
                and steps_of[r] > 0]
        if not live:
            return out
        with self._tspan("repro.engine.decode.reserve"):
            if on_pressure is not None:
                # decode-step reservation against page exhaustion: report
                # the shortfall so the frontend can preempt best-effort
                # victims before the capping below trims the step budget
                need = 0
                for r in live:
                    want = min(self.kv.length(r) + steps_of[r],
                               self.ecfg.max_len)
                    need += max(0, self.kv.pages_needed(want)
                                - len(self.kv.tables.get(r, [])))
                short = need - self.kv.free_pages
                if short > 0:
                    on_pressure(short)
            # Cap each lane's budget to the pages/context actually
            # available (sequential: earlier lanes claim free pages first)
            # rather than crashing the serving loop mid-stream; the planner
            # sees the shortfall as fewer emitted tokens.
            capped = {}
            for r in live:
                cur = self.kv.length(r)
                n = min(steps_of[r], self.kv.token_capacity(r) - cur)
                if n > 0:
                    self.kv.extend(r, cur + n)
                    self._cow_barrier(r, cur, n, on_pressure)
                    capped[r] = n
        steps_of = capped
        live = [r for r in live if r in capped]
        if not live:
            return out
        with self._tspan("repro.engine.decode.prepare"):
            n_steps = _bucket(max(steps_of[r] for r in live),
                              (1, 2, 4, 8, 16, 32, 64, 128, 256))
            B = _bucket(len(live), (1, 2, 4, 8, 16, 32, 64, 128))
            pad = B - len(live)
            slots = [self.kv.seq_of[r] for r in live]
            slots_p = slots + [slots[0]] * pad
            steps = self._put(np.asarray(
                [steps_of[r] for r in live] + [0] * pad, np.int32))
            starts = [self._last_token(r) for r in live]
            toks0 = self._put(np.asarray(starts + [0] * pad, np.int32))
            eos = self._put(np.asarray(
                [self.reqs[r].eos if self.reqs[r].eos is not None else -1
                 for r in live] + [-1] * pad, np.int32))
            pos0 = self._put(self.kv.seq_len[slots_p].astype(np.int32))
            cache = self.kv.lane_cache(slots_p)
            self.key, sk = jax.random.split(self.key)
            sk = self._put(sk)
            bt = self.kv.table_rows(slots_p)
            enc = self._gather_enc(live, B)
        # lane_steps: every lane of the scan, padded and finished ones
        # included, for every step — the work the device is handed
        with self._tspan("repro.engine.decode.dispatch",
                         lane_steps=n_steps * B):
            cache, emitted, _, _ = self._decode(
                self.params, cache, toks0, pos0, steps, eos, bt, enc, sk,
                n_steps=n_steps)
            self.counters["decode_calls"] += 1
            self.counters["decode_lane_steps"] += n_steps * B
            self.counters["decode_live_lanes"] += len(live)
        with self._tspan("repro.engine.decode.wait"):
            em = np.asarray(emitted)              # ONE host sync per group
        with self._tspan("repro.engine.decode.post"):
            self.kv.absorb(slots, cache)
            for i, r in enumerate(live):
                ctx = self.reqs[r]
                toks = [int(t) for t in em[i, :steps_of[r]] if t >= 0]
                ctx.generated.extend(toks)
                # tokens written to KV this call: the start input + all but
                # the last emission (whose KV lands on the next call)
                ctx.history.extend(([starts[i]] + toks)[:len(toks)])
                out[r].extend(toks)
                self.kv.seq_len[slots[i]] += len(toks)
                self.counters["decode_tokens"] += len(toks)
                if ctx.eos is not None and toks and toks[-1] == ctx.eos:
                    ctx.done = True
        return out

    def _gather_enc(self, rids, B):
        encs = [self.reqs[r].enc_states for r in rids]
        if all(e is None for e in encs):
            return None
        ref = next(e for e in encs if e is not None)
        stack = [e if e is not None else jnp.zeros_like(ref) for e in encs]
        stack += [jnp.zeros_like(ref)] * (B - len(stack))
        return jnp.concatenate(stack, axis=0)

    def _last_token(self, rid: int) -> int:
        ctx = self.reqs[rid]
        if ctx.generated:
            return ctx.generated[-1]
        return ctx.prompt[-1] if ctx.prompt else 0
