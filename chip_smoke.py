#!/usr/bin/env python3
"""Chip smoke test: the serving path on a TPU, at published widths.

Phases, in one process (a parent holding the chip and no children):

  (a) device report: platform, device kind, device count.  Exits non-zero
      unless JAX's platform is ``tpu`` — nothing below runs on a CPU.
  (b) every main-path Pallas kernel, compiled (``interpret=False``),
      against the XLA gather reference of models/attention.py
      (``paged_write`` + ``paged_gather`` + masked ``sdpa``) at
      Qwen3-1.7B widths (16/8 heads, head_dim 128, page 16), plus a
      sliding-window case, and the MLA latent-prefill kernel at
      DeepSeek-V2's latent 512 / rope 64 against kernels/ref.py.
  (c) a one-replica cluster built as launch/serve.py builds it
      (``build_cluster``: ClusterFrontend → ReplicaDriver → ServingEngine
      → PagedKVManager) serving Qwen3-1.7B at its published widths with
      random weights from ``--seed``: 8 requests, prompts of 256–1536
      tokens, 32–128 output tokens, mixed SLO classes, run until idle.
      Every request must finish with its full output.
  (d) each request's first generated token against the argmax of a plain
      dense forward of its prompt (no pages, no kernels) under
      ``jax.default_matmul_precision("highest")``.  Where the reference's
      top-2 logits are closer than twice the logit drift between default
      and highest matmul precision on that prompt, a mismatch is a tie
      and is reported, not failed.

``--four-chips`` runs only the paths that exist across chips, each
against what it is compared with: (i) four one-chip replicas behind the
router, each replica's params, pools, block tables and inputs committed to
its own device, against one replica; (ii) one replica on a 4-way mesh
against a single device.  Widths are published, depth is cut to 4 layers.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

  python3 chip_smoke.py [--four-chips] [--seed N]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# Kernel-vs-reference tolerances (abs and rel).  Kernels and reference
# both run f32 with highest-precision dots; a paging or masking fault
# moves outputs by ~1e-1, accumulation order by ~1e-6.
KERNEL_TOL = 1e-3
PROMPT_LENS = (256, 1536, 512, 1024, 384, 1280, 768, 1100)
OUTPUT_LENS = (32, 128, 64, 96, 48, 112, 80, 40)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums XLA backend compile seconds reported by jax.monitoring."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1


# ------------------------------ (b) kernels ------------------------------ #
def _close(name, got, want, tol):
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    ok = bool(np.allclose(got, want, atol=tol, rtol=tol))
    log(f"  {name}: max|err| {err:.3e} (tol {tol:g}) {'ok' if ok else 'FAIL'}")
    return ok


def check_kernels(seed: int) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_decode_attention
    from repro.kernels.paged_prefill import (mla_paged_prefill_attention,
                                             paged_prefill_attention,
                                             paged_verify_attention)
    from repro.kernels.ref import ref_mla_paged_prefill
    from repro.models.attention import (causal_mask, paged_gather,
                                        paged_write, sdpa)

    H, KVH, hd, page, max_pages = 16, 8, 128, 16, 128
    rng = np.random.default_rng(seed)
    ok = True

    def pools(B, n_extra=3):
        n = B * max_pages + n_extra
        k1, k2 = jax.random.split(jax.random.PRNGKey(int(rng.integers(1e9))))
        table = jnp.asarray(rng.permutation(n)[:B * max_pages].reshape(
            B, max_pages), jnp.int32)
        return (jax.random.normal(k1, (n, page, KVH, hd)),
                jax.random.normal(k2, (n, page, KVH, hd)), table)

    def normal(shape):
        return jax.random.normal(
            jax.random.PRNGKey(int(rng.integers(1e9))), shape)

    @functools.partial(jax.jit, static_argnames=("window",))
    def ref_decode(q, kp, vp, table, lens, window):
        k, v = paged_gather(kp, table), paged_gather(vp, table)
        mask = causal_mask(q.shape[0], 1, k.shape[1], lens - 1, lens, window)
        return sdpa(q[:, None], k, v, mask)[:, 0]

    @functools.partial(jax.jit, static_argnames=("window",))
    def ref_chunk(q, kn, vn, kp, vp, table, pos0, clen, window):
        kp = paged_write(kp, kn, table, pos0, clen)
        vp = paged_write(vp, vn, table, pos0, clen)
        k, v = paged_gather(kp, table), paged_gather(vp, table)
        mask = causal_mask(q.shape[0], q.shape[1], k.shape[1], pos0,
                           pos0 + clen, window)
        return sdpa(q, k, v, mask), kp, vp

    log("(b) kernels, compiled, vs the XLA gather reference "
        f"(H {H}, KVH {KVH}, head_dim {hd}, page {page})")
    for window in (None, 256):
        B = 8
        kp, vp, table = pools(B)
        q = normal((B, H, hd))
        lens = jnp.asarray([1, 16, 17, 300, 1000, 1537, 2047, 2048],
                           jnp.int32)
        got = jax.jit(functools.partial(paged_decode_attention,
                                        window=window))(q, kp, vp, table,
                                                        lens)
        with jax.default_matmul_precision("highest"):
            want = ref_decode(q, kp, vp, table, lens, window)
        ok &= _close(f"paged decode B{B} window {window}", got, want,
                     KERNEL_TOL)

    cases = [("fused prefill", paged_prefill_attention, 4, 2048, None,
              [2048, 1500, 700, 0], [0, 37, 80, 0]),
             ("fused prefill", paged_prefill_attention, 2, 512, 256,
              [512, 300], [1000, 1203]),
             ("fused verify", paged_verify_attention, 2, 4, None,
              [4, 3], [100, 517])]
    for name, fn, B, S, window, clen, pos0 in cases:
        kp, vp, table = pools(B)
        q, kn, vn = normal((B, S, H, hd)), normal((B, S, KVH, hd)), \
            normal((B, S, KVH, hd))
        clen = jnp.asarray(clen, jnp.int32)
        pos0 = jnp.asarray(pos0, jnp.int32)
        with jax.default_matmul_precision("highest"):
            want, wkp, wvp = ref_chunk(q, kn, vn, kp, vp, table, pos0, clen,
                                       window)
        got, gkp, gvp = jax.jit(functools.partial(fn, window=window))(
            q, kn, vn, kp, vp, table, pos0, clen)
        rows = np.arange(S)[None, :] < np.asarray(clen)[:, None]
        tag = f"{name} B{B} S{S} window {window}"
        ok &= _close(tag + " out", np.asarray(got)[rows],
                     np.asarray(want)[rows], KERNEL_TOL)
        ok &= _close(tag + " k pool", gkp, wkp, 0.0)
        ok &= _close(tag + " v pool", gvp, wvp, 0.0)

    # MLA latent prefill at DeepSeek-V2 widths (kv_lora_rank 512, rope 64
    # in a 128-lane pool), 16 heads
    Hm, r, rope, width, B, S, mp = 16, 512, 64, 128, 2, 16, 8
    n = B * mp + 3
    table = jnp.asarray(rng.permutation(n)[:B * mp].reshape(B, mp),
                        jnp.int32)
    cp, rp = normal((n, page, r)), normal((n, page, width))
    ql, qr = normal((B, S, Hm, r)), normal((B, S, Hm, rope))
    cn, rn = normal((B, S, r)), normal((B, S, rope))
    pos0 = jnp.asarray([5, 16], jnp.int32)
    clen = jnp.asarray([S, 9], jnp.int32)
    scale = (128 + rope) ** -0.5
    got, gcp, grp = jax.jit(functools.partial(
        mla_paged_prefill_attention, scale=scale))(
            ql, qr, cn, rn, cp, rp, table, pos0, clen)
    pad = ((0, 0), (0, 0), (0, width - rope))
    with jax.default_matmul_precision("highest"):
        want, wcp, wrp = ref_mla_paged_prefill(
            ql, jnp.pad(qr, ((0, 0),) + pad), cn, jnp.pad(rn, pad), cp, rp,
            np.asarray(table), np.asarray(pos0), np.asarray(clen),
            scale=scale)
    rows = np.arange(S)[None, :] < np.asarray(clen)[:, None]
    ok &= _close(f"MLA prefill B{B} S{S} H{Hm}", np.asarray(got)[rows],
                 np.asarray(want)[rows], KERNEL_TOL)
    ok &= _close("MLA prefill latent pool", gcp, wcp, 0.0)
    ok &= _close("MLA prefill rope pool", grp, wrp, 0.0)
    return ok


# ------------------------------ (c) serving ------------------------------ #
def make_requests(seed: int, vocab: int, n: int = 8, scale: float = 1.0):
    """n requests with prompts of 256–1536 tokens (times ``scale``),
    32–128 output tokens and mixed SLO classes: (rid, prompt, Request)."""
    import numpy as np

    from repro.core.request import Request
    from repro.core.slo import (LOOSE_TPOT, LOOSE_TTFT_SLOWDOWN, TIGHT_TPOT,
                                TIGHT_TTFT_SLOWDOWN, StageSpec, decode_slo,
                                prefill_slo)
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        tight = i % 2 == 0
        L = max(8, int(PROMPT_LENS[i % len(PROMPT_LENS)] * scale))
        O = max(4, int(OUTPUT_LENS[i % len(OUTPUT_LENS)] * scale))
        prompt = rng.integers(1, vocab, L).tolist()
        req = Request(i, arrival=0.05 * i, stages=[
            StageSpec(prefill_slo(TIGHT_TTFT_SLOWDOWN if tight
                                  else LOOSE_TTFT_SLOWDOWN), L),
            StageSpec(decode_slo(TIGHT_TPOT if tight else LOOSE_TPOT), O)])
        out.append((i, prompt, req))
    return out


def serve(cluster, requests):
    """Submit, run until idle, return ({rid: emitted tokens}, stats)."""
    streams = {rid: [] for rid, _, _ in requests}

    def on_token(rid, toks):
        streams[rid].extend(int(t) for t in toks)

    for rid, prompt, req in requests:
        cluster.submit(req, prompt=list(prompt), on_token=on_token)
    stats = cluster.run_until_idle(max_steps=100_000)
    return streams, stats


def check_served(requests, streams, stats) -> bool:
    from repro.core.request import RequestState
    ok = stats.served == len(requests) and stats.dropped == 0
    for rid, prompt, req in requests:
        want = req.total_decode_tokens()
        got = len(streams[rid])
        done = req.state == RequestState.FINISHED and got == want
        ok &= done
        log(f"  request {rid}: prompt {len(prompt)}, output {got}/{want}"
            f" {'ok' if done else 'FAIL'}")
    log(f"  served {stats.served}/{stats.submitted}, dropped "
        f"{stats.dropped}, tokens out {stats.tokens_out}")
    return ok


# ------------------------------ (d) reference ---------------------------- #
def check_first_tokens(cfg, params, requests, streams, pad_to: int) -> bool:
    """Engine first token vs argmax of a dense forward of the prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.transformer import logits_fn, model_forward

    @jax.jit
    def dense_logits(params, tokens, last):
        h, _, _ = model_forward(params, cfg, tokens)
        h_last = jax.lax.dynamic_slice_in_dim(h, last, 1, axis=1)
        return logits_fn(params, cfg, h_last)[0, 0]

    ok = True
    for rid, prompt, _ in requests:
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :len(prompt)] = prompt
        toks = jnp.asarray(toks)
        last = jnp.int32(len(prompt) - 1)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(dense_logits(params, toks, last))
        drift = float(np.max(np.abs(
            np.asarray(dense_logits(params, toks, last)) - ref)))
        top2 = np.argsort(ref)[-2:][::-1]
        gap = float(ref[top2[0]] - ref[top2[1]])
        tie_tol = 2 * drift
        got = streams[rid][0] if streams[rid] else -1
        if got == int(top2[0]):
            verdict = "match"
        elif got == int(top2[1]) and gap < tie_tol:
            verdict = "tie"
        else:
            verdict = "MISMATCH"
            ok = False
        log(f"  request {rid}: engine {got}, reference {int(top2[0])} "
            f"(top-2 gap {gap:.4f}, tie tolerance {tie_tol:.4f}) {verdict}")
    return ok


# ------------------------------ four chips ------------------------------- #
def _committed_to(tree, device) -> bool:
    import jax
    return all(x.devices() == {device} for x in jax.tree.leaves(tree))


def four_chips(seed: int) -> bool:
    import jax

    from repro.configs import get_config
    from repro.distributed.sharding import make_serving_mesh
    from repro.launch.serve import build_cluster
    from repro.models import init_params

    devs = jax.devices()
    if len(devs) < 4:
        log(f"--four-chips needs 4 devices, found {len(devs)}")
        return False
    base = get_config("qwen3-1.7b")
    cfg = dataclasses.replace(base, n_layers=4, block_pattern=())
    log(f"four chips: {cfg.name} at published widths, depth cut to "
        f"{cfg.n_layers} of {base.n_layers} layers")
    params = init_params(jax.random.PRNGKey(seed), cfg)
    requests = lambda: make_requests(seed, cfg.vocab)     # noqa: E731
    ok = True

    t = time.time()
    ref_streams, st = serve(build_cluster(cfg, params, full=True,
                                          replicas=1), requests())
    ok &= st.served == 8 and st.dropped == 0
    log(f"  single device: served {st.served}, tokens {st.tokens_out}, "
        f"wall {time.time() - t:.1f}s")

    log("(i) four one-chip replicas behind the router")
    t = time.time()
    cluster = build_cluster(cfg, params, full=True, replicas=4,
                            devices_per_replica=1)
    placed = set()
    for d in cluster.drivers:
        eng = d.engine
        dev = eng.device
        committed = (_committed_to(eng.params, dev)
                     and _committed_to(eng.kv.pools, dev)
                     and _committed_to(eng.kv.block_tables, dev))
        placed.add(dev.id)
        ok &= committed
        log(f"  replica {d.idx}: device id {dev.id} ({dev.device_kind}), "
            f"params/pools/block tables committed: {committed}")
    ok &= len(placed) == 4
    streams, st = serve(cluster, requests())
    per_replica = [d.engine.counters["prefill_calls"]
                   + d.engine.counters["decode_calls"]
                   for d in cluster.drivers]
    same = streams == ref_streams
    ok &= same and st.served == 8
    log(f"  device calls per replica {per_replica}; streams equal one "
        f"replica's: {same}; wall {time.time() - t:.1f}s")

    log("(ii) one replica on a 4-way mesh")
    t = time.time()
    cluster = build_cluster(cfg, params, full=True, replicas=1,
                            mesh=make_serving_mesh(devs[:4]))
    plan = cluster.drivers[0].engine._shard_plan
    streams, st = serve(cluster, requests())
    same = streams == ref_streams
    ok &= same and st.served == 8 and plan.heads
    log(f"  shard plan: heads {plan.heads}, axis size 4; streams equal "
        f"single-device streams: {same}; wall {time.time() - t:.1f}s")
    if not same:
        for rid in ref_streams:
            if streams[rid] != ref_streams[rid]:
                log(f"  request {rid} differs: {streams[rid][:8]} vs "
                    f"{ref_streams[rid][:8]}")
    return ok


# ------------------------------ main ------------------------------------- #
def one_chip(seed: int, clock: CompileClock) -> bool:
    import jax

    from repro.configs import get_config
    from repro.launch.serve import FULL_SIZE, build_cluster
    from repro.models import init_params

    t = time.time()
    ok = check_kernels(seed)
    log(f"  kernels wall {time.time() - t:.1f}s")
    if not ok:
        return False

    cfg = get_config("qwen3-1.7b")
    log(f"(c) serving {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, tied "
        f"embeddings {cfg.tie_embeddings}, float32, seed {seed}")
    t = time.time()
    params = jax.block_until_ready(init_params(jax.random.PRNGKey(seed),
                                               cfg))
    cluster = build_cluster(cfg, params, full=True, replicas=1)
    log(f"  weights {4 * cfg.param_count() / 1e9:.2f} GB, "
        f"{FULL_SIZE['replica_pages']} KV pages of {FULL_SIZE['page_size']}"
        f" tokens; set-up {time.time() - t:.1f}s")
    requests = make_requests(seed, cfg.vocab)
    c0, t = clock.seconds, time.time()
    streams, stats = serve(cluster, requests)
    wall = time.time() - t
    ok &= check_served(requests, streams, stats)
    counters = cluster.drivers[0].engine.counters
    log(f"  wall {wall:.1f}s, of which XLA compile {clock.seconds - c0:.1f}s;"
        f" {stats.tokens_out} tokens served; {counters['prefill_calls']} "
        f"prefill calls, {counters['decode_calls']} decode calls")

    log("(d) first tokens vs a dense forward at highest precision")
    t = time.time()
    ok &= check_first_tokens(cfg, params, requests, streams,
                             FULL_SIZE["max_len"])
    log(f"  reference wall {time.time() - t:.1f}s")
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (replicas, mesh)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"chip_smoke: no repro package under {SRC}; run from a checkout")
        return 2
    sys.path.insert(0, SRC)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    t_start = time.time()
    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log(f"(a) platform {dev.platform}, device kind {dev.device_kind}, "
        f"{len(devs)} device(s); compile cache {cache}")
    if dev.platform != "tpu":
        log("chip_smoke: JAX found no TPU; this check runs only on the chip")
        return 1
    clock = CompileClock()
    ok = four_chips(args.seed) if args.four_chips else \
        one_chip(args.seed, clock)
    stats = dev.memory_stats() or {}
    log(f"XLA compile {clock.seconds:.1f}s over {clock.count} programs; "
        f"wall {time.time() - t_start:.1f}s; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    if not ok:
        log("chip_smoke: FAILED")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
