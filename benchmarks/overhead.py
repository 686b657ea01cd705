"""Fig. 15: scheduling overhead per planning call (target: <10 ms,
majority <2 ms — paper §6.4), plus the engine-side device-call audit:
the paged runtime must issue exactly ONE jitted computation per decode
batch group (the fused lax.scan) whose step budget fits a step bucket
(<= 256 tokens); a longer budget runs as a chain of <= 256-step scans."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, system_factory
from repro.core.workload import generate_workload


def run(rate: float = 6.0, duration: float = 30.0):
    sim = system_factory("ours-ar")()
    res = sim.run(generate_workload("chatbot", rate, duration, seed=0))
    oh = np.array(res.sched_overheads)
    emit("sched_overhead_median", float(np.median(oh) * 1e6),
         f"p99_ms={np.percentile(oh, 99) * 1e3:.2f};"
         f"max_ms={oh.max() * 1e3:.2f};n={len(oh)};"
         f"frac_under_2ms={float((oh < 0.002).mean()):.2f}")


def run_engine_device_calls(n_decode_tokens: int = 16):
    """Count jitted device computations on the real paged engine: one
    prefill call per chunk, one decode call per batch group of at most
    256 steps (one per <= 256-step scan beyond) — O(1) host round-trips
    per 256 tokens where the dense-slot engine paid O(tokens)."""
    import jax

    from repro.configs import get_reduced
    from repro.core.batch import Batch
    from repro.core.slo import StageKind
    from repro.models import init_params
    from repro.serving.engine import EngineConfig, ServingEngine

    cfg = get_reduced("smollm-135m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params,
                        EngineConfig(max_slots=4, max_len=128,
                                     total_pages=64))
    rng = np.random.default_rng(0)
    for rid in (1, 2):
        eng.add_request(rid, rng.integers(0, cfg.vocab, 16).tolist(),
                        expected_total=64)
        b = Batch()
        b.add(rid, StageKind.PREFILL, 16)
        eng.execute(b)
    b = Batch()
    for rid in (1, 2):
        b.add(rid, StageKind.DECODE, n_decode_tokens)
    out = eng.execute(b)
    n_tokens = sum(len(t) for t in out.values())
    assert eng.counters["decode_calls"] == 1, eng.counters
    assert n_tokens == 2 * n_decode_tokens, (n_tokens, out)
    emit("engine_decode_device_calls", float(eng.counters["decode_calls"]),
         f"tokens={n_tokens};prefill_calls={eng.counters['prefill_calls']};"
         f"tokens_per_device_call={n_tokens / eng.counters['decode_calls']:.0f}")


def run_prefill_fusion(prompt_len: int = 32, chunk: int = 16):
    """Prefill-path op audit for the fused Pallas chunked-prefill kernel:
    per traced prefill chunk the gather reference issues three paged-KV
    ops per attention layer (two ``paged_write`` scatters + one
    gathered-slab attention); the fused kernel issues ONE (in-kernel page
    writes + attention over paged history in the same pass).  Counted
    from ``attention.OP_STATS`` deltas on fresh engines, so the numbers
    reflect the traced device program, not cached recompilations."""
    import jax

    import repro.models.attention as attention
    from repro.configs import get_reduced
    from repro.core.batch import Batch
    from repro.core.slo import StageKind
    from repro.models import init_params
    from repro.serving.engine import EngineConfig, ServingEngine

    cfg = get_reduced("smollm-135m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, prompt_len).tolist()
    ops = {}
    for impl in ("gather", "fused"):
        attention.PAGED_PREFILL_IMPL = impl
        try:
            eng = ServingEngine(cfg, params,
                                EngineConfig(max_slots=4, max_len=128,
                                             total_pages=64))
            eng.add_request(1, prompt, expected_total=prompt_len + 8)
            for _ in range(prompt_len // chunk):
                b = Batch()
                b.add(1, StageKind.PREFILL, chunk)
                eng.execute(b)
            c = eng.counters
            ops[impl] = (c["prefill_scatter_ops"] + c["prefill_attn_ops"]
                         + c["prefill_fused_ops"])
        finally:
            attention.PAGED_PREFILL_IMPL = "auto"
    reduction = ops["gather"] / max(ops["fused"], 1)
    emit("prefill_fused_op_reduction", reduction,
         f"gather_ops={ops['gather']};fused_ops={ops['fused']};"
         f"chunks={prompt_len // chunk};target>=2x")
    assert reduction >= 2.0, ops


def run_mla_prefill_fusion(prompt_len: int = 32, chunk: int = 16):
    """MLA prefill-path op audit (PR 8): per traced latent-prefill chunk
    the gather reference issues three paged-KV ops per MLA layer (ckv
    scatter + krope scatter + latent slab attention); the fused kernel
    issues ONE — in-kernel latent page writes + absorbed two-term
    attention over the paged latent history in one ``pallas_call``.
    Streams must also be bit-identical across backends."""
    import jax

    import repro.models.attention as attention
    from repro.configs import get_reduced
    from repro.core.batch import Batch
    from repro.core.slo import StageKind
    from repro.models import init_params
    from repro.serving.engine import EngineConfig, ServingEngine

    cfg = get_reduced("deepseek-v2-236b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab, prompt_len).tolist()
    ops, streams = {}, {}
    for impl in ("gather", "fused"):
        attention.PAGED_PREFILL_IMPL = impl
        try:
            eng = ServingEngine(cfg, params,
                                EngineConfig(max_slots=4, max_len=128,
                                             total_pages=64))
            eng.add_request(1, prompt, expected_total=prompt_len + 8)
            out = []
            for _ in range(prompt_len // chunk):
                b = Batch()
                b.add(1, StageKind.PREFILL, chunk)
                out += eng.execute(b).get(1, [])
            b = Batch()
            b.add(1, StageKind.DECODE, 4)
            out += eng.execute(b).get(1, [])
            c = eng.counters
            ops[impl] = (c["prefill_scatter_ops"] + c["prefill_attn_ops"]
                         + c["prefill_fused_ops"])
            streams[impl] = out
        finally:
            attention.PAGED_PREFILL_IMPL = "auto"
    assert streams["gather"] == streams["fused"], "MLA backends diverge"
    reduction = ops["gather"] / max(ops["fused"], 1)
    emit("mla_prefill_fused_op_reduction", reduction,
         f"gather_ops={ops['gather']};fused_ops={ops['fused']};"
         f"chunks={prompt_len // chunk};target>=2x")
    assert reduction >= 2.0, ops


def run_verify_fusion(sl: int = 3, rounds: int = 4):
    """Verify-path op audit for the fused multi-token verify step: the
    target's verify of ``sl`` drafts + 1 bonus token IS a chunked prefill
    of the drafted positions, so the fused kernel serves it with ONE op
    per attention layer where the gather reference pays three (two
    ``paged_write`` scatters + one gathered-slab attention).  Both
    backends produce bit-identical accepted streams (greedy acceptance
    is exact); only the traced op count differs."""
    import dataclasses

    import jax

    import repro.models.attention as attention
    from repro.configs import get_reduced
    from repro.core.batch import Batch
    from repro.core.slo import StageKind
    from repro.models import init_params
    from repro.serving.engine import EngineConfig, ServingEngine

    cfg = get_reduced("smollm-135m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    dcfg = dataclasses.replace(cfg, name="draft", n_layers=1,
                               block_pattern=("attn",))
    dparams = init_params(jax.random.PRNGKey(7), dcfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, 24).tolist()
    ops, streams = {}, {}
    for impl in ("gather", "fused"):
        attention.PAGED_VERIFY_IMPL = impl
        try:
            eng = ServingEngine(cfg, params,
                                EngineConfig(max_slots=4, max_len=128,
                                             total_pages=64),
                                draft=(dcfg, dparams))
            eng.add_request(1, prompt, expected_total=96)
            b = Batch()
            b.add(1, StageKind.PREFILL, len(prompt))
            out = eng.execute(b).get(1, [])
            for _ in range(rounds):
                b = Batch(spec_step=sl)
                b.add(1, StageKind.DECODE, sl + 1)
                out += eng.execute(b).get(1, [])
            c = eng.counters
            ops[impl] = (c["verify_scatter_ops"] + c["verify_attn_ops"]
                         + c["verify_fused_ops"])
            streams[impl] = out
        finally:
            attention.PAGED_VERIFY_IMPL = "auto"
    assert streams["gather"] == streams["fused"], "verify backends diverge"
    reduction = ops["gather"] / max(ops["fused"], 1)
    emit("verify_fused_op_reduction", reduction,
         f"gather_ops={ops['gather']};fused_ops={ops['fused']};"
         f"verifies={rounds};sl={sl};target>=2x")
    assert reduction >= 2.0, ops


if __name__ == "__main__":
    run()
    run_engine_device_calls()
    run_prefill_fusion()
    run_mla_prefill_fusion()
    run_verify_fusion()
