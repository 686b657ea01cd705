"""Mesh-sharded serving parity (distributed/sharding.py serving section).

Runs under a forced multi-device host — the CI mesh leg sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — and checks the
tentpole contract: a mesh-sharded engine produces BIT-IDENTICAL greedy
streams to the single-device engine for head-sharded GQA, expert-parallel
MoE, lane-sharded SSM, and MLA (including the fused latent-page prefill),
with the one-jitted-scan-per-decode-group counter audit unchanged, and a
2-replica x 2-device cluster carve serving the same tokens.  Bit identity
holds because cross-shard combination is by concatenation (all_gather of
head/d_ff tiles) before replicated output projections and by
single-contributor psum for MoE units — never by partial-summing
activations through a matmul.  Plan/spec unit tests that need no mesh
live in test_sharding.py."""
import dataclasses

import jax
import numpy as np
import pytest

import repro.models.attention as attention
from repro.configs import get_reduced
from repro.core.batch import Batch
from repro.core.perf_model import cpu_scale_perf_model
from repro.core.request import simple_request
from repro.core.router import RoutingPolicy, make_real_cluster
from repro.core.scheduler import SchedulerConfig
from repro.core.slo import StageKind
from repro.distributed.sharding import make_serving_mesh, serving_shard_plan
from repro.models import init_params
from repro.serving.engine import EngineConfig, ServingEngine

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")

KEY = jax.random.PRNGKey(0)


def _mesh(n):
    return make_serving_mesh(jax.devices()[:n])


def _stream(cfg, params, mesh, prompts, chunks=(11, 9), n_decode=6, **kw):
    """Greedy streams per request: chunked prefill (second chunk starting
    mid-page) then one decode burst; returns streams + engine counters."""
    defaults = dict(max_slots=4, max_len=128, total_pages=64, mesh=mesh)
    defaults.update(kw)
    eng = ServingEngine(cfg, params, EngineConfig(**defaults))
    streams = {}
    for rid, prompt in prompts:
        assert eng.add_request(rid, prompt, expected_total=48)
        got = []
        for n in chunks:
            b = Batch()
            b.add(rid, StageKind.PREFILL, n)
            got += eng.execute(b).get(rid, [])
        b = Batch()
        b.add(rid, StageKind.DECODE, n_decode)
        got += eng.execute(b).get(rid, [])
        streams[rid] = got
    return streams, dict(eng.counters)


def _prompts(cfg, n=2, length=20, seed=3):
    rng = np.random.default_rng(seed)
    return [(rid, rng.integers(1, cfg.vocab, length).tolist())
            for rid in range(1, n + 1)]


def _assert_parity(cfg, mesh_sizes, want_flags, **kw):
    params = init_params(KEY, cfg)
    prompts = _prompts(cfg)
    base, base_c = _stream(cfg, params, None, prompts, **kw)
    assert all(len(s) == 7 for s in base.values())
    for n in mesh_sizes:
        mesh = _mesh(n)
        plan = serving_shard_plan(cfg, mesh, "model", max_seqs=4)
        for flag in want_flags:
            assert getattr(plan, flag), (n, plan)
        got, got_c = _stream(cfg, params, mesh, prompts, **kw)
        assert got == base, (n, plan)
        # one-scan-per-decode-group audit unchanged under shard_map
        for k in ("decode_calls", "prefill_calls", "host_syncs"):
            if k in base_c:
                assert got_c[k] == base_c[k], (n, k, got_c, base_c)


# --------------------------- model families ----------------------------- #
def test_gqa_head_sharded_streams():
    _assert_parity(get_reduced("qwen3-1.7b"), (2,), ("heads", "mlp"))


def test_gqa_four_way_custom_heads():
    """4-way head sharding needs KVH % 4 == 0 — widen the reduced config."""
    cfg = dataclasses.replace(get_reduced("qwen3-1.7b"),
                              n_heads=8, n_kv_heads=4)
    _assert_parity(cfg, (4,), ("heads", "mlp"))


def test_moe_expert_parallel_streams():
    _assert_parity(get_reduced("phi3.5-moe-42b-a6.6b"), (2, 4), ("experts",))


def test_moe_held_share_expert_parallel_streams():
    """A layer holding 4 of 8 routed experts (DeepSeek-V2 weighting)
    splits its held experts over the shards: the same streams and
    expert-work counters as on one device."""
    base = get_reduced("phi3.5-moe-42b-a6.6b")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_experts=8, n_held=4, norm_topk_prob=False))
    _assert_parity(cfg, (2, 4), ("experts",))
    params = init_params(KEY, cfg)
    prompts = _prompts(cfg)
    _, one = _stream(cfg, params, None, prompts)
    _, four = _stream(cfg, params, _mesh(4), prompts)
    for k in ("moe_expert_rows", "moe_routed_units_held"):
        assert four[k] == one[k] > 0, k


def test_ssm_lane_sharded_streams():
    _assert_parity(get_reduced("mamba2-2.7b"), (2, 4), ("ssm_lanes",))


def test_mla_head_sharded_streams():
    _assert_parity(get_reduced("deepseek-v2-236b"), (2,),
                   ("mla_heads", "experts"))


def test_mla_fused_prefill_sharded_streams():
    """The fused latent-page prefill kernel under a mesh: replicated
    latent pools + head-sharded q/absorbed projections must reproduce the
    single-device gather stream bit-for-bit."""
    cfg = get_reduced("deepseek-v2-236b")
    params = init_params(KEY, cfg)
    prompts = _prompts(cfg)
    attention.PAGED_PREFILL_IMPL = "gather"
    try:
        base, _ = _stream(cfg, params, None, prompts)
        attention.PAGED_PREFILL_IMPL = "fused"
        for mesh in (None, _mesh(2)):
            got, _ = _stream(cfg, params, mesh, prompts)
            assert got == base, mesh
    finally:
        attention.PAGED_PREFILL_IMPL = "auto"


def test_indivisible_plan_falls_back_replicated():
    """A mesh the config can't split (3 devices vs 4 heads / 4 experts)
    still serves — every flag off, params replicated, streams identical."""
    cfg = get_reduced("qwen3-1.7b")
    params = init_params(KEY, cfg)
    prompts = _prompts(cfg, n=1)
    mesh = _mesh(3)
    plan = serving_shard_plan(cfg, mesh, "model", max_seqs=4)
    assert not plan.any and not plan.ssm_lanes
    base, _ = _stream(cfg, params, None, prompts)
    got, _ = _stream(cfg, params, mesh, prompts)
    assert got == base


# ----------------------------- 2x2 cluster ------------------------------ #
def test_cluster_two_replicas_two_devices_each():
    """ClusterFrontend.build(devices_per_replica=2) on a 4-device host:
    each replica gets its own 2-device mesh slice and the cluster serves
    the exact streams of an unsharded cluster."""
    cfg = get_reduced("qwen3-1.7b")
    params = init_params(KEY, cfg)
    perf = cpu_scale_perf_model()
    rng = np.random.default_rng(7)
    prompts = {rid: rng.integers(1, cfg.vocab, 16).tolist()
               for rid in range(1, 5)}

    def run(**build_kw):
        cl = make_real_cluster(
            2, cfg, params, perf, policy=RoutingPolicy(max_hops=1),
            total_pages=64, replica_pages=32, page_size=4,
            max_slots=8, max_len=64,
            sched_cfg=SchedulerConfig(page_size=4,
                                      prefill_emits_first_token=True),
            **build_kw)
        got: dict[int, list] = {}
        for rid, p in prompts.items():
            cl.submit(simple_request(rid, 0.0, prompt=len(p), output=4,
                                     ttft_slowdown=8.0, tpot=0.15),
                      prompt=p,
                      on_token=lambda r, t: got.setdefault(r, []).extend(t))
        stats = cl.run_until_idle()
        assert stats.served == len(prompts) and stats.dropped == 0
        return cl, got

    cl, sharded = run(devices_per_replica=2)
    meshes = [d.engine.mesh for d in cl.drivers]
    assert all(m is not None and m.devices.size == 2 for m in meshes)
    assert meshes[0].devices[0] != meshes[1].devices[0]   # distinct slices
    _, base = run()
    assert sharded == base
