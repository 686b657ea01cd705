"""Sharding-rule unit tests (AbstractMesh: no devices needed)."""
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import _fit, batch_spec, param_spec

MESH = AbstractMesh((16, 16), ("data", "model"))
POD_MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_fit_drops_indivisible_axes():
    assert _fit("model", 9, MESH) is None           # smollm heads
    assert _fit("model", 32, MESH) == "model"
    assert _fit(("pod", "data"), 256, POD_MESH) == ("pod", "data")
    assert _fit(("pod", "data"), 16, POD_MESH) == "data"  # falls back


def test_param_spec_attention():
    spec = param_spec("segments/0/p/attn/wq", (4096, 32, 128), MESH,
                      stacked=False)
    assert spec == P("data", "model", None)
    # stacked segments get a leading None for the layer axis
    spec = param_spec("segments/0/p/attn/wq", (32, 4096, 32, 128), MESH,
                      stacked=True)
    assert spec == P(None, "data", "model", None)


def test_param_spec_tp_mode_removes_data():
    spec = param_spec("segments/0/p/attn/wq", (4096, 32, 128), MESH,
                      stacked=False, mode="tp")
    assert spec == P(None, "model", None)
    spec = param_spec("segments/0/p/mlp/w_up", (4096, 14336), MESH,
                      stacked=False, mode="tp")
    assert spec == P(None, "model")


def test_param_spec_moe_expert_parallel():
    spec = param_spec("segments/1/p/moe/w_gate", (160, 5120, 1536), MESH,
                      stacked=False)
    assert spec == P("model", "data", None)


def test_param_spec_norms_replicated():
    assert param_spec("segments/0/p/norm1/scale", (4096,), MESH,
                      stacked=False) == P()


def test_param_spec_indivisible_heads_dropped():
    # smollm: 9 heads % 16 != 0 -> head axis replicated
    spec = param_spec("segments/0/p/attn/wq", (576, 9, 64), MESH,
                      stacked=False)
    assert spec == P("data", None, None)


def test_batch_spec():
    assert batch_spec(MESH, 256) == P("data", None)
    assert batch_spec(MESH, 1) == P(None, None)          # long_500k
    assert batch_spec(POD_MESH, 256) == P(("pod", "data"), None)


# --------------------- serving (mesh-sharded engine) --------------------- #
import jax
import jax.random

from repro.configs import get_reduced
from repro.distributed.sharding import (serving_cache_specs,
                                        serving_param_specs,
                                        serving_shard_plan)
from repro.models import init_params
from repro.serving.kvcache import PagedKVManager

M2 = AbstractMesh((2,), ("model",))
M4 = AbstractMesh((4,), ("model",))


def _tree(tree, path):
    for k in path.split("/"):
        tree = tree[int(k)] if k.isdigit() else tree[k]
    return tree


def test_serving_plan_flags():
    gqa = serving_shard_plan(get_reduced("qwen3-1.7b"), M2, max_seqs=4)
    assert gqa.heads and gqa.mlp and not gqa.experts and not gqa.ssm_lanes
    # 4-way: KVH=2 % 4 != 0 -> attention replicates, MLP still splits
    gqa4 = serving_shard_plan(get_reduced("qwen3-1.7b"), M4, max_seqs=4)
    assert not gqa4.heads and gqa4.mlp
    moe = serving_shard_plan(get_reduced("phi3.5-moe-42b-a6.6b"), M4,
                             max_seqs=4)
    assert moe.experts and not moe.heads
    mla = serving_shard_plan(get_reduced("deepseek-v2-236b"), M2, max_seqs=4)
    assert mla.mla_heads and mla.experts and not mla.heads
    ssm = serving_shard_plan(get_reduced("mamba2-2.7b"), M2, max_seqs=4)
    assert ssm.ssm_lanes and not ssm.mlp          # d_ff == 0 never "splits"
    # slot axis must divide too; otherwise lanes stay replicated
    assert not serving_shard_plan(get_reduced("mamba2-2.7b"), M2,
                                  max_seqs=3).ssm_lanes


def test_serving_param_specs_gqa():
    cfg = get_reduced("qwen3-1.7b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = serving_shard_plan(cfg, M2, max_seqs=4)
    sp = serving_param_specs(params, cfg, plan)
    attn = _tree(sp, "segments/0/p/attn")
    # stacked segment: leading layer axis replicated, head axis sharded
    assert attn["wq"] == attn["wk"] == attn["wv"] \
        == P(None, None, "model", None)
    assert attn["wo"] == P()                      # combine AFTER all_gather
    mlp = _tree(sp, "segments/0/p/mlp")
    assert mlp["w_up"] == P(None, None, "model")
    assert mlp["w_down"] == P()
    assert sp["embed"]["embed"] == P()


def test_serving_param_specs_mla_moe():
    cfg = get_reduced("deepseek-v2-236b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = serving_shard_plan(cfg, M2, max_seqs=4)
    sp = serving_param_specs(params, cfg, plan)
    attn = _tree(sp, "segments/0/p/attn")
    # latent down-projections replicate (they feed the shared latent
    # cache); absorbed up-projections shard on heads
    assert attn["w_dkv"] == attn["w_krope"] == P()
    assert attn["w_uq"] == attn["w_uk"] == attn["w_uv"] \
        == P(None, "model", None)
    moe = _tree(sp, "segments/1/p/moe")
    assert moe["w_gate"] == moe["w_down"] == P("model", None, None)
    assert moe["router"] == moe["shared_up"] == P()


def test_serving_cache_specs():
    cfg = get_reduced("qwen3-1.7b")
    kv = PagedKVManager(cfg, total_pages=16, page_size=4, max_seqs=4,
                        max_len=64)
    plan = serving_shard_plan(cfg, M2, max_seqs=4)
    cs = serving_cache_specs(kv.pools, cfg, plan)
    # stacked segment: leading layer axis, then (P, page, KVH, hd)
    assert _tree(cs, "0/self")["k_pages"] == P(None, None, None, "model",
                                               None)

    mla_cfg = get_reduced("deepseek-v2-236b")
    mkv = PagedKVManager(mla_cfg, total_pages=16, page_size=4, max_seqs=4,
                         max_len=64)
    mcs = serving_cache_specs(
        mkv.pools, mla_cfg, serving_shard_plan(mla_cfg, M2, max_seqs=4))
    # headless latent pools replicate: every shard writes identical rows
    assert _tree(mcs, "0/self")["ckv_pages"] == P()

    ssm_cfg = get_reduced("mamba2-2.7b")
    skv = PagedKVManager(ssm_cfg, total_pages=16, page_size=4, max_seqs=4,
                         max_len=64)
    splan = serving_shard_plan(ssm_cfg, M2, max_seqs=4)
    at_rest = serving_cache_specs(skv.pools, ssm_cfg, splan)
    lane = serving_cache_specs(skv.pools, ssm_cfg, splan, lane_view=True)
    assert _tree(at_rest, "0")["state"] == P(None, "model")
    assert _tree(lane, "0")["state"] == P()       # gathered rows replicate
