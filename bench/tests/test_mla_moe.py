"""The MLA + MoE family (``bench/families/mla_moe.py``) at a tiny size of
the same architecture, on the CPU.

The configuration file is held to the published entry key by key; the
family refuses what it does not model; its weights have the program's
layout; its plain reference agrees with the program's forward; a run
served through the cluster (chunked prefill, then decode through the
paged latent pools) is correct against it while the float8 control is
not; and the expert-layer reader reads the engine's counters.

Tolerances.  Reference and program are both float32 on the CPU, where
XLA multiplies float32 in float32 and the reference asks for HIGHEST;
they differ in reduction order, in the program's absorbed attention
(queries folded into the latent: a reassociation) and in its scattered
expert dispatch, so logits of magnitude ~4 agree to ~1e-5: 1e-4 is ten
times that and three orders below the float8 control's error at this
size (~1 on the logits).
"""
import copy

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.lib import spec
from bench.lib.record import Run

B = spec.benchmark()
FILE = spec.config_file(B, "deepseek-v2-lite")
FAM = spec.family(FILE)
HI = jax.lax.Precision.HIGHEST
TOL = 1e-4

# The catalog's published config of DeepSeek-V2-Lite, copied literally
# from https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json
# (the keys that describe its shape).
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "greedy",
    "v_head_dim": 128, "vocab_size": 102400}
REDUCED = {"num_hidden_layers": 14, "n_routed_experts": 8,
           "torch_dtype": "float32"}

# The same architecture at a tiny size: 3 layers (1 dense), the first 4
# of 8 routed experts held, YaRN as published.
TINY = copy.deepcopy(FILE)
TINY["config"].update(
    hidden_size=64, intermediate_size=96, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    num_attention_heads=4, num_key_value_heads=4, moe_intermediate_size=24,
    num_hidden_layers=3, vocab_size=512, n_routed_experts=4,
    num_experts_per_tok=3)
TINY.update(TINY["config"], router_experts=8,
            zero_load_ttft_s={"a": 0.05, "b": 1e-4})


def test_config_holds_the_published_entry():
    """The published keys stand at the file's top level, where the
    catalog's entry is compared, and in its config group, which the
    harness reads: the same keys in both, each with its published value
    but those ``reduced`` lists, and ``reduced`` is exactly the keys that
    differ (``torch_dtype``, which the catalog omits, among them)."""
    cfg = FILE["config"]
    assert {k: v for k, v in FILE.items() if k not in FAM.FILE_KEYS} == cfg
    assert len(PUBLISHED) == 32
    differ = {k for k in set(cfg) | set(PUBLISHED)
              if cfg.get(k, "absent") != PUBLISHED.get(k, "absent")}
    reduced = next(c["reduced"] for c in B["configs"]
                   if c["name"] == "deepseek-v2-lite")
    assert differ == set(reduced) == set(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert cfg["first_k_dense_replace"] == 1 and cfg["q_lora_rank"] is None
    assert run.dtype_of(FILE) == jnp.float32
    # the router's published width and the deployment stand beside the cut
    assert FILE["router_experts"] == PUBLISHED["n_routed_experts"]
    assert set(FILE["changed"]) == set(REDUCED)
    d = FAM.dims(FILE)
    assert (d.router, d.n_held, d.n_layers, d.n_dense) == (64, 8, 14, 1)


def changed(**keys):
    """The deepseek-v2-lite file with published keys set in both its
    copies (None removes one)."""
    cfg = copy.deepcopy(FILE)
    for k, v in keys.items():
        for group in (cfg, cfg["config"]):
            if v is None:
                group.pop(k)
            else:
                group[k] = v
    return cfg


@pytest.mark.parametrize("cfg", [
    changed(topk_method="group_limited_greedy"),
    changed(topk_method="noaux_tc"),
    changed(n_group=8),
    changed(topk_group=3),
    changed(scoring_func="sigmoid"),
    changed(q_lora_rank=1536),
    changed(model_type="deepseek_v3"),
    changed(model_type=None),
    changed(moe_layer_freq=2),
    changed(tie_word_embeddings=True),
    changed(rope_scaling={"type": "linear", "factor": 4}),
    changed(rope_scaling=None),
    changed(rope_scaling=dict(PUBLISHED["rope_scaling"], mscale=1.0)),
    changed(rms_norm_eps=1e-5),
    changed(routed_scaling_factor=2.5),
    changed(hidden_act="gelu"),
    changed(num_key_value_heads=8),
    changed(partial_rotary_factor=0.5),
    dict(FILE, router_experts=4),
    dict(FILE, num_hidden_layers=27),
], ids=["topk-group-limited", "topk-noaux", "n-group", "topk-group",
        "sigmoid", "q-lora", "deepseek-v3", "no-model-type",
        "moe-every-other", "tied", "rope-linear", "no-rope-scaling",
        "yarn-mscales-differ", "eps", "routed-scale",
        "act", "gqa", "partial-rotary", "held-past-router",
        "copies-differ"])
def test_unmodelled_configs_are_refused(cfg):
    with pytest.raises(ValueError):
        FAM.dims(cfg)


def test_weights_have_the_programs_layout():
    from repro.models.transformer import init_params
    d = FAM.dims(TINY)
    pcfg = FAM.program_config(TINY, "tiny")
    ours = jax.tree.map(lambda a: (a.shape, a.dtype), FAM.make_weights(d, 3))
    theirs = jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), pcfg)))
    assert ours == theirs


def test_reference_matches_model_forward():
    from repro.models.transformer import logits_fn, model_forward
    d = FAM.dims(TINY)
    pcfg = FAM.program_config(TINY, "tiny")
    w = FAM.make_weights(d, 7)
    toks = jax.random.randint(jax.random.PRNGKey(1), (300,), 1, 512)
    h, _, _ = model_forward(w, pcfg, toks[None], moe_cf=8 / 3)
    want = logits_fn(w, pcfg, h)[0]
    got = jnp.matmul(FAM.hidden(d, False, w, FAM._pad(toks.tolist()))[:300],
                     w["unembed"], precision=HI)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    control = jnp.matmul(
        FAM.hidden(d, True, w, FAM._pad(toks.tolist()))[:300],
        w["unembed"], precision=HI)
    assert float(jnp.max(jnp.abs(control - want))) > 1000 * TOL


@pytest.mark.parametrize("prefill", ["gather", "fused"])
def test_sound_run_is_correct(monkeypatch, prefill):
    """Served through the cluster at the program's CPU sizes: chunked
    prefill (the gather reference, or the fused latent kernel in
    interpret mode), then absorbed decode over the paged latent pools;
    the served tokens' logit gaps against the reference stay under the
    float32 tolerance, the control's do not."""
    import repro.models.attention as attention
    from bench.tests.test_harness import SEED, TRAFFIC
    monkeypatch.setattr(attention, "PAGED_PREFILL_IMPL", prefill)
    limits = {"max_logit_gap": TOL, "sample_requests": 4}
    res = run.serve(TINY, FAM, TRAFFIC, 2.0, 3.0, SEED, "tiny", 30, False,
                    full=False)
    w = res["cluster"].drivers[0].engine.params
    got = run.check(res["outs"], {r.rid: r.prompt for r in res["reqs"]},
                    FAM, res["dims"], SEED, limits, w, control=True)
    ok, checks = run.verdict(got, limits)
    assert ok and got["sampled_tokens"] >= 20, checks
    c_ok, c_checks = run.verdict(
        dict(got, max_logit_gap=got["control_max_logit_gap"]), limits)
    assert not c_ok, c_checks


def test_expert_rows_reader_on_a_traced_run_through_the_cluster():
    import gc

    from bench.tests.test_harness import SEED, TRAFFIC
    from repro.telemetry import exporters
    gc.collect()
    assert exporters.live_tracers() == []
    res = run.serve(TINY, FAM, TRAFFIC, 2.0, 3.0, SEED, "tiny", 0, True,
                    full=False)
    r = Run(outs=res["outs"], t0=res["t0"], t_end=res["t_end"],
            t_drain=res["t_drain"], setup_s=1.0, counters=res["counters"],
            plan=res["plan"], trace=res["trace"], family=FAM,
            dims=res["dims"], peaks={}, calls=res["calls"],
            t_trace=res["t_trace"])
    got = spec.reader("moe_expert_rows_useful_pct")(r)
    # 3 of 8 experts per token, 4 held: at most 3/8 of the rows are live
    # units, fewer where lanes and chunk tails are padding
    assert 0 < got <= 100 * 3 / 8
    eng = res["cluster"].drivers[0].engine
    assert eng.counters["moe_expert_rows"] > 0
    del res, eng
    gc.collect()


def test_prefill_kernel_counts():
    """(ops, bytes) of one recorded prefill call at the published widths:
    per layer, each (query, key) pair costs 2 H (r + rope) for the scores
    and 2 H r for the latent context; q_lat and q_rope come in, ctx_lat
    goes out, the chunk's latents come in and go to pages, and every key
    is read once, in float32."""
    d = FAM.dims(FILE)
    H, r, rope, L = 16, 512, 64, 14
    ops, nbytes = FAM.KERNELS["mla_paged_prefill_attention"](
        d, ("prefill", 0.0, [0, 100, 7], [2048, 30, 0], 1))
    keys = 2048 * 2049 // 2 + (30 * 100 + 30 * 31 // 2)
    assert ops == L * 2 * H * (2 * r + rope) * keys
    q_io = lambda n: n * H * (2 * r + rope)
    assert nbytes == L * 4 * (q_io(2048) + 3 * 2048 * (r + rope)
                              + q_io(30) + (3 * 30 + 100) * (r + rope))
    assert FAM.KERNELS["mla_paged_prefill_attention"](
        d, ("decode", 0.0, [5], [3], 4)) is None


def test_model_flops_count_the_held_share():
    d = FAM.dims(FILE)
    D, E, F = 2048, 1408, 10944
    mla = (2 * D * 16 * 192 + 2 * D * 576 + 2 * 16 * 128 * 512 * 2
           + 2 * 16 * 128 * D)
    moe = 2 * D * 64 + 2 * 6 * D * E + 6 * D * E * 6 * 8 / 64
    per_token = 14 * mla + 6 * D * F + 13 * moe
    assert FAM.layer_flops(d) == pytest.approx(per_token, abs=13)
    assert FAM.decode_model_flops(d, 100) == pytest.approx(
        per_token + 14 * 2 * 16 * (576 + 512) * 100 + 2 * D * 102400,
        abs=13)
