"""Each configuration names its model family, and the harness reaches the
model code only through it: the configurations in BENCHMARK.json load
theirs, a configuration the family does not model is refused, the dense
family counts what the harness counted before families existed, and a
family that no harness file names is served by its name alone."""
import copy
import re
from pathlib import Path

import pytest

from bench import run
from bench.lib import spec
from bench.tests.test_harness import LIMITS, SEED, TINY, TRAFFIC

B = spec.benchmark()
CONFIGS = {c["name"]: spec.config_file(B, c["name"]) for c in B["configs"]}
DATA_FAMILIES = Path(__file__).parent / "data" / "families"
HARNESS = ([spec.BENCH / "run.py"] + sorted((spec.BENCH / "lib").glob("*.py"))
           + sorted((spec.BENCH / "metrics").glob("*.py")))
FUNCTIONS = ("dims", "program_config", "make_weights", "served_gaps",
             "control_gaps", "prefill_model_flops", "decode_model_flops")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_config_loads_its_family(name):
    from repro.models.config import ModelConfig
    cfg = CONFIGS[name]
    fam = spec.family(cfg)
    for f in FUNCTIONS:
        assert callable(getattr(fam, f)), f
    assert fam.KERNELS and all(callable(c) for c in fam.KERNELS.values())
    d = fam.dims(cfg)
    hash(d)
    assert d.vocab == cfg["config"]["vocab_size"]
    assert isinstance(fam.program_config(cfg, name), ModelConfig)
    # every kernel roofline a cell of this config reports, the family counts
    for w in B["workloads"]:
        if w["config"] == name:
            for m in spec.metrics_of(B, w["name"], "per_layer"):
                if m["name"].endswith("_roofline"):
                    assert m["name"][:-len("_roofline")] in fam.KERNELS


QWEN = CONFIGS["qwen3-1.7b"]


def changed(**keys):
    """The qwen3-1.7b file with keys of its published config set (None
    removes one)."""
    cfg = copy.deepcopy(QWEN)
    for k, v in keys.items():
        if v is None:
            cfg["config"].pop(k)
        else:
            cfg["config"][k] = v
    return cfg


def without_family():
    cfg = copy.deepcopy(QWEN)
    del cfg["family"]
    return cfg


@pytest.mark.parametrize("cfg,error", [
    (dict(QWEN, family="no_such_family"), FileNotFoundError),
    (without_family(), ValueError),
    (dict(QWEN, family="../families/dense_gqa"), ValueError),
    (changed(model_type="phi3"), ValueError),
    (changed(tie_word_embeddings=False), ValueError),
    (changed(tie_word_embeddings=None), ValueError),
    (changed(partial_rotary_factor=0.75), ValueError),
    (changed(rope_scaling={"type": "longrope", "short_factor": [1.0] * 48,
                           "long_factor": [1.0] * 48}), ValueError),
    (changed(rms_norm_eps=1e-5), ValueError),
    (changed(hidden_act="gelu"), ValueError),
], ids=["unknown-family", "no-family", "family-path", "phi3", "untied",
        "tie-unstated", "partial-rotary", "rope-scaling", "eps", "act"])
def test_unmodelled_configs_are_refused(cfg, error):
    with pytest.raises(error):
        spec.family(cfg).dims(cfg)


# What the harness counted for qwen3-1.7b before families existed (the
# dense GQA formulas of the former ``bench/lib/roofline.py``), as literals.
PREFILL_FLOPS = {1: 3441131520, 17: 48573153280, 2000: 6096748281856}
DECODE_FLOPS = {1: 3441131520, 300: 3509714944, 2047: 3910434816}
CALLS = [
    (("prefill", 0.0, [0, 100, 0], [512, 256, 0], 1),
     "paged_prefill_attention", (43541069824, 903741440)),
    (("prefill", 0.0, [1024], [1024], 1),
     "paged_prefill_attention", (360894693376, 1409286144)),
    (("prefill", 0.0, [7], [1], 1),
     "paged_prefill_attention", (1835008, 2752512)),
    (("decode", 0.0, [10, 300, 2000], [3, 256, 40], 256),
     "paged_decode_attention", (145642291200, 145994612736)),
    (("decode", 0.0, [5], [1], 1),
     "paged_decode_attention", (1376256, 1835008)),
    (("decode", 0.0, [0, 64], [0, 200], 128),
     "paged_decode_attention", (3802136576, 3919577088)),
]


def test_dense_family_counts_as_before():
    fam = spec.family(QWEN)
    d = fam.dims(QWEN)
    assert {p: fam.prefill_model_flops(d, p)
            for p in PREFILL_FLOPS} == PREFILL_FLOPS
    assert {c: fam.decode_model_flops(d, c)
            for c in DECODE_FLOPS} == DECODE_FLOPS
    for call, kernel, want in CALLS:
        assert fam.KERNELS[kernel](d, call) == want, (call, kernel)
        for other in set(fam.KERNELS) - {kernel}:
            assert fam.KERNELS[other](d, call) is None, (call, other)


def test_no_harness_file_names_a_family():
    names = [p.stem for p in (spec.FAMILIES.glob("*.py"))] + [
        p.stem for p in DATA_FAMILIES.glob("*.py")]
    assert "dense_gqa" in names and "dense_untied" in names
    for path in HARNESS:
        text = path.read_text()
        for n in names:
            assert not re.search(rf"\b{n}\b", text), (path, n)


UNTIED = dict(TINY, family="dense_untied",
              config=dict(TINY["config"], tie_word_embeddings=False))


def test_a_family_found_by_name_alone_is_served():
    """The test family's config, through the harness's own serve and
    check at the program's CPU sizes: the program serves an untied head,
    and the family's reference finds it correct, where the dense family's
    reference, which reads the embedding as the head, does not."""
    fam = spec.family(UNTIED, DATA_FAMILIES)
    res = run.serve(UNTIED, fam, TRAFFIC, 2.0, 3.0, SEED, "tiny", 30, False,
                    full=False)
    w = res["cluster"].drivers[0].engine.params
    assert "unembed" in w
    prompts = {r.rid: r.prompt for r in res["reqs"]}
    got = run.check(res["outs"], prompts, fam, res["dims"], SEED, LIMITS, w)
    ok, checks = run.verdict(got, LIMITS)
    assert ok and got["sampled_tokens"] >= 20, checks
    dense = spec.family(TINY)
    tied = run.check(res["outs"], prompts, dense, res["dims"], SEED, LIMITS,
                     w)
    assert not run.verdict(tied, LIMITS)[0]
