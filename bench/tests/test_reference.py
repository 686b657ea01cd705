"""The plain reference against the program's own forward at a tiny size.

Both sides are float32; on the CPU XLA multiplies float32 in float32, and
the reference asks for HIGHEST.  They differ only in the order of
reductions (einsum contraction order, the program's fused attention
softmax), so logits agree to a few float32 ulps of their magnitude
(~1): 1e-4 absolute is a hundred times that and a thousand times below
the float8 control's error at this size (~1e-1 on the logits).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import model, reference

TINY = {"source": "tiny", "qk_norm": True, "config": {
    "model_type": "qwen3", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "torch_dtype": "float32"}}
TOL = 1e-4


@pytest.mark.parametrize("qk_norm", [True, False])
def test_reference_matches_model_forward(qk_norm):
    from repro.models.transformer import logits_fn, model_forward
    f = dict(TINY, qk_norm=qk_norm)
    d = model.dims(f)
    pcfg = model.program_config(f, "tiny")
    w = model.make_weights(d, 7)
    toks = jax.random.randint(jax.random.PRNGKey(1), (300,), 1, 512)
    h, _, _ = model_forward(w, pcfg, toks[None])
    want = logits_fn(w, pcfg, h)[0]
    got = jnp.matmul(reference.hidden(d, False, w,
                                      reference._pad(toks.tolist()))[:300],
                     w["embed"]["embed"].T,
                     precision=jax.lax.Precision.HIGHEST)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_weights_have_the_programs_layout():
    from repro.models.transformer import init_params
    d = model.dims(TINY)
    pcfg = model.program_config(TINY, "tiny")
    ours = jax.tree.map(lambda a: (a.shape, a.dtype),
                        model.make_weights(d, 3))
    theirs = jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), pcfg)))
    assert ours == theirs


def test_gaps_of_the_argmax_are_zero_and_of_others_positive():
    d = model.dims(TINY)
    w = model.make_weights(d, 4)
    prompt = list(range(5, 45))
    h = reference.hidden(d, False, w, reference._pad(prompt))
    lg = jnp.matmul(h, w["embed"]["embed"].T,
                    precision=jax.lax.Precision.HIGHEST)
    greedy = [int(jnp.argmax(lg[len(prompt) - 1]))]
    g = reference.served_gaps(d, w, prompt, greedy)
    assert float(g[0]) == 0.0
    worst = [int(jnp.argmin(lg[len(prompt) - 1]))]
    assert float(reference.served_gaps(d, w, prompt, worst)[0]) > 0.1
    assert np.all(np.asarray(reference.control_gaps(d, w, prompt, greedy))
                  >= 0)
