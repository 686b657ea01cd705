"""A model family for the tests only: the dense GQA block with an output
head of its own (untied embeddings), which the dense family refuses.  It
lives outside ``bench/families``, and no harness file names it: the test
that serves it shows that a configuration's ``"family"`` key alone brings
its sizes, weights, program config, reference and counts to the harness.
"""
import dataclasses

import jax
import jax.numpy as jnp

from bench.lib import spec

base = spec.family({"family": "dense_gqa"})
Dims = base.Dims
KERNELS = base.KERNELS
prefill_model_flops = base.prefill_model_flops
decode_model_flops = base.decode_model_flops


def _as_tied(cfg_file: dict) -> dict:
    m = cfg_file["config"]
    if m.get("tie_word_embeddings", True):
        raise ValueError("dense_untied models untied embeddings only")
    return dict(cfg_file, config=dict(m, tie_word_embeddings=True))


def dims(cfg_file: dict) -> Dims:
    return base.dims(_as_tied(cfg_file))


def program_config(cfg_file: dict, name: str):
    return dataclasses.replace(base.program_config(_as_tied(cfg_file), name),
                               tie_embeddings=False)


def make_weights(d: Dims, seed: int, dtype=jnp.float32):
    w = base.make_weights(d, seed, dtype)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    w["unembed"] = (jax.random.normal(key, (d.d_model, d.vocab), jnp.float32)
                    * d.d_model ** -0.5).astype(dtype)
    return w


def _gaps(d: Dims, w32, prompt: list, served: list, control: bool):
    seq = list(prompt) + list(served[:-1])
    toks = base._pad(seq)
    head = w32["unembed"].T
    if control:
        choose = base._control_argmax(base.hidden(d, True, w32, toks), head)
    else:
        choose = jnp.zeros_like(toks).at[len(prompt) - 1:len(seq)].set(
            jnp.asarray(served, jnp.int32))
    g = base._gaps(base.hidden(d, False, w32, toks), head, choose)
    return g[len(prompt) - 1:len(seq)]


def served_gaps(d: Dims, w32, prompt: list, served: list):
    return _gaps(d, w32, prompt, served, False)


def control_gaps(d: Dims, w32, prompt: list, served: list):
    return _gaps(d, w32, prompt, served, True)
