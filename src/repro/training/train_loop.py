"""Training loop: loss, train_step (pjit-able), and the host driver."""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.transformer import init_params, logits_fn, model_forward
from repro.training.optimizer import (AdamWConfig, adamw_update,
                                      init_opt_state)


def lm_loss(params, cfg: ModelConfig, tokens, labels, *, enc_states=None,
            moe_cf=None, aux_coef: float = 0.01):
    h, _, terms = model_forward(params, cfg, tokens, enc_states=enc_states,
                                moe_cf=moe_cf)
    aux = terms["aux_loss"]
    logits = logits_fn(params, cfg, h).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll) + aux_coef * aux
    return loss, {"nll": jnp.mean(nll), "aux": aux}


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    moe_cf=None, has_enc: bool = False):
    """Returns train_step(params, opt_state, batch) -> (params, state, metrics).
    Pure function of its inputs: jit/pjit it at the call site with the
    desired shardings (launch/train.py does this for the production mesh)."""

    def train_step(params, opt_state, batch):
        enc = batch.get("enc_states") if has_enc else None
        (loss, parts), grads = jax.value_and_grad(lm_loss, has_aux=True)(
            params, cfg, batch["tokens"], batch["labels"],
            enc_states=enc, moe_cf=moe_cf)
        params, opt_state, om = adamw_update(opt_cfg, params, grads,
                                             opt_state)
        metrics = {"loss": loss, **parts, **om}
        return params, opt_state, metrics

    return train_step


@dataclasses.dataclass
class TrainResult:
    losses: list
    steps: int
    wallclock: float


def train(cfg: ModelConfig, steps: int = 100, batch: int = 8,
          seq_len: int = 128, seed: int = 0,
          opt_cfg: Optional[AdamWConfig] = None,
          log_every: int = 10, checkpoint_dir: Optional[str] = None,
          checkpoint_every: int = 0) -> TrainResult:
    """Single-host training driver (CPU example / smoke scale)."""
    from repro.training import data as data_mod
    from repro.training.checkpoint import save_checkpoint

    opt_cfg = opt_cfg or AdamWConfig(total_steps=steps,
                                     warmup_steps=max(steps // 20, 5))
    key = jax.random.PRNGKey(seed)
    params = init_params(key, cfg)
    opt_state = init_opt_state(params)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg))
    it = data_mod.batches(cfg.vocab, batch, seq_len, seed)
    losses = []
    t0 = time.time()
    for step in range(steps):
        b = next(it)
        params, opt_state, m = step_fn(params, opt_state,
                                       {k: jnp.asarray(v)
                                        for k, v in b.items()})
        if step % log_every == 0 or step == steps - 1:
            losses.append(float(m["loss"]))
        if checkpoint_dir and checkpoint_every and (
                step + 1) % checkpoint_every == 0:
            save_checkpoint(checkpoint_dir, step + 1, params, opt_state)
    return TrainResult(losses=losses, steps=steps,
                       wallclock=time.time() - t0)
