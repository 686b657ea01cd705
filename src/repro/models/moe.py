"""Mixture-of-Experts FFN: top-k routing, shared experts, load balancing.

Default dispatch is capacity-based scatter/gather (GShard/Switch style):
each (token, slot) unit is scattered into a per-expert buffer of capacity
C = ceil(cf * k * T / E), experts run dense matmuls over their buffers, and
outputs are gathered back with the router combine weights.  This keeps
compiled FLOPs proportional to *active* experts (top-k), shards with expert
parallelism (experts axis on the "model" mesh axis), and has fully static
shapes.  Tokens overflowing an expert's capacity are dropped (standard
Switch behaviour) — the load-balance aux loss keeps this rare.

The layer holds a share of the experts, the first ``MoEConfig.n_held``
(all of them by default): the router spans all E experts, and only the
units routed to held experts are scattered into the (held, C, D) buffer
and computed.  The result is that share's part of the layer plus the
shared experts, which is what one rank of an expert-parallel deployment
passes on.  Under the engine's ``shard_map`` the held experts split
evenly over the shards (shard i holds n_held/n experts from
i * n_held/n) and the shards' parts are ``psum``'d — the same code, with
the exchange.

``mode="dense"`` computes every held expert on every token (exact, no
drops) — used as the small-shape reference oracle in tests.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    ks = jax.random.split(key, 7)
    s_in, s_out = d ** -0.5, f ** -0.5
    E = m.n_held                       # experts held here
    p = {
        "router": jax.random.normal(ks[0], (d, m.n_experts), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (E, d, f), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (E, d, f), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (E, f, d), dtype) * s_out,
    }
    if m.n_shared:
        p["shared_gate"] = jax.random.normal(
            ks[4], (d, m.n_shared * f), dtype) * s_in
        p["shared_up"] = jax.random.normal(
            ks[5], (d, m.n_shared * f), dtype) * s_in
        p["shared_down"] = jax.random.normal(
            ks[6], (m.n_shared * f, d), dtype) * s_out
    return p


def capacity(cfg: ModelConfig, tokens: int, capacity_factor=None) -> int:
    """Rows per expert buffer for a call over ``tokens`` tokens:
    ceil(cf * k * T / E) over the router's E (cf None -> 2.0).  Any
    cf >= E/k gives C >= T: dispatch is dropless."""
    m = cfg.moe
    cf = 2.0 if capacity_factor is None else capacity_factor
    return max(1, math.ceil(cf * m.top_k * tokens / m.n_experts))


def held_range(m, shard=None):
    """(first expert held, number held) by this layer; inside the
    engine's expert-parallel ``shard_map`` each shard holds an even slice
    of the held range."""
    if shard is not None and shard.experts:
        n = m.n_held // shard.size
        return jax.lax.axis_index(shard.axis) * n, n
    return 0, m.n_held


def _route(p, xt, m):
    logits = xt.astype(jnp.float32) @ p["router"]            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, m.top_k)               # (T, k)
    if m.norm_topk_prob:
        topv = topv / jnp.clip(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
    return probs, topv, topi


def _expert_ffn(p, h_in):
    """h_in: (E, C, D) -> (E, C, D)."""
    g = jnp.einsum("ecd,edf->ecf", h_in, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h_in, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])


def moe_forward(p, x, cfg: ModelConfig, mode: str = "dispatch",
                capacity_factor=None, shard=None, live=None):
    """Returns (out, aux) where aux carries load-balance terms and
    ``units_held``: the (token, expert) units routed to (and kept by)
    the held experts, over the tokens ``live`` (B, S) marks (all when
    None).

    capacity_factor None -> 2.0 (training/dry-run default).  Any value
    >= n_experts/top_k makes dispatch provably dropless (C >= T), the
    exact-inference setting used by the serving engine and tests.

    shard: serving ShardPlan inside shard_map (expert parallel).  The
    router is replicated so routing/keep decisions are globally exact;
    each shard scatters only the units routed to ITS slice of the held
    experts (others scatter nothing via an out-of-bounds index + drop),
    and the per-token outputs are ``psum``'d — exactly one shard
    contributes a non-zero value per unit, so with top-k <= 2 the combined
    sum is bit-identical to the single-device scatter-add."""
    m = cfg.moe
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    probs, topv, topi = _route(p, xt, m)
    live_t = (jnp.ones((T,), bool) if live is None
              else live.reshape(-1).astype(bool))

    if mode == "dense":
        n = m.n_held
        combine = jnp.zeros_like(probs)
        combine = jax.vmap(lambda c, i, v: c.at[i].set(v))(combine, topi, topv)
        combine = combine[:, :n].astype(x.dtype)
        h_g = jnp.einsum("td,edf->etf", xt, p["w_gate"])
        h_u = jnp.einsum("td,edf->etf", xt, p["w_up"])
        h = jax.nn.silu(h_g) * h_u
        eo = jnp.einsum("etf,efd->etd", h, p["w_down"])
        out = jnp.einsum("etd,te->td", eo, combine)
        in_held = topi < n
        units_held = jnp.sum(in_held & live_t[:, None], dtype=jnp.int32)
    else:
        E, k = m.n_experts, m.top_k
        C = capacity(cfg, T, capacity_factor)
        e_u = topi.reshape(-1)                               # (T*k,)
        w_u = topv.reshape(-1).astype(x.dtype)
        t_u = jnp.repeat(jnp.arange(T), k)
        # position of each unit within its expert queue
        oh = jax.nn.one_hot(e_u, E, dtype=jnp.int32)         # (Tk, E)
        pos_u = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(e_u.shape[0]), e_u]
        keep = pos_u < C
        pos_c = jnp.where(keep, pos_u, C - 1)
        start, n = held_range(m, shard)
        e_loc = e_u - start
        held = keep & (e_loc >= 0) & (e_loc < n)
        e_scat = jnp.where(held, e_loc, n)         # OOB index -> dropped
        vals = xt[t_u] * held[:, None].astype(x.dtype)
        buf = jnp.zeros((n, C, D), x.dtype).at[e_scat, pos_c].add(
            vals, mode="drop")
        eo = _expert_ffn(p, buf)                   # (held, C, D)
        unit_out = (eo[jnp.clip(e_loc, 0, n - 1), pos_c]
                    * (w_u * held.astype(x.dtype))[:, None])
        out = jnp.zeros((T, D), x.dtype).at[t_u].add(unit_out, mode="drop")
        units_held = jnp.sum(held & live_t[t_u], dtype=jnp.int32)
        if shard is not None and shard.experts:
            out = jax.lax.psum(out, shard.axis)
            units_held = jax.lax.psum(units_held, shard.axis)

    if m.n_shared:
        sh = jax.nn.silu(xt @ p["shared_gate"]) * (xt @ p["shared_up"])
        out = out + sh @ p["shared_down"]

    density = jnp.mean(
        jax.nn.one_hot(topi, m.n_experts, dtype=jnp.float32).sum(1), axis=0)
    router_mean = jnp.mean(probs, axis=0)
    aux_loss = m.n_experts * jnp.sum(density / m.top_k * router_mean)
    return out.reshape(B, S, D), {"aux_loss": aux_loss,
                                  "expert_density": density,
                                  "units_held": units_held}
