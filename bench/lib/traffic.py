"""One general generator for every traffic file.

Lengths follow the paper's Table 4 log-normal fits (mean, std), as
``core/workload.py`` draws them; arrivals are Poisson.  Both are taken
here as a fixed set of requests and gaps: the stratified quantiles of
those distributions, prompt and output lengths paired within a class
the same way for every seed.  The seed only chooses their order and the
prompt tokens.  Every seed thus offers the same work in another order,
so runs on different seeds differ by the system's response and not by
how much was asked.

With ``order_seed`` in the traffic file, the order and the gaps are
drawn from that seed and not from the run's: every run then offers the
same sequence of sizes at the same times, and the run's seed draws only
the prompt tokens.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    due: float               # seconds after the window opens
    cls: str
    prompt: list             # token ids
    out_len: int
    ttft_slowdown: float
    tpot_s: float
    clipped: bool


def lognormal_quantiles(mean: float, std: float, k: int,
                        lo: int = 4) -> np.ndarray:
    """The k stratified quantiles, (i + 0.5) / k, of the log-normal with
    this mean and std (the fit of ``core/workload.LengthDist``), as whole
    tokens, at least ``lo``."""
    s = max(std, 1.0)
    sigma2 = math.log(1.0 + (s / mean) ** 2)
    mu = math.log(mean) - sigma2 / 2.0
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / k) for i in range(k)])
    return np.maximum(np.exp(mu + math.sqrt(sigma2) * z), lo).astype(int)


def class_counts(weights: list[float], n: int) -> list[int]:
    """Split n requests over the classes by weight (largest remainder)."""
    w = np.asarray(weights, float) / sum(weights)
    raw = w * n
    cnt = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - cnt), kind="stable")[:n - cnt.sum()]:
        cnt[i] += 1
    return cnt.tolist()


def clip(prompt: int, out: int, max_context: int) -> tuple[int, int, bool]:
    """Keep prompt + output within the context: the prompt first gives
    way down to ``max_context - out`` but keeps at least 4 tokens, then
    the output."""
    if prompt + out <= max_context:
        return prompt, out, False
    p = max(min(prompt, max_context - out), 4)
    return p, max_context - p, True


def generate(traffic: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> list[Req]:
    """The requests due in a window of ``seconds`` at ``rate`` per
    second, in due order."""
    n = max(1, int(round(rate * seconds)))
    classes = traffic["classes"]
    pairing = np.random.default_rng(0)          # the same for every seed
    by_class = []
    for c, k in zip(classes, class_counts([c["weight"] for c in classes],
                                          n)):
        p = lognormal_quantiles(c["prompt"]["mean"], c["prompt"]["std"], k)
        o = pairing.permutation(lognormal_quantiles(
            c["output"]["mean"], c["output"]["std"], k))
        by_class.append([(c,) + clip(int(a), int(b), traffic["max_context"])
                         for a, b in zip(p, o)])
    rng = np.random.default_rng(seed)
    order_rng = np.random.default_rng(traffic.get("order_seed", seed))
    rows = [r for rows in by_class for r in rows]
    rows = [rows[i] for i in order_rng.permutation(len(rows))]
    gaps = order_rng.permutation(
        [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])
    due = np.cumsum(gaps) - gaps[0]
    out = []
    for rid, ((c, p, o, clipped), t) in enumerate(zip(rows, due)):
        out.append(Req(rid=rid, due=float(t), cls=c["name"],
                       prompt=rng.integers(1, vocab, p).tolist(),
                       out_len=o, ttft_slowdown=c["ttft_slowdown"],
                       tpot_s=c["tpot_s"], clipped=clipped))
    return out
