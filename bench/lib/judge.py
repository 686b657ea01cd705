"""Client-side arithmetic of a run: what each request saw on the host's
wall clock, the SLO judge, percentiles and rates.

Every time is a ``time.perf_counter`` reading taken by the harness; each
request is timed from its due time, so a stall of the server or of the
generator counts against every request that waits for it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Outcome:
    """One request as the client saw it (absolute perf_counter times)."""
    rid: int
    cls: str
    due: float
    prompt_len: int
    out_len: int
    ttft_limit: float        # seconds
    tpot_limit: float        # seconds per token
    submitted: Optional[float] = None
    times: list = dataclasses.field(default_factory=list)   # (t, n_tokens)
    tokens: list = dataclasses.field(default_factory=list)
    dropped: bool = False

    @property
    def first(self) -> Optional[float]:
        return self.times[0][0] if self.times else None

    @property
    def last(self) -> Optional[float]:
        return self.times[-1][0] if self.times else None

    @property
    def finished(self) -> bool:
        return len(self.tokens) >= self.out_len

    def ttft(self, end: float) -> float:
        """Due to first token; a request still waiting at ``end`` counts
        with the time it has waited (a lower bound)."""
        return (self.first if self.first is not None else end) - self.due

    def tpot(self) -> Optional[float]:
        """Mean seconds per output token after the first."""
        if len(self.tokens) < 2:
            return None
        return (self.last - self.first) / (len(self.tokens) - 1)

    def met(self) -> bool:
        """Every stage limit met on the wall clock: finished, TTFT within
        its limit, mean TPOT within its tier."""
        if self.dropped or not self.finished:
            return False
        if self.ttft(self.first) > self.ttft_limit:
            return False
        t = self.tpot()
        return t is None or t <= self.tpot_limit


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default) of a non-empty
    sample."""
    return float(np.percentile(np.asarray(values, float), q))


def ttft_p(outs: list[Outcome], q: float, end: float) -> float:
    return percentile([o.ttft(end) for o in outs], q)


def tpot_p(outs: list[Outcome], q: float) -> Optional[float]:
    v = [t for t in (o.tpot() for o in outs) if t is not None]
    return percentile(v, q) if v else None


def attainment_pct(outs: list[Outcome]) -> float:
    return 100.0 * sum(o.met() for o in outs) / len(outs)


def tokens_in(outs: list[Outcome], t0: float, t1: float) -> tuple[int, int]:
    """(prompt tokens whose prefill completed, output tokens emitted)
    inside [t0, t1]: a prompt counts once, when its first token arrives;
    recompute after a preemption emits nothing and counts nothing."""
    prompt = out = 0
    for o in outs:
        if o.first is not None and t0 <= o.first <= t1:
            prompt += o.prompt_len
        out += sum(n for t, n in o.times if t0 <= t <= t1)
    return prompt, out


def tokens_per_s(outs: list[Outcome], t0: float, t1: float) -> float:
    p, o = tokens_in(outs, t0, t1)
    return (p + o) / (t1 - t0)
