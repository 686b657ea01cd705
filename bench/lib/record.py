"""What one run hands to the metric readers (``bench/metrics/*.py``)."""
from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Hashable, Optional

from bench.lib import roofline
from bench.lib.judge import Outcome


@dataclasses.dataclass
class Run:
    outs: list[Outcome]      # every request due in the window
    t0: float                # window opens (perf_counter)
    t_end: float             # window closes: end of the step in flight
    t_drain: float           # end of the drain (== t_end without one)
    setup_s: float
    counters: dict           # engine counters over the window
    plan: Optional[tuple]    # (planner wall s, calls) over the window
    trace: Optional[dict]    # bench.lib.trace.reduce of the window
    family: ModuleType       # bench/families/<family>.py of the config
    dims: Hashable           # its sizes, ``family.dims(cfg_file)``
    peaks: dict
    # (kind, perf_counter, pos0, steps or chunk lengths, n_steps) of each
    # kernel-bearing engine call, in traced runs
    calls: list = dataclasses.field(default_factory=list)
    t_trace: Optional[float] = None   # the trace starts (traced runs)

    @property
    def traced_from(self) -> float:
        return self.t0 if self.t_trace is None else self.t_trace

    def prompts_done(self) -> list[int]:
        """Prompt lengths whose first token arrived inside the traced
        window."""
        return [o.prompt_len for o in self.outs if o.first is not None
                and self.traced_from <= o.first <= self.t_end]

    def decode_contexts(self) -> list[int]:
        """For each decoded token emitted inside the traced window (every
        output token after the first), the keys its query attended."""
        ctx = []
        for o in self.outs:
            k = 0
            for t, n in o.times:
                if self.traced_from <= t <= self.t_end:
                    ctx += [o.prompt_len + i for i in range(max(k, 1), k + n)]
                k += n
        return ctx

    def kernel_roofline_pct(self, kernel: str) -> Optional[float]:
        """Least time of the work the family counts for ``kernel`` in the
        calls of the traced window over the kernel's device time, in
        percent; None without a trace, for a kernel the family does not
        name, or without the kernel's calls or time in the window."""
        cost = self.family.KERNELS.get(kernel)
        if self.trace is None or cost is None:
            return None
        costs = [c for c in (cost(self.dims, call) for call in self.calls
                             if self.traced_from <= call[1] <= self.t_end)
                 if c is not None]
        if not costs:
            return None
        dev = self.trace["op_s"].get(kernel, 0.0)
        if dev <= 0:
            return None
        least = sum(roofline.roofline_seconds(o, b, self.peaks)[0]
                    for o, b in costs)
        return 100.0 * least / dev

    def model_flops(self) -> int:
        f, d = self.family, self.dims
        return (sum(f.prefill_model_flops(d, p)
                    for p in self.prompts_done())
                + sum(f.decode_model_flops(d, c)
                      for c in self.decode_contexts()))
