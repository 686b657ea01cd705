"""Share of the rows the expert FFN ran over the window that a live token
routed to a held expert filled, in percent: the engine's counters
``moe_routed_units_held`` over ``moe_expert_rows``, as each prefill
group's and decode scan's ``repro.engine.*.post`` span records them.
Dispatch is dropless by padding (every held expert runs capacity = all
the call's tokens), so about top_k / router width of the rows are useful,
less where lanes or chunk tails are padding.  Read in traced runs, where
telemetry is on; None for a program or a model without the counters."""
from bench.lib import program

ROWS, UNITS = "moe_expert_rows", "moe_routed_units_held"


def read(run):
    spans = program.spans(run)
    if spans is None:
        return None
    lo, hi = program.window_ns(run)
    posts = [s for s in spans if program.is_phase(s.name, "post")
             and ROWS in s.attrs and lo <= s.start_ns <= hi]
    rows = sum(s.attrs[ROWS] for s in posts)
    return 100.0 * sum(s.attrs[UNITS] for s in posts) / rows if rows \
        else None
