"""Share of its roofline that ``paged_prefill_attention`` reached over
the prefill calls of the traced window, at the shapes each call ran."""
from bench.lib.roofline import prefill_call_cost


def read(run):
    return run.kernel_roofline_pct(
        "paged_prefill_attention",
        [prefill_call_cost(run.dims, pos0, chunk)
         for _, _, pos0, chunk, _ in run.calls_in_window("prefill")])
