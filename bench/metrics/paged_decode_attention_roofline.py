"""Share of its roofline that ``paged_decode_attention`` reached over the
decode scans of the traced window, at the shapes each scan ran: every
lane and every step, finished lanes included."""
from bench.lib.roofline import decode_call_cost


def read(run):
    return run.kernel_roofline_pct(
        "paged_decode_attention",
        [decode_call_cost(run.dims, pos0, steps, n)
         for _, _, pos0, steps, n in run.calls_in_window("decode")])
