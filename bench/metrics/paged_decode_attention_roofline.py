"""Share of its roofline that ``paged_decode_attention`` reached over the
decode scans of the traced window, at the shapes each scan ran: every
lane and every step, finished lanes included (the family's count)."""


def read(run):
    return run.kernel_roofline_pct("paged_decode_attention")
