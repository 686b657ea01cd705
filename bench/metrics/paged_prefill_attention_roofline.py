"""Share of its roofline that ``paged_prefill_attention`` reached over
the prefill calls of the traced window, at the shapes each call ran (the
family's count)."""


def read(run):
    return run.kernel_roofline_pct("paged_prefill_attention")
