"""Share of its roofline that ``mla_paged_prefill_attention`` reached over
the prefill calls of the traced window, at the shapes each call ran (the
family's count)."""


def read(run):
    return run.kernel_roofline_pct("mla_paged_prefill_attention")
