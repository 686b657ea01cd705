"""Share of the requests due in the window that met every stage limit on
the wall clock; a declined, dropped or unfinished request missed."""
from bench.lib.judge import attainment_pct


def read(run):
    return attainment_pct(run.outs)
