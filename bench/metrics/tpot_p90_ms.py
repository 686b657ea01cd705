"""90th percentile over the requests due in the window of their mean
time per output token after the first, in ms."""
from bench.lib.judge import tpot_p


def read(run):
    v = tpot_p(run.outs, 90)
    return None if v is None else 1000.0 * v
