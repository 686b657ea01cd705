"""90th percentile over every request due in the window of due time to
first token, in ms; a request with no first token by the end of the drain
counts with the time it waited."""
from bench.lib.judge import ttft_p


def read(run):
    return 1000.0 * ttft_p(run.outs, 90, run.t_drain)
