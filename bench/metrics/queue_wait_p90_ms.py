"""90th percentile over the requests due in the window of their
``repro.request.queue`` span, in ms: from ``ClusterFrontend.submit`` to
the start of the first engine batch that prefills the request.  A
request whose first prefill had not started by ``t_end`` (still queued,
dropped or cancelled) counts up to ``t_end``, so where most requests
never reach a prefill in the window (a cell above capacity) the
percentile is mostly that censored value.  Read in traced runs, where
telemetry is on."""
from bench.lib import program
from bench.lib.judge import percentile


def read(run):
    spans = program.spans(run)
    if spans is None:
        return None
    w = program.queue_waits_s(spans, run.outs, *program.window_ns(run))
    return 1000.0 * percentile(w, 90) if w else None
