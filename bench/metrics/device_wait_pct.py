"""Share of the whole window (``t0`` to ``t_end``) in which the host was
blocked on a device result: the union of the program's
``repro.engine.*.wait`` spans, on the host's clock.  It says the host
waited, not that the device was busy meanwhile: device busy and idle
time come from the profiler's trace (``device_idle_pct``).  Lower means
the host spends less of the window stalled.  Read in traced runs, where
telemetry is on, over the whole window."""
from bench.lib import program


def read(run):
    spans = program.spans(run)
    if spans is None:
        return None
    return program.device_wait_pct(spans, *program.window_ns(run))
