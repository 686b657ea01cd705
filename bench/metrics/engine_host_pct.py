"""Share of the whole window (``t0`` to ``t_end``) spent in
``repro.driver.execute`` spans outside their engine ``*.dispatch`` and
``*.wait`` spans: the host work of the engine itself (page reservation,
copy-on-write, input preparation, bookkeeping).  Read in traced runs,
where telemetry is on."""
from bench.lib import program


def read(run):
    spans = program.spans(run)
    if spans is None:
        return None
    return program.engine_host_pct(spans, *program.window_ns(run))
