"""Share of the lane-steps that the decode scans of the window ran which
emitted a token: the engine counter ``decode_tokens`` over the window
divided by the ``lane_steps`` (steps x lanes, padded and finished lanes
included) that each ``repro.engine.decode.dispatch`` span records for
its scan as an attribute, in percent.  The run record's counters do not
hold the engine's ``decode_lane_steps``, so the span attribute stands in
for it.  Read in traced runs, where telemetry is on."""
from bench.lib import program


def read(run):
    spans = program.spans(run)
    if spans is None:
        return None
    n = program.lane_steps(spans, *program.window_ns(run))
    return 100.0 * run.counters["decode_tokens"] / n if n else None
