"""Wall milliseconds per DP planner call over the window, from the
program's telemetry (``plan_latency``: a host clock around ``sched.plan``).
Read in traced runs, where telemetry is on."""


def read(run):
    if run.plan is None or run.plan[1] == 0:
        return None
    return 1000.0 * run.plan[0] / run.plan[1]
