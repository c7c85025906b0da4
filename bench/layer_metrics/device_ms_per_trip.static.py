"""Device time of the peel program's executions in the profiler trace,
over the peel trips of the queries answered in the traced window."""


def read(run):
    p = run.profile
    trips = sum(r.iterations or 0 for r in run.answered_in_window())
    if p is None or not p.peel_s or not trips:
        return None
    return 1e3 * p.peel_s / trips
