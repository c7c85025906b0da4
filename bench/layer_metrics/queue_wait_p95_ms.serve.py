"""95th percentile of the wait from a query's due time to the start of
the ``poll()`` that answered it (the harness's own timestamps)."""

from bench.record import p95


def read(run):
    waits = [r.poll_start - r.target for r in run.queries if r.poll_start is not None]
    return 1e3 * p95(waits) if waits else None
