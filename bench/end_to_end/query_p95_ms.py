"""95th percentile, over every query due in the window, of the time from
its due time to its answer; a failed or missing answer counts as slower
than any other."""

from bench.record import latency_p95_ms as read  # noqa: F401
