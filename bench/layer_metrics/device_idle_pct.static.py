"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses (profiler trace)."""

from bench.record import device_idle_pct as read  # noqa: F401
