"""Host packing per batch: the mean self time of the program's ``pack``
span (``repro.api.planner``) over the batches of the traced window."""

from bench.record import pack_ms_per_batch as read  # noqa: F401
