"""Host readback per batch: the mean self time of the program's
``unpack`` span (``repro.api.planner``: the peel state read back and each
member's answer built) over the batches of the traced window."""

from bench.record import span_self_seconds


def read(run):
    count, total = span_self_seconds(run.spans, "unpack")
    return 1e3 * total / count if count else None
