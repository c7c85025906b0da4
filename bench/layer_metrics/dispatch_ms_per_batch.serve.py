"""Host launch cost per batch: the mean self time of the program's
``dispatch`` span (``repro.exec.peel``: the peel's arguments built and
placed, then its asynchronous launch) over the batches of the traced
window."""

from bench.record import span_self_seconds


def read(run):
    count, total = span_self_seconds(run.spans, "dispatch")
    return 1e3 * total / count if count else None
