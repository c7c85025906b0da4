"""Host cost per batch of placing the peel's arguments across the slot
mesh: the mean self time of the program's ``shard`` span
(``repro.exec.peel``, inside ``dispatch``: the packed batch, built on the
default device, re-placed over the mesh's devices) over the batches of the
traced window.  A program with no such span has nothing to read."""

from bench.record import span_self_seconds


def read(run):
    count, total = span_self_seconds(run.spans, "shard")
    return 1e3 * total / count if count else None
