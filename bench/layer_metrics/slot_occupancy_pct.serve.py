"""Mean share of a batch's slots that held a query: the program's
``batch_occupancy`` histogram (members / slots) over the window."""


def read(run):
    count, total = run.histograms.get("batch_occupancy", (0, 0.0))
    return 100.0 * total / count if count else None
