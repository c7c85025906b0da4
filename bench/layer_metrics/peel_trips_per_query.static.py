"""Peel while-loop trips per query: the program's
``QueryStats.iterations`` (one query per dispatch in these cells)."""


def read(run):
    trips = [r.iterations for r in run.answered_in_window() if r.iterations is not None]
    return sum(trips) / len(trips) if trips else None
