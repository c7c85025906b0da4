"""Query kind ``decompose``: the trussness of every edge of one graph.
The shape of a workload file is set out in ``ktruss.py``."""

import numpy as np

from bench import reference as R

CHECK = "edges_wrong"


def to_program(graph, args):
    from repro.api import TrussQuery

    return TrussQuery.decompose(graph)


def from_program(result):
    return np.asarray(result.trussness, np.int64)


def expected(n, edges, args, window=None):
    return R.trussness(n, edges, window=window)


def wrong(answer, expected):
    if getattr(answer, "shape", None) != expected.shape:
        return len(expected)
    return int((answer != expected).sum())


def answer_bytes(n, m, args):
    return 4 * m  # one trussness per edge
