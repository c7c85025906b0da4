"""Query kind ``ktruss``: the edges of one graph's k-truss, for the ``k``
that the traffic mix's entry gives (``{"workload": "ktruss", "k": 3}``).

Every file of ``bench/workloads/`` has this shape, so that a traffic mix
can name a new query kind by adding its file:

- ``CHECK``: the name of the number compared, beside its limit 0.
- ``to_program(graph, args)``: the program's query for a ``CSRGraph``.
- ``from_program(result)``: what of the program's answer is compared.
- ``expected(n, edges, args, window=None)``: the plain reference's
  answer (``window``: the control's cut support, ``bench/control.py``).
- ``wrong(answer, expected)``: how many parts of the answer differ.
- ``answer_bytes(n, m, args)``: bytes the answer takes to write, for the
  byte floor of ``bench/work.py``.
"""

import numpy as np

from bench import reference as R

CHECK = "edges_wrong"


def to_program(graph, args):
    from repro.api import TrussQuery

    return TrussQuery.ktruss(graph, int(args["k"]))


def from_program(result):
    return np.asarray(result.alive, bool)


def expected(n, edges, args, window=None):
    return R.ktruss_alive(n, edges, int(args["k"]), window=window)


def wrong(answer, expected):
    if getattr(answer, "shape", None) != expected.shape:
        return len(expected)
    return int((answer != expected).sum())


def answer_bytes(n, m, args):
    return 5 * m  # the alive mask and the support of every edge
