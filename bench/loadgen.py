"""The one traffic generator: a cell's queries from its configuration,
its traffic file and ``--seed``.

A configuration (``bench/configs/<name>.json``) fixes the graphs that are
served: a family (``bench/families/<family>.py``) with its parameters,
and a population of ``size`` graphs taken from generator seeds
``first_seed``, ``first_seed + 1``, ... that pass the shape filters
(``max_degree`` and ``edges``, each an inclusive ``[low, high]``).  The
population does not depend on ``--seed``.

A traffic mix (``bench/traffic/<name>.json``) is data, and says how
queries reach the system:

- ``system`` (optional): the system under test, ``bench/systems/<name>.py``
  (default ``session``, the program's ``Session``).
- ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  query when its last one is answered) or ``"open"`` (queries due on a
  schedule whatever the system does).
- ``rate_qps`` (open): the mean arrival rate.  ``phases`` (optional):
  ``[{"seconds": s, "rate": r}, ...]``, cycled from the window's start,
  each phase arriving at ``r * rate_qps`` (bursts: a phase with ``r`` 0
  after one above 1).  Without it the rate is constant.
- ``mix``: ``[{"workload": <kind>, "weight": w, <args>}]``; ``<kind>``
  names a file of ``bench/workloads/``, and the other keys (``"k"``) are
  the query's arguments.
- ``graphs``: how a query's graph is drawn from the population.
  ``{"pick": "cycle"}`` walks it in an order the seed shuffles (every
  member equally often); ``{"pick": "weights", "weights": [...]}`` gives
  each member a popularity in proportion to its weight (a Zipf law, say).
  ``"relabel": true`` renames the vertices of every query's graph by a
  random permutation, so no two queries repeat and no cache of answers
  can stand in for work, while the work per graph stays the same.
- ``session``: keyword arguments for the system (``max_batch``).
- ``warmup``: the query (``workload`` and its arguments) that warms each
  shape bucket during set-up.
- ``trace_seconds`` (optional): the window of a ``--trace 1`` run, when
  shorter than ``--seconds``.

Every seed offers the same work.  A closed loop deals workloads in
exact proportion, 64 queries at a time, in a seeded order.  An open loop
has as many queries as its rate gives over the window, with the same
schedule for every seed: due times from one draw of a Poisson process
conditioned on its count, and (workload, graph) pairs in exact
proportion to the mix and to the popularity, in one shuffled order,
both from a fixed stream.  Its seed changes the inputs and not the work:
with ``"relabel": true`` each query's graph gets vertex labels of its
own.  (Shuffling the order by the seed moved a p95 latency by 12-15%
between seeds, more than between runs of one seed.)
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import graphs as G
from .spec import load_module

__all__ = ["Graph", "Query", "arrivals", "population", "Traffic"]


@dataclasses.dataclass(frozen=True)
class Graph:
    gid: int  # index in the population
    n: int
    edges: np.ndarray  # canonical (m, 2), see bench/graphs.py

    @property
    def m(self) -> int:
        return int(len(self.edges))


@dataclasses.dataclass
class Query:
    index: int
    workload: str  # a file of bench/workloads/
    args: dict  # its arguments from the mix ("k")
    graph: Graph
    due: float | None = None  # open loop: seconds after the window opens


def _in(value: int, bounds) -> bool:
    return bounds is None or bounds[0] <= value <= bounds[1]


def population(root: str, config: dict) -> list[Graph]:
    """The configuration's population of graphs, in generator-seed order."""
    make = load_module(root, "families", config["family"]).generate
    spec = config["population"]
    size, seed = int(spec["size"]), int(spec.get("first_seed", 0))
    out: list[Graph] = []
    for tried in range(64 * size):
        n, edges = make(**config["params"], seed=seed + tried)
        if _in(G.max_degree(n, edges), spec.get("max_degree")) and _in(
            len(edges), spec.get("edges")
        ):
            out.append(Graph(len(out), n, edges))
            if len(out) == size:
                return out
    raise ValueError(f"{config['name']}: only {len(out)} of {size} graphs pass the filters")


def _apportion(shares: np.ndarray, count: int) -> np.ndarray:
    """Whole counts summing to ``count`` in proportion to ``shares``
    (largest remainders, ties to the lower index)."""
    exact = np.asarray(shares, np.float64) * count
    counts = np.floor(exact).astype(int)
    rest = np.argsort(-(exact - counts), kind="stable")[: count - int(counts.sum())]
    counts[rest] += 1
    return counts


def _args(entry: dict) -> dict:
    return {k: v for k, v in entry.items() if k not in ("workload", "weight")}


def arrivals(rate_qps: float, seconds: float, phases, rng: np.random.Generator) -> np.ndarray:
    """Sorted due times in ``[0, seconds)`` of a Poisson process whose
    rate is ``rate_qps`` times the rate of the phase in force, conditioned
    on its expected count (rounded)."""
    phases = phases or [{"seconds": seconds, "rate": 1.0}]
    segments = []  # (start, end, rate) over the window
    t, i = 0.0, 0
    while t < seconds:
        ph = phases[i % len(phases)]
        end = min(seconds, t + float(ph["seconds"]))
        segments.append((t, end, rate_qps * float(ph["rate"])))
        t, i = end, i + 1
    mass = np.cumsum([(b - a) * r for a, b, r in segments])
    count = int(round(mass[-1]))
    u = np.sort(rng.uniform(0.0, mass[-1], count))
    seg = np.searchsorted(mass, u, side="right")
    starts = np.array([a for a, _, _ in segments])
    rates = np.array([r for _, _, r in segments])
    before = np.concatenate([[0.0], mass[:-1]])
    return starts[seg] + (u - before[seg]) / rates[seg]


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # SeedSequence takes any non-negative integer, however large.
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


class Traffic:
    """The queries of one run: ``query(i)`` for a closed loop,
    ``schedule()`` for an open one; the same seed gives the same queries."""

    _STREAM_ORDER, _STREAM_MIX, _STREAM_ARRIVALS, _STREAM_QUERY, _STREAM_WARM = range(5)
    _FIXED = 0  # the seed of what every run shares: arrivals, pairing

    def __init__(self, traffic: dict, pop: list[Graph], seed: int, seconds: float):
        self.spec = traffic
        self.pop = pop
        self.seed = int(seed)
        self.seconds = float(seconds)
        pick = traffic["graphs"]
        self.relabel = bool(pick.get("relabel", False))
        self._order = _rng(seed, self._STREAM_ORDER).permutation(len(pop))
        if pick["pick"] == "cycle":
            self._popularity = None
        elif pick["pick"] == "weights":
            p = np.asarray(pick["weights"], np.float64)
            if p.shape != (len(pop),):
                raise ValueError(f"{len(p)} weights for a population of {len(pop)}")
            self._popularity = p / p.sum()
        else:
            raise ValueError(f"unknown graph pick {pick['pick']!r}")
        weights = np.array([float(w.get("weight", 1)) for w in traffic["mix"]])
        self._shares = weights / weights.sum()

    @property
    def loop(self) -> str:
        return self.spec["loop"]

    @property
    def clients(self) -> int:
        return int(self.spec.get("clients", 1))

    def _deal(self, count: int, block: int) -> np.ndarray:
        """Mix indices for queries ``[block*count, (block+1)*count)``:
        each workload in exact proportion, in a seeded order."""
        kinds = np.repeat(np.arange(len(self._shares)), _apportion(self._shares, count))
        return _rng(self.seed, self._STREAM_MIX, block).permutation(kinds)

    def _graph_for(self, i: int, rng: np.random.Generator, gid: int | None = None) -> Graph:
        if gid is not None:
            base = self.pop[gid]
        elif self._popularity is None:
            base = self.pop[self._order[i % len(self.pop)]]
        else:
            base = self.pop[rng.choice(len(self.pop), p=self._popularity)]
        if not self.relabel:
            return base
        perm = rng.permutation(base.n)
        return Graph(base.gid, base.n, G.relabel(base.n, base.edges, perm))

    def _make(self, i: int, kind: int, due: float | None, gid: int | None = None) -> Query:
        w = self.spec["mix"][kind]
        rng = _rng(self.seed, self._STREAM_QUERY, i)
        return Query(i, w["workload"], _args(w), self._graph_for(i, rng, gid), due)

    def query(self, i: int) -> Query:
        """Closed loop: the ``i``-th query sent (dealt in blocks of 64)."""
        kind = self._deal(64, i // 64)[i % 64]
        return self._make(i, int(kind), None)

    def schedule(self) -> list[Query]:
        """Open loop: every query due in the window, in due order."""
        due = arrivals(float(self.spec["rate_qps"]), self.seconds, self.spec.get("phases"),
                       _rng(self._FIXED, self._STREAM_ARRIVALS))
        count = len(due)
        popularity = self._popularity
        if popularity is None:
            popularity = np.full(len(self.pop), 1 / len(self.pop))
        gids = np.repeat(np.arange(len(self.pop)), _apportion(popularity, count))
        kinds = np.repeat(np.arange(len(self._shares)), _apportion(self._shares, count))
        kinds = _rng(self._FIXED, self._STREAM_MIX).permutation(kinds)  # the fixed pairing
        order = _rng(self._FIXED, self._STREAM_ORDER).permutation(count)
        return [
            self._make(i, int(kinds[j]), float(due[i]), int(gids[j]))
            for i, j in enumerate(order)
        ]

    def warmup(self, graph: Graph) -> Query:
        """The set-up query for ``graph``'s shape bucket (a relabelled
        copy when the traffic relabels, so it repeats no timed query)."""
        w = self.spec["warmup"]
        rng = _rng(self.seed, self._STREAM_WARM, graph.gid)
        if self.relabel:
            graph = Graph(graph.gid, graph.n, G.relabel(graph.n, graph.edges, rng.permutation(graph.n)))
        return Query(-1, w["workload"], _args(w), graph, None)
