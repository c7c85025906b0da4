"""The system under test, as the benchmark drives it: the program's
``repro.api.Session`` (a traffic mix's ``"system": "session"``, the default).

Every file of ``bench/systems/`` holds a class ``System(cell, trace=)``
with the methods below; the harness drives nothing else.

Queries go in with ``Session.submit`` and are served by the harness's
server thread calling ``Session.poll``; answers come out through the
future.  The session runs on the program's defaults (the auto backend
rule) with one exception: it retries nothing, falls back to no other
backend and bisects no batch, so a kernel fault shows as a failed query
and not as a quietly different path.  With a cell on several chips the
session shards its slots over a ``slot_mesh`` of that many devices.
"""

from __future__ import annotations

from bench import graphs as G
from bench.spec import load_module


class System:
    def __init__(self, cell, *, trace: bool):
        from repro.api import Session
        from repro.resilience.retry import RetryPolicy

        self.root = cell.root
        kwargs = dict(cell.traffic.get("session", {}))
        if cell.chips > 1:
            from repro.distributed import slot_mesh

            kwargs["mesh"] = slot_mesh(cell.chips)
        strict = RetryPolicy(max_attempts=1, fallback=False, bisect=False)
        self.session = Session(trace=bool(trace), retry=strict, **kwargs)

    # -- driving ------------------------------------------------------- #
    def prepare(self, q):
        """The program's ``TrussQuery`` for a benchmark query."""
        from repro.graphs.csr import CSRGraph

        rowptr, colidx = G.to_csr(q.graph.n, q.graph.edges)
        g = CSRGraph(q.graph.n, rowptr, colidx, name=f"g{q.graph.gid}")
        return load_module(self.root, "workloads", q.workload).to_program(g, q.args)

    def submit(self, payload):
        return self.session.submit(payload)

    def poll(self) -> int:
        return self.session.poll()

    def answer(self, q, result):
        """What the reference is compared with (the workload's file says)."""
        return load_module(self.root, "workloads", q.workload).from_program(result)

    @staticmethod
    def iterations(future) -> int | None:
        return int(future.stats.iterations)

    # -- set-up ---------------------------------------------------------- #
    def bucket_representatives(self, pop) -> list:
        """One population graph per shape bucket the program puts the
        population in: the buckets that set-up has to warm."""
        from repro.api.cache import bucket_for
        from repro.graphs.csr import CSRGraph

        reps: dict = {}
        for g in pop:
            rowptr, colidx = G.to_csr(g.n, g.edges)
            bucket = bucket_for(CSRGraph(g.n, rowptr, colidx), chunk=self.session.chunk)
            reps.setdefault(bucket, g)
        return list(reps.values())

    # -- what the traced run reads ------------------------------------- #
    def histogram(self, name: str) -> tuple[int, float]:
        h = self.session.obs.metrics.histogram(name)
        return (h.count, h.sum) if h is not None else (0, 0.0)

    def spans(self) -> list[dict]:
        return self.session.obs.tracer.events()

    def close(self) -> None:
        self.session = None
