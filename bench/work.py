"""The least work a query needs, from the graph and the query alone.

These functions never look at the program's padded shapes, so a number
built on them reads the same whatever implements the query.

The byte floor of one query is what any implementation must move through
device memory at least once: read the undirected CSR (row pointers and
both directions of every edge, 4-byte ids) and write the answer.

    read   4 * (2m + n + 1)          bytes
    write  the answer: ``answer_bytes`` of the query kind's file in
           ``bench/workloads/`` (4 * m for trussness, 5 * m for an
           alive mask and support)

Worked example: a Kronecker scale-8 graph with n = 256 vertices and
m = 2150 edges, decomposed, moves 4 * (4300 + 257) + 4 * 2150 = 26828
bytes.  At the 819 GB/s of a TPU v5e (``bench/peaks.json``) that takes
26828 / 819e9 = 32.8 ns; a query whose peel ran on the device for 10 s
reaches 3.3e-7 % of that roofline.
"""

from __future__ import annotations

import json
import os

__all__ = ["csr_bytes", "query_bytes", "peaks"]


def csr_bytes(n: int, m: int) -> int:
    """Bytes of the undirected CSR of ``n`` vertices and ``m`` edges."""
    return 4 * (2 * m + n + 1)


def query_bytes(root: str, query) -> int:
    """Byte floor of one query (``bench.loadgen.Query``) of the benchmark
    at ``root``: its graph's CSR read once, its answer written once."""
    from bench.spec import load_module

    kind = load_module(root, "workloads", query.workload)
    n, m = query.graph.n, query.graph.m
    return csr_bytes(n, m) + kind.answer_bytes(n, m, query.args)


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device missing from
    the table is an error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]
