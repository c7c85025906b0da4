"""Shape-bucket canonicalization + compile cache for the query API.

XLA (and Pallas) executables are specialized to static shapes, so a naive
server recompiles the fixed-point program for every distinct graph — tens
of milliseconds to seconds per request.  Canonicalizing every incoming
graph to power-of-two ``(n_pad, nnz_pad, window)`` buckets collapses the
shape space: one executable per bucket serves every request (and every
micro-batch) that lands in it.  GraphBLAST makes the same bet — reusable
kernels behind a stable API beat per-input specialization.

The compiled artifact is a *problem-polymorphic* on-device peel: the
executor takes the ``FineProblem`` pytree as an argument, so any
same-bucket problem — including a block-diagonal batch of them — reuses
the program.  Thresholds are per-slot state advanced inside the compiled
loop, which lets one dispatch run different k values *and* mixed
ktruss/kmax/decompose/stream workloads to completion for every member of
a packed batch (``repro.exec.peel``).  Cache keys are
``(bucket, slots, variant)``: the slot count scales the packed shapes and
the variant captures everything else that specializes the executable —
the registry backend key, dataflow mode, and mesh placement.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Hashable, NamedTuple

import numpy as np

from ..errors import CompileError, TrussError
from ..graphs.csr import CSRGraph
from ..obs import MetricsRegistry

__all__ = [
    "Bucket",
    "bucket_for",
    "bucket_str",
    "build_peel",
    "CacheStats",
    "CompileCache",
    "enable_persistent_cache",
    "persistent_cache_dir",
]


# <checkout>/.jax_cache: src/repro/api/cache.py is four levels below the
# checkout root.  A fixed path, because the directory is part of what a
# persistent-cache entry is found by.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ),
    ".jax_cache",
)


def persistent_cache_dir() -> str:
    """Where :func:`enable_persistent_cache` puts the cache by default:
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else the fixed
    ``.jax_cache`` directory at the root of the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE_DIR


def enable_persistent_cache(cache_dir: str | None = None) -> str:
    """Point XLA's persistent compilation cache at ``cache_dir`` (default
    :func:`persistent_cache_dir`) and return the directory used.

    The in-process :class:`CompileCache` dedupes executables per
    ``(bucket, slots, variant)`` key but dies with the process; wiring
    JAX's persistent cache underneath means a restarted server's *first*
    compile per bucket is a disk hit instead of a cold XLA compile
    (skipped warmup).  Process-wide by necessity — the JAX cache is
    global — and idempotent.  ``Session(cache_dir=...)`` passes an
    explicit directory, which overrides the default.

    The entry-size/compile-time floors are dropped to 0 so even the small
    CPU-test executables round-trip (JAX's defaults skip sub-second
    compiles, which would make a warm restart silently cold).
    """
    import jax

    cache_dir = str(cache_dir) if cache_dir is not None else persistent_cache_dir()
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


class Bucket(NamedTuple):
    """Canonical power-of-two shape class of one graph slot.

    A graph in this bucket is packed to ``n_pad`` vertices, ``nnz_pad``
    directed nonzeros (twice that undirected) and intersected with windows
    of width ``window``.  Batches of B same-bucket graphs use the scaled
    shapes ``(B * n_pad, B * nnz_pad)``; the executor cache key is
    ``(bucket, slots, variant)``.
    """

    n_pad: int
    nnz_pad: int
    window: int


def _next_pow2(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def bucket_for(g: CSRGraph, *, chunk: int = 256, min_window: int = 8) -> Bucket:
    """Canonical shape bucket of one graph.

    The window is sized to the max *undirected* degree so one bucket is
    valid for every backend (eager needs out-degree, owner/pallas need
    the symmetric degree).
    """
    deg = g.degrees()
    indeg = np.bincount(g.colidx, minlength=g.n + 1)
    und_max = int((deg + indeg).max(initial=0))
    return Bucket(
        n_pad=_next_pow2(max(g.n, 1)),
        nnz_pad=_next_pow2(max(g.nnz, chunk)),
        window=_next_pow2(max(min_window, und_max)),
    )


def bucket_str(bucket: Bucket) -> str:
    """Canonical string label of one bucket (``n{..}-nnz{..}-w{..}``).

    The one spelling shared by metrics labels, planner stats rows, and the
    serving tier's affinity keys — the router matches these against a
    replica's ``compiled_buckets``, so every producer must agree."""
    return f"n{bucket.n_pad}-nnz{bucket.nnz_pad}-w{bucket.window}"


def build_peel(
    *,
    mode: str = "eager",
    backend: str = "xla",
    window: int,
    chunk: int = 256,
    max_iters: int | None = None,
    mesh=None,
):
    """Compile-cachable on-device peel for one shape bucket.

    Legacy bucket-config adapter over the exec layer (the registry's
    :meth:`repro.api.BackendSpec.make_executor` is the first-class path);
    kept so existing ``repro.service`` imports keep working.
    """
    from ..exec.peel import PeelExecutor

    return PeelExecutor(
        mode=mode,
        backend=backend,
        window=window,
        chunk=chunk,
        max_iters=max_iters,
        mesh=mesh,
    )


class CacheStats:
    """Compile-cache hit/miss counters — a view over the metrics registry.

    The counters live in a :class:`repro.obs.MetricsRegistry`
    (``cache_compiles`` / ``cache_hits``), so they show up in
    ``obs.metrics_snapshot()`` and the Prometheus exposition alongside
    every other instrument; ``compiles`` / ``hits`` / ``hit_rate`` keep
    their historical read surface, and :meth:`snapshot` (alias
    :meth:`row`) keeps the historical key set.
    """

    def __init__(self, metrics: "MetricsRegistry | None" = None):
        if metrics is None:
            metrics = MetricsRegistry()  # standalone cache: private series
        self.metrics = metrics

    def record_compile(self, bucket: "Bucket | None" = None) -> None:
        self.metrics.inc("cache_compiles")
        if bucket is not None:
            self.metrics.inc("cache_bucket_compiles", bucket=bucket_str(bucket))

    def record_hit(self, bucket: "Bucket | None" = None) -> None:
        self.metrics.inc("cache_hits")
        if bucket is not None:
            self.metrics.inc("cache_bucket_hits", bucket=bucket_str(bucket))

    @property
    def compiles(self) -> int:
        return int(self.metrics.value("cache_compiles"))

    @property
    def hits(self) -> int:
        return int(self.metrics.value("cache_hits"))

    @property
    def requests(self) -> int:
        return self.compiles + self.hits

    @property
    def hit_rate(self) -> float:
        return self.hits / self.requests if self.requests else 0.0

    def row(self) -> dict:
        return {
            "compiles": self.compiles,
            "hits": self.hits,
            "hit_rate": round(self.hit_rate, 4),
        }

    # The key-locked export name (tests/test_obs.py snapshots this).
    snapshot = row


class CompileCache:
    """Executor store keyed by ``(bucket, slots, variant)`` with hit/miss
    counters.

    Each key maps to one peel executor built by ``builder(key)``; a key's
    executable only ever sees one argument-shape signature (the
    bucket-canonical one), so ``compiles`` counts actual XLA compilations,
    not just builder calls.  ``variant`` folds in whatever else
    specializes the program — the backend key, dataflow mode, and mesh
    placement.  ``metrics`` routes the hit/miss counters into the owning
    session's registry (default: a private one).
    """

    def __init__(
        self,
        builder: Callable[[tuple[Bucket, int, Hashable]], Callable],
        *,
        metrics: "MetricsRegistry | None" = None,
    ):
        self._builder = builder
        self._exes: dict[tuple[Bucket, int, Hashable], Callable] = {}
        self._lock = threading.Lock()
        self.stats = CacheStats(metrics)

    def get(
        self, bucket: Bucket, slots: int, variant: Hashable = "contig"
    ) -> tuple[Callable, bool]:
        """Return (executor, was_hit) for one bucket/slots/variant key."""
        key = (bucket, int(slots), variant)
        with self._lock:
            exe = self._exes.get(key)
            if exe is not None:
                self.stats.record_hit(bucket)
                return exe, True
            try:
                exe = self._exes[key] = self._builder(key)
            except TrussError:
                raise  # already typed (e.g. an injected CompileError)
            except Exception as e:
                # A failed build is a CompileError no matter which layer
                # threw — the resilience runner keys its fallback on it.
                raise CompileError(
                    f"building executor for bucket={bucket} slots={slots} "
                    f"variant={variant} failed: {e}",
                    bucket=bucket,
                    cause=e,
                ) from e
            self.stats.record_compile(bucket)
            return exe, False

    def buckets(self) -> tuple[str, ...]:
        """Labels of every bucket holding at least one compiled executable
        (sorted) — a replica's ``compiled_buckets`` health field, and the
        raw material of the router's bucket affinity."""
        with self._lock:
            seen = {bucket_str(b) for (b, _slots, _variant) in self._exes}
        return tuple(sorted(seen))

    def executors(self) -> list[Callable]:
        """The cached executors, in the order they were built."""
        with self._lock:
            return list(self._exes.values())

    def __len__(self) -> int:
        return len(self._exes)
