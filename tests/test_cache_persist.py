"""Compile-cache persistence: a restarted server warm-starts from disk.

``TrussService(cache_dir=...)`` wires the in-process shape-bucket cache to
JAX's persistent compilation cache.  The contract: process A populates the
cache directory; a FRESH process B running the same bucket reports a
persistent-cache **hit on its first compile** (counted via JAX's own
``/jax/compilation_cache/cache_hits`` monitoring event — no timing
heuristics).  Subprocesses are required because the persistent cache is
keyed per process lifetime and must observe the config before first use.

With no explicit directory, :func:`repro.api.cache.enable_persistent_cache`
uses ``$JAX_COMPILATION_CACHE_DIR`` when it is set and configures no other
directory; otherwise it uses the fixed ``.jax_cache`` at the checkout root.
"""

import os
import subprocess
import sys

from repro.api.cache import persistent_cache_dir

_SCRIPT = """
import sys
import jax
import jax.monitoring

hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits"
    else None
)

from repro.api.cache import enable_persistent_cache
from repro.graphs import erdos
from repro.service import TrussService

# "-": no explicit directory; the helper picks the default.
explicit = None if sys.argv[1] == "-" else sys.argv[1]
if explicit is None:
    print(f"PERSIST_DIR={enable_persistent_cache()}")
svc = TrussService(max_batch=1, chunk=64, cache_dir=explicit)
fut = svc.submit_decompose(erdos(40, 5.0, seed=0))
svc.flush()
assert fut.result().kmax >= 2
print(f"PERSIST_CONFIGURED={jax.config.jax_compilation_cache_dir}")
print(f"PERSIST_HITS={len(hits)}")
print(f"PERSIST_COMPILES={svc.stats()['cache_compiles']}")
"""


def _run(cache_dir: str, env_cache_dir: str | None = None) -> dict:
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_cache_dir
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, cache_dir],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return dict(
        line.split("=", 1)
        for line in proc.stdout.splitlines()
        if line.startswith("PERSIST_")
    )


def test_fresh_process_reports_warm_first_compile(tmp_path):
    cache_dir = str(tmp_path / "xla-cache")
    cold = _run(cache_dir)
    # Process A: compiled once, nothing to hit in an empty cache dir...
    assert cold["PERSIST_COMPILES"] == "1"
    assert cold["PERSIST_HITS"] == "0"
    # ...but its executable persisted to disk.
    assert os.listdir(cache_dir), "persistent cache wrote no entries"

    warm = _run(cache_dir)
    # Process B: same in-process compile count (fresh process), but the
    # XLA compile underneath was served from the persistent cache.
    assert warm["PERSIST_COMPILES"] == "1"
    assert int(warm["PERSIST_HITS"]) >= 1, warm


def test_env_cache_dir_is_the_only_one_configured(tmp_path):
    env_dir = str(tmp_path / "env-cache")
    cold = _run("-", env_cache_dir=env_dir)
    assert cold["PERSIST_DIR"] == env_dir
    assert cold["PERSIST_CONFIGURED"] == env_dir
    assert os.listdir(env_dir), "no entries under JAX_COMPILATION_CACHE_DIR"
    warm = _run("-", env_cache_dir=env_dir)
    assert int(warm["PERSIST_HITS"]) >= 1, warm


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = persistent_cache_dir(), persistent_cache_dir()
    assert first == second
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
    assert first == os.path.join(root, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert persistent_cache_dir() == "/elsewhere/cache"
