"""Where the persistent XLA compilation cache lives, and what it did.

One rule for every entry point (the server, bench.py, the tests' opt-in):
where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it stands
and nothing here sets a directory, so the cache can be placed from outside
(the operator points pods at the weight-cache volume with it). Where it is
not set, the cache is ONE fixed directory inside the checkout. The path is
part of what an entry is found by, so it never derives from a temporary
name, a pid or the time: a directory that moves never hits.
"""

from __future__ import annotations

import contextlib
import os
from typing import Dict, Iterator

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def enable(min_compile_secs: float = 1.0) -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile. The callers keep their own off
    switches (TPU_XLA_CACHE=0, BENCH_XLA_CACHE=0, the tests' opt-in)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return path


@contextlib.contextmanager
def watch() -> Iterator[Dict[str, int]]:
    """Count the persistent cache's hits and misses inside the block (a
    miss is an entry compiled and written; programs under the minimum
    compile time are neither). Both stay 0 while the cache is off."""
    seen = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_listener(on_event)
