"""Where JAX's persistent compilation cache lives, decided in one place.

A process that compiles for the chip — a train worker, a serve replica, a
bench script — calls `configure()` before its first compile.  The cache
directory is part of the cache key, so it must not move between runs:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; no other path is
  set in code.
- unset: one fixed, git-ignored directory in the checkout, exported
  through the same variable so every process this one starts agrees.
"""
from __future__ import annotations

import os
import sys

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_WRITTEN = "/jax/compilation_cache/cache_misses"   # recorded on a write


def configure() -> str:
    """Settle the cache directory for this process and its children;
    returns it.  Imports nothing: worker boot calls this for every
    worker, most of which never touch JAX."""
    path = os.environ.setdefault(CACHE_DIR_ENV, DEFAULT_CACHE_DIR)
    jax = sys.modules.get("jax")
    if jax is not None:
        # Imported before us (a zygote preload): JAX read the variable
        # at import, when it was not there yet.
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_counts = {"hits": 0, "written": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == _HIT:
        _counts["hits"] += 1
    elif event == _WRITTEN:
        _counts["written"] += 1


def counts() -> dict:
    """This process's persistent-cache traffic, from JAX's own monitoring
    events: entries read back (`hits`) and entries written (`written`;
    programs under JAX's one-second compile-time floor are neither).
    Counting starts at the first call — one listener for the process,
    never removed — so call it once before the first compile."""
    global _listening
    if not _listening:
        import jax

        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return {"dir": os.environ.get(CACHE_DIR_ENV), **_counts}
