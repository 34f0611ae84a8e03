"""Where JAX's persistent compilation cache lives, decided in one place,
and what this process paid JAX's compile path for, program by program.

A process that compiles for the chip — a train worker, a serve replica, a
bench script — calls `configure()` before its first compile.  The cache
directory is part of the cache key, so it must not move between runs:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; no other path is
  set in code.
- unset: one fixed, git-ignored directory in the checkout, exported
  through the same variable so every process this one starts agrees.

The compile log (`log()`, `counts()`) is built from JAX's own monitoring
events and from nothing else: a trace, a lowering and a backend phase
(the load of an executable on a cache hit, its compilation on a miss)
each raise one, named for the jitted function, on the thread that
compiles, with start and end on `time.time()` — the clock of
`tracing.Span`, so an entry lies on a replica's timeline with no
conversion.  JAX raises them only where a function is traced or an
executable is made: a call of a program that exists (a warm decode or
prefill tick) raises none, calls no listener here and adds no entry.
"""
from __future__ import annotations

import os
import sys
import threading
from collections import deque
from typing import Any, Dict, List, Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# The three phases of one program, as time spans with `fun_name`.
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
           "/jax/core/compile/backend_compile_duration": "backend_s"}
# Inside a backend phase, on its thread, where a cache directory is set:
# the request, then a hit and its retrieval time, or (after compiling)
# the entry's write.
_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

# A replica's whole start fits: the catalog's cells put 300-900 entries
# in it, most of them one-operation programs (`convert_element_type`)
# beside the tiers.
LOG_KEPT = 4096


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


class _Compiling:
    """What one thread's compile in progress has raised so far."""
    __slots__ = ("traced", "cache", "retrieval_s")

    def __init__(self):
        # Entries with no backend phase yet, oldest first.  The last
        # may go on to its next phase; one that began inside a later
        # phase (a jitted function called while another is traced or
        # lowered) is part of that phase and never compiles on its own.
        self.traced: List[dict] = []
        self.cache: Optional[str] = None
        self.retrieval_s: Optional[float] = None


_lock = threading.Lock()
_entries: deque = deque(maxlen=LOG_KEPT)
_compiling: Dict[int, _Compiling] = {}
_totals = {"hits": 0, "written": 0, "programs": 0, "misses": 0, "off": 0,
           "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0}
_listening = False


def _program(fun_name: Any) -> str:
    name = str(fun_name)
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") \
        else name


def _keep(entry: dict) -> None:
    _entries.append(entry)
    for k in ("trace_s", "lower_s", "backend_s"):
        _totals[k] += entry[k] or 0.0
    if entry["backend_s"] is not None:
        _totals["programs"] += 1
        if entry["cache"] != "hit":
            _totals["misses" if entry["cache"] == "miss" else "off"] += 1


def _mine(tid: int) -> _Compiling:
    """The calling thread's compile in progress (under `_lock`)."""
    st = _compiling.get(tid)
    if st is None:
        st = _compiling[tid] = _Compiling()
    return st


def _on_span(event: str, start: float, end: float, fun_name: Any = "",
             **_kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    program, tid = _program(fun_name), threading.get_ident()
    with _lock:
        st = _mine(tid)
        # What was traced inside this phase (a jitted function called
        # while another is traced or lowered) is this phase's.
        while st.traced and st.traced[-1]["start_ts"] >= start:
            st.traced.pop()
        entry = None
        if phase == "trace_s":
            if len(st.traced) >= LOG_KEPT:      # traces that never compile
                _keep(st.traced.pop(0))
        else:
            if st.traced and st.traced[-1]["program"] == program:
                entry = st.traced.pop()
            # Nothing earlier on this thread is going to compile now.
            for e in st.traced:
                _keep(e)
            st.traced.clear()
        if entry is None:
            entry = {"program": program, "start_ts": start, "end_ts": end,
                     "trace_s": None, "lower_s": None, "backend_s": None,
                     "cache": None, "retrieval_s": None, "thread": tid}
        entry[phase] = end - start
        entry["end_ts"] = end
        if phase == "backend_s":
            # JAX raises the request with no directory to ask, too.
            entry["cache"] = (st.cache if sys.modules["jax"].config
                              .jax_compilation_cache_dir else None) or "off"
            entry["retrieval_s"] = st.retrieval_s
            _keep(entry)
            del _compiling[tid]
        else:
            st.traced.append(entry)


def _on_event(event: str, **_kw) -> None:
    if event not in (_REQUEST, _HIT, _MISS):
        return
    with _lock:
        if event == _MISS:
            _totals["written"] += 1
            return
        # A request is a miss until its hit is raised.
        _mine(threading.get_ident()).cache = (
            "hit" if event == _HIT else "miss")
        _totals["hits"] += event == _HIT


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == _RETRIEVAL:
        with _lock:
            st = _compiling.get(threading.get_ident())
            if st is not None:
                st.retrieval_s = secs


def _listen() -> None:
    """One set of listeners for the process, never removed; registered
    at the first call of `counts()` or `log()`, so call one of them
    before the first compile."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_time_span_listener(_on_span)


def counts() -> dict:
    """This process's persistent-cache traffic, from JAX's own monitoring
    events: entries read back (`hits`) and entries written (`written`;
    programs under JAX's one-second compile-time floor are neither),
    and the running totals of the compile log, which dropping an old
    entry never lowers: `programs` (entries with a backend phase),
    `misses` and `off` of them, and the seconds by phase.  Counting
    starts at the first call, so call it once before the first
    compile."""
    _listen()
    with _lock:
        return {"dir": os.environ.get(CACHE_DIR_ENV), **_totals}


def log(since: Optional[float] = None) -> List[dict]:
    """The newest `LOG_KEPT` entries of the compile log, oldest first
    (those that started at or after `since`, where given), one per
    program that went through JAX's compile path:

        {"program", "start_ts", "end_ts", "trace_s", "lower_s",
         "backend_s", "cache": "hit" | "miss" | "off", "retrieval_s",
         "thread"}

    `program` is JAX's `fun_name` without its `jit(...)`; `start_ts` is
    the start of the first phase seen and `end_ts` the end of the last;
    a phase that did not run is None (a trace JAX had cached; an
    `eval_shape`, which has no backend phase and so no `cache`).
    `backend_s` is the load of the executable where `cache` is "hit"
    (`retrieval_s` of it reading the entry) and its compilation
    otherwise; "off" is a compile with no cache directory.  A function
    traced inside another's trace has no entry: its time is the outer
    trace's.  An entry still compiling on another thread is left out."""
    _listen()
    with _lock:
        return [dict(e) for e in _entries
                if since is None or e["start_ts"] >= since]
