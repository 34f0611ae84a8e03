"""What runs inside the process that owns the chip, for both kinds of
cell: seeded parameters made on the device, compile counting, the
profiler window.  (Only that process can ask JAX anything.)"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, Optional

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts this process's XLA compilations from JAX's own monitoring
    events, and the persistent cache's traffic through the program's
    `compile_cache.counts()`.  With the cache's floors lowered to zero by
    the harness's environment (run.py), every program is either read from
    the cache or compiled and written, so both views see every one."""

    def __init__(self):
        import jax

        from ray_tpu.util import compile_cache

        self._compiles = 0
        self._cache = compile_cache
        compile_cache.counts()          # registers the program's listener
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self._compiles += 1

    def snapshot(self) -> Dict[str, int]:
        c = self._cache.counts()
        return {"compiled": self._compiles, "cache_hits": c["hits"],
                "cache_written": c["written"]}


def seeded_key(seed: int):
    """A key from `--seed`, which is any whole number up to a little over
    2**31: folded in as two 31-bit halves, since a key takes 32 signed
    bits where x64 is off.  The `rbg` generator: the chip's own random
    bits.  The default (threefry) draws a 7B model's layers at about half
    a gigabyte a second on a v5e, 15 s of every run's set-up (PR 23)."""
    import jax

    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def seeded_params(family, cfg, seed: int):
    """The parameter tree of the program configuration `cfg`, made on the
    device in one jitted call of the family's `init_params` from the
    seed, in the configuration's `param_dtype` (the program's own
    initialiser called eagerly draws float32 leaf by leaf and casts)."""
    import jax

    return jax.jit(lambda k: family.init_params(k, cfg))(seeded_key(seed))


def device_facts() -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


class ProfilerWindow:
    """One `jax.profiler` trace of a few seconds, reduced in this process
    after the measured window has closed."""

    def __init__(self):
        self._dir: Optional[str] = None
        self._lock = threading.Lock()

    def start(self) -> None:
        import jax

        self._lock.acquire()
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0         # host spans only from TraceMe
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        try:
            jax.profiler.stop_trace()
        finally:
            self._lock.release()

    def run(self, seconds: float) -> None:
        self.start()
        try:
            time.sleep(seconds)
        finally:
            self.stop()

    def reduce(self, programs, keep: Optional[str] = None
               ) -> Dict[str, Any]:
        """`keep`: a directory to leave a copy of the trace in, to look
        at by hand (bench/tools only)."""
        from bench.harness import xplane

        with self._lock:
            if self._dir is None:
                raise RuntimeError("no profiler window was recorded")
            try:
                (path,) = glob.glob(os.path.join(
                    self._dir, "plugins", "profile", "*", "*.xplane.pb"))
                if keep:
                    os.makedirs(keep, exist_ok=True)
                    shutil.copy(path, os.path.join(keep, "trace.xplane.pb"))
                return xplane.reduce_file(path, programs=programs)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
                self._dir = None
