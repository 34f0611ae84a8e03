"""The served cells' replica: `LLMDeployment` plus only what the program
cannot yet do for itself.  Nothing here changes scheduling.

  - parameters made on the device from the seed, in the configuration's
    dtype, through `params_loader`;
  - compilation of only the tiers the cell's traffic can reach, through
    the engine's own `warmup()`;
  - the logits check against the family's plain float32 reference
    (`correct`);
  - compile counts, engine spans of each request, device facts;
  - with tracing only: `jax.profiler` start/stop in this process, and
    `TraceAnnotation`s around the engine's tick phases.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from ray_tpu.serve.llm import LLMDeployment

_TICK_PHASES = {"_admit_one": "bench.engine.admit",
                "_decode_tick": "bench.engine.decode_tick",
                "_prefill_tick": "bench.engine.prefill_tick"}
_ENGINE_SPANS = ("serve.engine.queue_wait", "serve.engine.prefill_chunk")


# The logits check's sizes, where the configuration file has no `check`
# block of its own: 4 seeded prompts of 256 tokens, 16 decode steps.
CHECK_SIZES = {"lanes": 4, "prompt_len": 256, "decode_steps": 16}


def logits_check(e, c: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """`correct`, for the engine `e` of configuration `c`: seeded
    sequences scored by the engine's own programs (the family's `score`:
    prefill of each prompt, then teacher-forced decode steps, every lane
    in one call a step) against the family's plain float32 `forward`
    over all tokens of each, at the last prefill position and every
    decode step: 4 x (1 + 16) = 68 positions at the default sizes.
    (ISSUE 23 asked for 2 prompts and 8 steps; a model with experts
    needs more positions, reference.py says why.)  What is compared, the
    seeds, the precision of the reference and the verdict are the same
    for every family; the sizes are the configuration's (`check`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.harness import reference, spec

    fam = spec.family(c)
    sizes = {**CHECK_SIZES, **c.get("check", {})}
    lanes, n_decode = sizes["lanes"], sizes["decode_steps"]
    n_prompt = min(sizes["prompt_len"], e.max_len // 2)
    rng = np.random.default_rng(seed + 1)
    seqs = rng.integers(1, c["vocab_size"],
                        (lanes, n_prompt + n_decode), dtype=np.int64)
    got = fam.score(e, c, seqs, n_prompt)
    errors, margins = [], []
    with jax.default_matmul_precision("highest"):
        for lane in range(lanes):
            want, margin = fam.forward(
                e.params, jnp.asarray(seqs[lane], jnp.int32), c, jit=jax.jit)
            errors += list(reference.position_errors(
                jnp.stack(got[lane]), want[n_prompt - 1:]))
            margins += list(margin[n_prompt - 1:])
    return reference.logits_verdict(errors, margins, fam)


class BenchLLMDeployment(LLMDeployment):
    def __init__(self, config: Dict[str, Any], seed: int,
                 max_concurrency: int, trace: bool):
        from bench.harness import device, spec

        self._t = {"init_start": time.time()}
        self._counter = device.CompileCounter()
        self._marks: Dict[str, Dict[str, int]] = {}
        fam = spec.family(config)
        cfg = fam.program_config(config)
        eng = config["engine"]

        def loader():
            import jax

            params = device.seeded_params(fam, cfg, seed)
            jax.block_until_ready(params)
            self._t["params_ready"] = time.time()
            return params

        super().__init__(
            cfg, engine="paged", num_slots=eng["num_slots"],
            max_len=eng["max_len"], seed=seed & 0x7FFFFFFF,
            block_size=eng["block_size"],
            prefill_chunk=eng["prefill_chunk"],
            speculation_k=eng["speculation_k"], disagg=False,
            params_loader=loader)
        e = self.engine
        for name, want in (("max_burst", eng["max_burst"]),
                           ("block_size", eng["block_size"]),
                           ("prefill_chunk", eng["prefill_chunk"])):
            if getattr(e, name) != want:
                raise RuntimeError(f"engine.{name} is {getattr(e, name)}, "
                                   f"the configuration says {want}")
        self._t["engine_built"] = time.time()
        # Only the width tiers this cell's concurrency can reach, by the
        # engine's own warm-up.  Every chunk tier is reachable: the last
        # chunk of a prompt, and whatever budget the next prompt is left.
        reach = e._tier_for(e._width_tiers,
                            min(max_concurrency, e.num_slots))
        all_tiers = e._width_tiers
        e._width_tiers = [w for w in all_tiers if w <= reach]
        try:
            with e._tick_lock:
                e.warmup()
        finally:
            e._width_tiers = all_tiers
        self._warmed_widths = [w for w in all_tiers if w <= reach]
        self._t["warmed"] = time.time()
        self._check = logits_check(e, config, seed)
        self._t["checked"] = time.time()
        self._spans: List[dict] = []
        self._profiler = device.ProfilerWindow() if trace else None
        if trace:
            self._annotate_ticks()
        from ray_tpu.util import tracing

        tracing.set_exporter(self._keep_engine_spans)

    # -- tracing ----------------------------------------------------------
    def _annotate_ticks(self) -> None:
        e = self.engine
        for method, label in _TICK_PHASES.items():
            inner = getattr(e, method, None)
            if inner is None:
                raise RuntimeError(
                    f"traced run: PagedLLMEngine has no {method}() to put "
                    f"{label} around")
            setattr(e, method, self._annotated(inner, label))

    def _annotated(self, inner, label: str):
        """`inner` under a TraceAnnotation, and beside it a zero-length
        `bench.count.*` annotation whose arguments are the work of the
        tick, so that the trace itself says how many lanes, KV tokens and
        prompt tokens its device time stands for."""
        import jax

        e = self.engine
        note = jax.profiler.TraceAnnotation

        def decode_tick():
            live = [i for i, r in enumerate(e._slots)
                    if r is not None and not r.prefilling]
            if live:
                with note("bench.count.decode", lanes=len(live),
                          kv_tokens=int(sum(e._lengths[i] for i in live))):
                    pass
            with note(label):
                return inner()

        def prefill_tick():
            before = {id(r): r.pos for r in e._slots
                      if r is not None and r.prefilling}
            chunks = e.stats["prefill_chunks"]
            with note(label):
                out = inner()
            tokens = context = 0
            for r in e._slots:
                a = before.get(id(r)) if r is not None else None
                if a is not None and r.pos > a:
                    tokens += r.pos - a
                    # token at position p attends p + 1 positions
                    context += (r.pos * (r.pos + 1) - a * (a + 1)) // 2
            if tokens:
                with note("bench.count.prefill", tokens=tokens,
                          context=context,
                          chunks=e.stats["prefill_chunks"] - chunks):
                    pass
            return out

        def other(*a, **kw):
            with note(label):
                return inner(*a, **kw)

        return {"bench.engine.decode_tick": decode_tick,
                "bench.engine.prefill_tick": prefill_tick}.get(label, other)

    def _keep_engine_spans(self, spans: List[dict]) -> None:
        for s in spans:
            if s.get("name") in _ENGINE_SPANS:
                self._spans.append(s)

    # -- what the harness calls through the handle ------------------------
    def bench_mark(self, request: dict) -> dict:
        """Window opens / closes: remember the compile counts now."""
        self._marks[request["mark"]] = self._counter.snapshot()
        if request["mark"] == "open":
            self._spans.clear()
        return {"wall": time.time()}

    def bench_profile(self, request: dict) -> dict:
        self._profiler.run(float(request["seconds"]))
        return {}

    def bench_report(self, request: Optional[dict] = None) -> dict:
        from bench.harness import device
        from ray_tpu.util import tracing

        tracing.drain()                    # the rest goes to the exporter
        per_request: Dict[str, Dict[str, float]] = {}
        for s in self._spans:
            r = per_request.setdefault(s["trace_id"], {})
            if s["name"] == "serve.engine.queue_wait":
                r["submitted"], r["admitted"] = s["start_ts"], s["end_ts"]
            else:
                r["prefill_end"] = max(r.get("prefill_end", 0.0),
                                       s["end_ts"])
                r["prefill_tokens"] = r.get("prefill_tokens", 0) + int(
                    s["attrs"].get("tokens", 0))
        import os

        out = {"device": device.device_facts(), "pid": os.getpid(),
               "times": self._t, "check": self._check,
               "warmed_widths": self._warmed_widths,
               "compile_marks": self._marks,
               "compile_now": self._counter.snapshot(),
               "stats": self.engine.engine_stats(),
               "engine_spans": per_request}
        if self._profiler is not None and (request or {}).get("reduce"):
            out["trace"] = self._profiler.reduce(
                programs=request["programs"],
                keep=request.get("keep_trace"))
        return out
