"""The served contract, written once (no test of its own): what every
family of model on the served path is held to, as functions of a small
description (`Family`) that the family's file fills in.

A family's file (`tests/test_*_serving.py`) writes its description, the
faults of its own mechanism (the `monkeypatch` functions name the family's
modules, so they stay there) and the cases of that mechanism; it gets the
helpers (a tiny configuration read with overrides, an engine built from
the configuration's `engine` block, the reference's logits for a lane,
every compared position's error, seeded sequences, "is this stream the
greedy one"), a file's engines each built once (`fixtures`), and the
shared cases, which it collects under its own names by one-line
delegations:

    FAM = Family(tiny="mellumfamily/configs/tinymellum-serve.json", ...)
    engines, served = fixtures(FAM)

    def test_a_preempted_stream_equals_the_undisturbed_one(engines):
        stats = preempted_stream(FAM, engines)
        assert stats["state"]["state_resets"] == 0      # the family's own

`tests/burst_ahead_cases.py` keeps what the engine's own tests of the
run-ahead tick share with these (a parked engine, the step-by-step
reference, the join-and-leave scenario); this module imports it."""
import contextlib
import dataclasses
import functools
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import reference, spec
from burst_ahead_cases import join_and_leave, park, ticks_of
from ray_tpu import models
from ray_tpu.models import configs, decoding
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "bench", "tests", "data")
FLOAT32 = jnp.dtype("float32")
# The benchmark's own arithmetic, where a case asks whether its
# comparison tells a fault from rounding.
BFLOAT16 = dict(param_dtype="bfloat16", compute_dtype="bfloat16",
                cache_dtype="bfloat16")
SMALL = dict(num_slots=2, max_len=64, block_size=8, prefill_chunk=16)


# -- a program is made once -----------------------------------------------------
_JITTED: dict = {}
_PARAMS: dict = {}


def jit(f):
    """`jax.jit` that remembers: a reference's `forward(..., jit=jit)` wraps
    `functools.partial(block, c=c, kind=kind)` anew at every call, and a
    fresh `jax.jit` of it traces, lowers and compiles a program that the
    call before made.  Remembered by what the program is made from: the
    function, the partial's arguments and its module's `TOLERANCES` (a case
    may put its own in place), so a case that changes one of them gets a
    program of its own.  A function defined inside another is never
    remembered.  Only for the program as it is: a case that patches what a
    traced function calls wraps its own `jax.jit`."""
    part = f if isinstance(f, functools.partial) else functools.partial(f)
    fn = part.func
    if getattr(fn, "__qualname__", "<") != getattr(fn, "__name__", ">"):
        return jax.jit(f)
    try:
        key = (fn, part.args, json.dumps(part.keywords, sort_keys=True),
               json.dumps(getattr(sys.modules.get(fn.__module__),
                                  "TOLERANCES", None), sort_keys=True))
        hash(key)
    except TypeError:
        return jax.jit(f)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(f)
    return _JITTED[key]


def bound(fn, cfg, **jit_kw):
    """`jax.jit(decoding._bind_cfg(fn, cfg), **jit_kw)`, made once a
    configuration (as `jit`: for the program as it is)."""
    key = (fn, cfg, tuple(sorted(jit_kw.items())))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(decoding._bind_cfg(fn, cfg), **jit_kw)
    return _JITTED[key]


# -- the description -----------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)
class Teeth:
    """How `logits_check_has_teeth` reads the family's verdicts."""
    bound_key: str = "LOGITS_REL_EXPERTS"
    # {name: value} put into the family's TOLERANCES for the case (this
    # size's readings, where the family's own are the published widths'),
    # or a function of the reference's module that returns them
    tolerances: Any = None
    seed: Optional[int] = None          # parameters and check; None: `seed`
    sound_margin: Optional[float] = None    # as it is: worst < this x bound
    fault_reads: Optional[str] = "worst_decided"    # what is over the bound
    decided_under_fault: bool = False   # no router: a fault decides all too


@dataclasses.dataclass(frozen=True, eq=False)
class Family:
    tiny: str                   # its tiny configuration, under DATA
    registry: str               # the preset the tiny configuration is
    as_registry: dict           # what the configuration's file replaces in it
    published: tuple = ()       # (preset, unit, its parameters in units)
    leaves: tuple = ()          # (preset, how far over num_params its
    #                             arrays may count: None for equal)
    seed: int = 5
    exact: float = 2e-5         # float32 engine against float32 reference
    own_init: bool = True       # cfg.init_params(key), else
    #                             models.init_params(key, cfg)
    routes: bool = True         # the reference's forward takes a routing
    # what `score(..., routing=True)` left for a lane -> the keywords that
    # hand it to the reference; None: `score` hands nothing out
    handed: Optional[Callable] = None
    greedy_by_reference: bool = True    # else a stream is held to the
    #                                     undisturbed engine's
    engine: dict = dataclasses.field(default_factory=dict)  # over the
    #                             configuration's `engine` block, always
    deployment: dict = dataclasses.field(default_factory=lambda: SMALL)
    request: tuple = (20, 3)    # a deployment's prompt, its max_tokens
    front: Optional[tuple] = None       # (app name, keywords beside SMALL)
    slot_leaves: tuple = ()     # the state's leaves by slot
    written: tuple = ()         # those of them a decode step must change
    preempt: dict = dataclasses.field(default_factory=lambda: dict(
        engine=dict(num_blocks=12, max_burst=4),
        prompts=((30, 21), (30, 22)), max_tokens=24, stagger=0.05))
    refusals: dict = dataclasses.field(default_factory=dict)
    # (engine, a tick's dict): what a burst's tick must count
    burst_tick: Optional[Callable] = None
    teeth: Optional[Teeth] = None

    def config(self, **over):
        with open(os.path.join(DATA, self.tiny)) as f:
            return dict(json.load(f), **over)

    def reference(self, c=None):
        return spec.family(c or self.config())

    def program_config(self, c):
        return spec.family(c).program_config(c)

    def draw(self, cfg, seed):
        key = jax.random.key(seed)
        return cfg.init_params(key) if self.own_init \
            else models.init_params(key, cfg)

    def params(self, cfg, seed=None):
        """Seeded parameters of `cfg`, drawn once a (cfg, seed)."""
        key = (cfg, self.seed if seed is None else seed)
        if key not in _PARAMS:
            _PARAMS[key] = self.draw(*key)
        return _PARAMS[key]

    def build(self, c, cfg=None, params=None, seed=None, **over):
        """A `PagedLLMEngine` of configuration `c` from its `engine`
        block, `self.engine` and `over`; `cfg`: the program's, where it is
        not the stated one (a fault); the parameters are the stated one's."""
        true = self.program_config(c)
        eng = {**c["engine"], **self.engine, **over}
        return PagedLLMEngine(
            cfg or true,
            self.params(true, seed) if params is None else params,
            num_slots=eng.pop("num_slots"), max_len=eng.pop("max_len"),
            block_size=eng.pop("block_size"),
            prefill_chunk=eng.pop("prefill_chunk"),
            max_burst=eng.pop("max_burst"),
            speculation_k=eng.pop("speculation_k"), **eng)

    def want(self, e, c, seq, **handed):
        """The reference's logits for one lane (`handed`: the program's
        routing and the like; nothing: the reference's own)."""
        if self.routes:
            handed.setdefault("routing", None)
        logits, margin = spec.family(c).forward(
            e.params, jnp.asarray(seq, jnp.int32), c, jit=jit, **handed)
        if not self.routes:
            assert bool(jnp.all(jnp.isinf(margin)))
        return logits

    def errors(self, e, c, seqs, n_prompt):
        """Every compared position's error against the reference, which is
        handed what the program took where `score` hands it out (and
        refuses it, NaN, outside its slack)."""
        if self.handed is None:
            got, taken = e.score(seqs, n_prompt), None
        else:
            got, taken = e.score(seqs, n_prompt, routing=True)
        return np.concatenate([
            np.asarray(reference.position_errors(
                jnp.stack(got[lane]), self.want(
                    e, c, seqs[lane],
                    **(self.handed(taken[lane]) if taken else {})
                )[n_prompt - 1:]))
            for lane in range(len(seqs))])

    def is_greedy(self, e, c, prompt, out):
        """`out` is the reference's greedy continuation of `prompt`: one
        full forward over both, whose argmax at every position from the
        prompt's last is the token that follows."""
        logits = self.want(e, c, list(prompt) + list(out))
        return out == [int(t) for t in
                       jnp.argmax(logits[len(prompt) - 1:-1], axis=-1)]


def seqs(lanes, total, seed=0):
    return np.random.default_rng(seed).integers(1, 512, (lanes, total))


def ints(row):
    return list(map(int, row))


def prompt(n, seed):
    return ints(seqs(1, n, seed=seed)[0])


# -- a file's engines, each built once -------------------------------------------
def unpark(e):
    """Start the loop thread of an engine that `park` stopped."""
    if not e._thread.is_alive():
        e._stop = False
        e._thread = threading.Thread(target=e._loop, daemon=True)
        e._thread.start()
    return e


def _idle(e) -> bool:
    """Makes `e` as a fresh engine as far as a case can see, and says
    whether it is: its loop runs, no sequence is live, no burst is in
    flight, and the pool's blocks are free, no prefix registered (those an
    earlier case left are forgotten: its prompt would be a hit here)."""
    unpark(e)
    with e._tick_lock:
        e._drain()
        if e._pending or e._prefillq or any(r is not None for r in e._slots):
            return False
        pool = e.allocator
        with pool._lock:
            while pool._cached:             # nobody's: refcount 0
                blk, _ = pool._cached.popitem(last=False)
                pool._forget_locked(blk)
                pool._free.append(blk)
        snap = pool.snapshot()
        return snap["blocks_active"] == 0 == snap["prefixes_registered"]


class Engines:
    """A file's engines by (configuration overrides, parameter seed,
    engine overrides): each built once, handed out idle (`engines(...)`
    returns (engine, configuration)), all shut down at the module's end.
    One that a failed case left busy is built anew.

    A shared engine's counters and logs are cumulative: a case reads what
    it added (`Since`).  `private(...)` builds an engine for one case and
    shuts it down with it: for a fault patched in before the trace, a
    program that is not the stated one, or a case that counts from an
    empty engine; a refusal raised by the constructor calls
    `Family.build` itself."""

    def __init__(self, fam):
        self.fam, self._built = fam, {}

    def __call__(self, config=None, seed=None, **over):
        key = json.dumps([config, seed, over], sort_keys=True, default=repr)
        held = self._built.get(key)
        if held is not None and not _idle(held[0]):
            self._built.pop(key)[0].shutdown()
            held = None
        if held is None:
            c = self.fam.config(**(config or {}))
            held = self._built[key] = (self.fam.build(c, seed=seed, **over),
                                       c)
        return held

    @contextlib.contextmanager
    def private(self, config=None, cfg=None, params=None, seed=None, **over):
        c = self.fam.config(**(config or {}))
        e = self.fam.build(c, cfg, params, seed, **over)
        try:
            yield e, c
        finally:
            e.shutdown()

    def shutdown(self):
        while self._built:
            self._built.popitem()[1][0].shutdown()


def fixtures(fam):
    """(`engines`: the module's `Engines`; `served`: the engine of the
    configuration as it is, and the configuration) for a family's file."""
    @pytest.fixture(scope="module")
    def engines():
        made = Engines(fam)
        yield made
        made.shutdown()

    @pytest.fixture
    def served(engines):
        return engines()

    return engines, served


class Since:
    """What an idle engine's counters and logs gain from here on."""

    def __init__(self, e):
        self.e, self.at = e, time.time()
        self.before = self._read()

    def _read(self):
        # a tick logs itself when it ends, under this lock; generate()
        # returns from inside the tick that finished the request
        with self.e._tick_lock:
            return self.e.engine_stats()

    def stats(self):
        """`engine_stats()` with its counters (the allocator's, the
        launches' tokens by tier and `state`'s two among them) as what they
        gained, and `tick_log` and `request_phases` from here on; levels
        (`queue_depth`, `blocks_free`, the resident bytes) as they stand."""
        now, was = self._read(), self.before
        at = now["tick_fields"].index("start")
        out = dict(
            now,
            tick_log=[t for t in now["tick_log"] if t[at] >= self.at],
            request_phases=[r for r in now["request_phases"]
                            if r["submitted"] >= self.at],
            prefill_launch_tokens={
                tier: n - was["prefill_launch_tokens"][tier]
                for tier, n in now["prefill_launch_tokens"].items()},
            state={k: v - was["state"][k] if k.startswith("state_") else v
                   for k, v in now["state"].items()})
        for name in set(self.e.stats) | set(self.e.allocator.stats):
            if isinstance(now.get(name), int):
                out[name] = now[name] - was[name]
        return out

    def ticks(self, stats=None):
        stats = stats or self.stats()
        return [dict(zip(stats["tick_fields"], t))
                for t in stats["tick_log"]]


# -- the shared cases ------------------------------------------------------------
def tiny_configuration_is_the_registry_s(fam):
    """The family's tiny configuration gives the registry's preset, but
    for its name and what its file states.  Returns (c, cfg)."""
    c = fam.config()
    cfg = fam.program_config(c)
    assert cfg == dataclasses.replace(configs.get(fam.registry),
                                      name=c["name"], **fam.as_registry)
    return c, cfg


def published_parameter_count(fam):
    """The published preset counts the published parameters, and a
    preset's arrays count what `num_params` says (norms' gains, biases and
    the like on top, where it leaves them out).  Returns (the published
    preset, the shapes of `leaves`' arrays)."""
    name, unit, count = fam.published
    cfg, shapes = configs.get(name), None
    assert round(cfg.num_params / unit) == count
    if fam.leaves:
        preset, over = fam.leaves
        of = configs.get(preset)
        shapes = jax.eval_shape(lambda: fam.draw(of, 0))
        total = sum(x.size for x in jax.tree.leaves(shapes))
        if over is None:
            assert total == of.num_params
        else:
            assert 0 < total - of.num_params < over * of.num_params
    return cfg, shapes


def prefill_then_decode_equals_the_reference(fam, e, c, lanes, n_prompt,
                                             steps, seed=0):
    """`lanes` prompts of `n_prompt` prefilled in the engine's launches,
    then `steps` decode steps through the function the burst scans, all
    lanes a step: logits, not tokens, against the reference's full
    forward.  Returns the errors."""
    errs = fam.errors(e, c, seqs(lanes, n_prompt + steps, seed=seed),
                      n_prompt)
    assert errs.shape == (lanes * (steps + 1),), errs.shape
    assert errs.max() < fam.exact, errs
    return errs


def every_chunk_tier_and_a_padded_tail(fam, engines, n_prompt, tiers):
    """An engine of `prefill_chunk` 64 (its `tiers`): prompts that are one
    whole chunk, a chunk and a padded tail of either tier, and two whole.
    A padded position writes no ring row and advances no recurrence."""
    e, c = engines(prefill_chunk=64)
    assert e._chunk_tiers == tiers
    errs = fam.errors(e, c, seqs(2, n_prompt + 4, seed=n_prompt), n_prompt)
    assert errs.max() < fam.exact, errs
    return e


def a_fault_is_seen(fam, engines, cfg, c=None, n_prompt=50, steps=4, seed=9,
                    times=100):
    """Float32 on both sides: a program that leaves one mechanism out
    (`cfg`, or what the caller patched in before this trace) is `times`
    further from the reference than the engine's own error, or what it
    took strays outside the reference's slack (NaN)."""
    with engines.private(cfg=cfg) as (e, stated):
        errs = fam.errors(e, c or stated, seqs(2, n_prompt + steps, seed=seed),
                          n_prompt)
    assert not np.isfinite(errs).all() or errs.min() > times * fam.exact, errs


def unequal_lanes_with_an_idle_lane_between(fam, e, c):
    """The step the burst scans, over lanes 0 and 2 of different lengths
    with lane 1 idle and pointed at its own slot all the same (the engine
    points idle lanes at the null slot): the live lanes' logits are the
    reference's, the idle lane's slot and the null slot keep every leaf of
    `fam.slot_leaves` to the bit, and lane 0's slot has `fam.written`
    changed."""
    rows = [seqs(1, 70, seed=1)[0], seqs(1, 30, seed=2)[0],
            seqs(1, 41, seed=3)[0]]
    step = bound(decoding.paged_decode_step, e.cfg)
    bs = e.block_size
    with e._tick_lock:
        tables = np.zeros((4, e._b_max), np.int32)
        for lane, seq in enumerate(rows):
            per = -(-(len(seq) + 1) // bs)
            tables[lane, :per] = 1 + lane * 16 + np.arange(per)
            if e._reset_state is not None:
                e.cache = e._reset_state(e.cache, jnp.int32(lane))
            for start in range(0, len(seq) - 1, e.prefill_chunk):
                toks = np.zeros((e.prefill_chunk,), np.int32)
                nv = min(e.prefill_chunk, len(seq) - 1 - start)
                toks[:nv] = seq[start:start + nv]
                e.cache, *_ = e._prefill_chunk_fn(
                    e.params, e.cache, jnp.asarray(toks),
                    jnp.asarray(tables[lane]), jnp.int32(start),
                    jnp.int32(nv), slot=jnp.int32(lane))
        before = jax.tree.map(np.asarray, e.cache)
        lengths = np.array([69, 29, 40, 0], np.int32)
        active = np.array([True, False, True, False])
        cache, logits = step(
            e.params, e.cache, jnp.asarray([s[-1] for s in rows] + [0],
                                           jnp.int32),
            jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(active),
            slots=jnp.asarray([0, 1, 2, e.num_slots], jnp.int32))
        after = jax.tree.map(np.asarray, cache)
    for lane in (0, 2):
        err = reference.position_errors(
            logits[lane][None], fam.want(e, c, rows[lane])[-1:])
        assert float(err[0]) < fam.exact
    for name in fam.slot_leaves:
        for slot in (1, e.num_slots):
            assert np.array_equal(getattr(after, name)[:, slot],
                                  getattr(before, name)[:, slot]), name
    for name in fam.written:
        assert not np.array_equal(getattr(after, name)[:, 0],
                                  getattr(before, name)[:, 0]), name


def preempted_stream_equals_the_undisturbed_one(fam, engines):
    """A pool too small for two streams' growth (`fam.preempt`): the
    younger is preempted mid-decode and re-prefills prompt + emitted
    tokens; each stream is the reference's greedy one (or, where the
    reference cannot settle a stream, the one an undisturbed engine
    gives).  Returns what the engine's statistics gained."""
    how = fam.preempt
    n = how["max_tokens"]
    prompts = [prompt(length, seed) for length, seed in how["prompts"]]
    alone = None
    if not fam.greedy_by_reference:
        undisturbed, _ = engines()
        alone = [undisturbed.generate(p, max_tokens=n) for p in prompts]
    e, c = engines(**how["engine"])
    since, outs = Since(e), [None, None]

    def run(i):
        outs[i] = e.generate(prompts[i], max_tokens=n)

    threads = [threading.Thread(target=run, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
        time.sleep(how["stagger"])
    for t in threads:
        t.join(timeout=300)
    stats = since.stats()
    assert stats["preemptions"] >= 1
    assert all(len(o) == n for o in outs)
    if alone is None:
        assert all(fam.is_greedy(e, c, p, o) for p, o in zip(prompts, outs))
    else:
        assert outs == alone
    return stats


def streams_equal_the_step_reference_while_lanes_join_and_leave(fam, engines):
    """The engine launches a burst before it has read the one before
    (tests/test_burst_ahead.py), here on the family's state and counts:
    requests of different lengths join and leave mid-stream, the tiers go
    4, 8, 4, a slot changes hands while its last burst is unread, every
    stream is the step-by-step reference's, and every burst's tick counts
    what `fam.burst_tick` says."""
    e, _ = engines(num_slots=8)
    since = Since(e)
    join_and_leave(park(e), bound=bound)
    if fam.burst_tick is not None:
        launched = [t for t in ticks_of(e)
                    if t["lanes"] and t["start"] >= since.at]
        assert launched
        for t in launched:
            fam.burst_tick(e, t)


def refusals(fam):
    """What sequence state by slot forbids is refused, and says why
    (`fam.refusals`: the messages): speculation, a hit on a prefix (off by
    itself), frames out and in, prefill offload and a mesh in the
    deployment.  Returns (cfg, params, the small engine, shut down) for
    what else the family refuses."""
    says = fam.refusals
    cfg = fam.program_config(fam.config())
    params = fam.params(cfg, seed=0)
    with pytest.raises(ValueError, match=says["speculation_k"]):
        PagedLLMEngine(cfg, params, **SMALL, speculation_k=4)
    e = PagedLLMEngine(cfg, params, **SMALL, prefix_sharing=True)
    try:
        assert not e.allocator.prefix_sharing     # off by itself
        prompt = list(range(1, 30))
        e.generate(prompt, max_tokens=2)
        e.generate(prompt, max_tokens=2)
        assert e.stats["prefix_hits"] == 0
        with pytest.raises(ValueError, match=says["export_streams"]):
            e.export_streams()
        with pytest.raises(ValueError, match=says["import_prefix"]):
            e.import_prefix(prompt, np.zeros(says["frame"]), 8)
    finally:
        e.shutdown()
    for kw in ({"disagg": True}, {"tensor_parallel": 2}):
        with pytest.raises(ValueError, match=says["deployment"]):
            LLMDeployment(cfg, num_slots=2, max_len=64, **kw)
    return cfg, params, e


@contextlib.contextmanager
def deployed(fam, cfg=None):
    """`LLMDeployment` of the family's preset by name (or `cfg`), one
    request through it; the deployment for what else the family reads."""
    dep = LLMDeployment(cfg or fam.registry, **fam.deployment)
    try:
        n_prompt, n_out = fam.request
        out = dep({"tokens": list(range(1, n_prompt)), "max_tokens": n_out})
        assert len(out["tokens"]) == n_out
        yield dep
    finally:
        dep.engine.shutdown()


def served_through_the_front_like_any_model(fam):
    """serve.run -> proxy -> handle -> replica -> engine: the stream and
    the HTTP answer are a local twin engine's tokens.  Returns the
    replica's statistics."""
    import urllib.request

    import ray_tpu
    from ray_tpu import serve

    app, kw = fam.front
    cfg = configs.get(fam.registry)
    shape = dict(SMALL, max_len=128)
    prompt = list(range(3, 40))
    twin = PagedLLMEngine(cfg, fam.params(cfg, seed=0), **shape)
    try:
        want = twin.generate(prompt, max_tokens=10)
    finally:
        twin.shutdown()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        serve.run(serve.deployment(LLMDeployment).bind(
            fam.registry, **shape, **kw), name=app, _http=True,
            route_prefix="/" + app)
        handle = serve.get_app_handle(app)
        streamed = [it["token"] for it in handle.options(
            method_name="stream").remote_streaming(
                {"tokens": prompt, "max_tokens": 10})]
        assert streamed == want
        req = urllib.request.Request(
            f"http://127.0.0.1:{serve.http_port()}/{app}",
            data=json.dumps({"tokens": prompt, "max_tokens": 10}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert json.loads(resp.read())["tokens"] == want
        stats = handle.options(method_name="stats").remote({}).result(
            timeout=60)
        assert stats["prefix_hits"] == 0
        return stats
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def on_the_engine(fault):
    """Marks a fault of `logits_check_has_teeth` that is put into the
    built engine, `fault(e, ref, monkeypatch)`, through `monkeypatch`
    alone (so that the engine, shared, is whole again after the case);
    any other is `fault(monkeypatch, cfg)`, patched in before the trace,
    and may return the program's configuration."""
    fault.on_the_engine = True
    return fault


def logits_check_has_teeth(fam, engines, fault, monkeypatch):
    """`deployment.logits_check` (3 lanes x (the last of 100 prompt
    positions + 8 decode steps), bfloat16 parameters, compute and cache as
    the benchmark's configuration has them, what the program took handed
    over and held to the family's slack, the error to `fam.teeth`'s bound)
    passes the program as it is (`fault` None) with every position decided
    and fails each fault."""
    from bench.harness.deployment import logits_check

    teeth = fam.teeth
    ref = fam.reference()
    put = teeth.tolerances(ref) if callable(teeth.tolerances) \
        else teeth.tolerances or {}
    for name, value in put.items():
        monkeypatch.setitem(ref.TOLERANCES, name, value)
    seed = fam.seed if teeth.seed is None else teeth.seed
    if fault is None or getattr(fault, "on_the_engine", False):
        e, c = engines(config=BFLOAT16, seed=seed)
        if fault is not None:
            fault(e, ref, monkeypatch)
        v = logits_check(e, c, seed)
    else:
        c = fam.config(**BFLOAT16)
        cfg = fault(monkeypatch, fam.program_config(c))
        with engines.private(config=BFLOAT16, cfg=cfg, seed=seed) as (e, c):
            v = logits_check(e, c, seed)
    assert v["positions"] == 27
    assert v["bound"] == ref.TOLERANCES[teeth.bound_key]
    if fault is None:
        assert v["ok"] and v["decided"] == 27, v
        if teeth.sound_margin is not None:
            assert v["worst"] < teeth.sound_margin * v["bound"], v
        return
    assert not v["ok"], v
    if teeth.decided_under_fault:
        assert v["decided"] == 27, v
    if teeth.fault_reads == "worst":
        assert v["worst"] > v["bound"], v
    elif teeth.fault_reads == "worst_decided":
        assert not v["finite"] or v["worst_decided"] > v["bound"], v


def shares_cut_in_the_program_add_up(fam, module, ffn, li, experts_key):
    """A layer of 8 experts whole, and cut into the shares (0..3) and
    (4..7) with the same router (and bias): the routed parts of the two
    shares, plus the shared expert counted once, equal the uncut layer;
    experts visited and choices routed add up too.  In the program
    (`module`'s `ffn`) and in the reference alike, and the two agree."""
    from ray_tpu.ops import moe

    c = fam.config(**{experts_key: 8, "first_local_expert": 0})
    ref = spec.family(c)
    whole = dataclasses.replace(ref.program_config(c),
                                compute_dtype=jnp.float32)
    assert whole.experts_held is None
    params = fam.params(whole)
    x, fp, experts = layer_inputs(module, whole, params, li)
    live = jnp.ones(x.shape[:2], bool)
    full, n_full, r_full, _ = ffn(fp, experts, li, x, live, whole, False)
    assert int(r_full) == x.shape[0] * x.shape[1] * whole.expert_top_k
    h = module.rms_norm(x, fp["norm"], eps=whole.norm_eps)
    router = {k: fp[k] for k in ("router", "router_bias") if k in fp}
    shared = full - moe.moe_mlp_dropless(h, {**router, **experts}, whole.moe,
                                         layer=li)[0]
    parts, ref_parts, visited, routed = [], [], 0, 0
    u = jnp.asarray(np.asarray(h).reshape(-1, whole.d_model))
    for first in (0, 4):
        cut = dataclasses.replace(whole, experts_held=(first, 4))
        held = {k: v[:, first:first + 4] for k, v in experts.items()}
        out, n, r, _ = ffn(fp, held, li, x, live, cut, False)
        parts.append(out - shared)
        visited, routed = visited + int(n), routed + int(r)
        c_cut = dict(c, **{experts_key: 4, "first_local_expert": first,
                           "published": {experts_key: 8}})
        ref_fp = {**fp, **{k: v[li] for k, v in held.items()}}
        ref_parts.append(ref.experts(u, ref_fp, None, c_cut)[0])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(full), atol=1e-5)
    assert visited == int(n_full) and routed == int(r_full)
    assert 0 < routed - int(r) < routed            # neither share is empty
    ref_fp = {**fp, **{k: v[li] for k, v in experts.items()}}
    ref_full = ref.experts(u, ref_fp, None, c)[0] \
        + ref.shared_expert(u, ref_fp)
    np.testing.assert_allclose(
        np.asarray(ref_parts[0] + ref_parts[1]
                   + ref.shared_expert(u, ref_fp)),
        np.asarray(ref_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(full).reshape(u.shape),
                               np.asarray(ref_full), atol=1e-5)


def layer_inputs(module, cfg, params, li, rows=24):
    """(seeded rows, layer `li`'s own weights, the stacks of experts) of
    a model whose `module` names its `_EXPERT_WEIGHTS`."""
    x = jax.random.normal(jax.random.key(3), (2, rows // 2, cfg.d_model),
                          jnp.float32)
    fp = {k: v[li] for k, v in params["ffn"].items()
          if k not in module._EXPERT_WEIGHTS}
    experts = {k: params["ffn"][k] for k in module._EXPERT_WEIGHTS}
    return x, fp, experts


def ranks_shares_add_up(fam, published, held, eps, groups=False):
    """The program's expert layer (`mla_moe._expert_ffn`) run as each rank
    of the tiny model (`published` experts, `held` a rank, the router
    `published` wide on every rank): the ranks' routed parts plus the
    shared expert counted once are the uncut reference's layer, and each
    rank's part is the reference's given that share.  `groups`: experts
    chosen group by group, whose kept groups ride behind the experts
    taken; the ranks' `group_open_rows` are then the rows that keep a
    group of theirs.  Returns (rows, the choices routed, the rows
    opened)."""
    from ray_tpu.models import mla_moe

    said = {} if groups else {"published": {"n_routed_experts": published}}
    whole = fam.config(n_routed_experts=published, **said)
    ref = spec.family(whole)
    cfg_all = ref.program_config(whole)
    assert cfg_all.experts_held is None
    params = fam.params(cfg_all)
    fp = {k: v[2] for k, v in params["ffn"].items()}
    x = jax.random.normal(jax.random.key(2), (1, 40, 64), jnp.float32)
    stacks = ("w_gate", "w_up", "w_down")
    u = ref._rms_norm(x[0], fp["norm"], eps)
    shared = ref.shared_expert(u, fp)
    uncut, margin, _ = ref.experts(u, fp, None, whole)
    if groups:
        assert float(margin.min()) > 0
    parts, routed, opened = [], 0, 0
    for first in range(0, published, held):
        share = fam.config(n_routed_experts=held, first_local_expert=first,
                           **said)
        cfg = ref.program_config(share)
        assert cfg.experts_held == (first, held)
        mine = {k: (v[first:first + held] if k in stacks else v)
                for k, v in fp.items()}
        out, visited, counts, taken = mla_moe._expert_ffn(
            {k: v for k, v in mine.items() if k not in stacks},
            {k: mine[k][None] for k in stacks}, 0, x,
            jnp.ones((1, 40), bool), cfg, True)
        if groups:
            want, _, bad = ref.experts(u, mine, taken[0][:, :3], share,
                                       groups=taken[0][:, 3:])
            here = int((taken[0][:, 3:] == first // 4).any(-1).sum())
            assert int(counts[2]) == here
            routed, opened = routed + int(counts[0]), opened + here
        else:
            want, _, bad = ref.experts(u, mine, taken[0], share)
            routed += int(counts)
        assert not bool(bad.any())
        np.testing.assert_allclose(out[0], want + shared, atol=2e-5)
        parts.append(out[0] - shared)
    assert routed == 40 * 3
    np.testing.assert_allclose(sum(parts) + shared, uncut + shared,
                               atol=6e-5)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 0.01
    return 40, routed, opened


def burst_equals_its_steps(e, held=None):
    """Lanes of unequal lengths with an idle lane between, on a sequence
    state of their own (lane i on a slot that is not i, where the state is
    by slot): the burst's tokens are its steps'.  `held`: (first, count)
    of the experts held here, for a model that counts what it routed; the
    steps then hand their routing out.  Returns (the burst's state, the
    steps', the experts the burst visited, the choices it counted or None,
    those the steps' own routing put on held experts)."""
    cfg = e.cfg
    state = decoding.init_sequence_state(cfg, 17, 8, num_slots=4,
                                         prefill_chunk=32)
    tables = jnp.asarray(np.arange(1, 17, dtype=np.int32).reshape(4, 4))
    lengths = jnp.asarray([3, 0, 9, 1], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    toks = jnp.asarray([5, 0, 7, 9], jnp.int32)
    by_slot = {"slots": jnp.asarray([2, 4, 0, 3], jnp.int32)} \
        if getattr(cfg, "state_by_slot", False) else {}
    burst = bound(decoding.paged_decode_burst, cfg,
                  static_argnames=("n_steps",))
    b_state, b_toks, _, visited, *routed = burst(
        e.params, state, toks, tables, lengths, active,
        jnp.zeros((4,), jnp.float32), jax.random.key(0), n_steps=3, **by_slot)
    step = bound(decoding.paged_decode_step, cfg,
                 **({"static_argnames": ("routing",)} if held else {}))
    live, s_toks, here = np.asarray(active), [], 0
    for _ in range(3):
        if held:
            state, logits, taken = step(e.params, state, toks, tables,
                                        lengths, active, routing=True,
                                        **by_slot)
            taken = np.asarray(taken)[:, live]             # (L, live, k)
            here += int(np.sum((taken >= held[0]) & (taken < sum(held))))
        else:
            state, logits = step(e.params, state, toks, tables, lengths,
                                 active, **by_slot)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        lengths = jnp.where(active, lengths + 1, lengths)
        s_toks.append(toks)
    assert np.array_equal(np.asarray(b_toks)[:, live],
                          np.stack(s_toks)[:, live])
    return (b_state, state, int(visited),
            int(routed[0]) if routed else None, here)


def leaves_agree(a, b, atol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=atol)


def an_idle_row_is_routed_nowhere(fam, module, ffn, cast=False):
    """`live` by row: a chunk's padded tail and an idle lane take no
    expert, hit none, and are not counted (the shared expert, a dense
    layer, runs on them all the same and nobody reads it)."""
    from ray_tpu.ops import moe

    cfg = configs.get(fam.registry)
    x, fp, experts = layer_inputs(module, cfg, fam.params(cfg, seed=0), 0)
    if cast:
        x = x.astype(cfg.compute_dtype)
    live = jnp.arange(x.shape[1])[None, :] < jnp.asarray([[5], [0]])
    out, n, r, _ = ffn(fp, experts, 0, x, live, cfg, False)
    _, n_all, r_all, _ = ffn(fp, experts, 0, x, jnp.ones_like(live), cfg,
                             False)
    assert 0 < int(r) <= 5 * cfg.expert_top_k and int(r) < int(r_all)
    assert int(n) <= int(n_all)
    h = module.rms_norm(x, fp["norm"], eps=cfg.norm_eps)
    router = {k: fp[k] for k in ("router", "router_bias") if k in fp}
    routed_part = moe.moe_mlp_dropless(h, {**router, **experts}, cfg.moe,
                                       live=live, layer=0)[0]
    assert not np.asarray(routed_part[1], np.float32).any()
    assert not np.asarray(routed_part[0, 5:], np.float32).any()


def copy_block_copies_both_pooled_leaves(cfg):
    """A block copied on write carries the latent rows and the index
    keys.  Returns (the state, the state after the copy)."""
    state = cfg.init_state(5, 8, 2, 16)
    state = dataclasses.replace(
        state, kv=state.kv.at[:, 1].set(1.0), idx=state.idx.at[:, 1].set(2.0))
    out = decoding.copy_block(state, jnp.int32(3), jnp.int32(1))
    assert float(out.kv[:, 3].min()) == 1.0 == float(out.kv[:, 1].min())
    assert float(out.idx[:, 3].min()) == 2.0
    assert float(out.idx[:, 2].max()) == 0
    return state, out


def prefix_shared_and_both_leaves_shipped(src, dst, prompt, max_tokens, rid):
    """A model that selects, its blocks alone the sequence: a second
    request hits the first one's prefix (latent rows and index keys), and a
    stream's blocks shipped to another engine as a frame of both leaves
    side by side (128 + 16 wide) are adopted there, where the prompt then
    hits them and streams what it streamed at home.  Parks `src`."""
    from burst_ahead_cases import run_until_done, submit, tick

    first = src.generate(prompt, max_tokens=max_tokens)
    hits = src.stats["prefix_hits"]
    assert src.generate(prompt, max_tokens=max_tokens) == first
    assert src.stats["prefix_hits"] == hits + 1
    park(src)
    req = submit(src, prompt, max_tokens, stream=True)
    req.trace = {"trace_id": rid}
    for _ in range(50):
        tick(src)
        if len(req.out_tokens) >= 4:
            break
    (ticket,) = src.export_streams()
    n_kv = len(ticket["tokens"])
    kv = np.asarray(ticket["kv"])
    assert kv.shape == (1, src.cfg.n_layers, -(-n_kv // 8), 8, 128 + 16)
    assert kv[..., 128:].any()                   # the index keys ride
    assert dst.import_prefix(ticket["tokens"], kv[..., :128], 8) == 0
    assert dst.import_prefix(ticket["tokens"], kv, 8) == -(-n_kv // 8)
    hits = dst.stats["prefix_hits"]
    assert dst.generate(prompt, max_tokens=max_tokens) == first
    assert dst.stats["prefix_hits"] == hits + 1
    run_until_done(src, [req])
    assert req.out_tokens == first


def prefill_alone(cfg, params, tokens, size, pad_with=0,
                                  chunk=32, blocks=8):
    """`tokens` through `paged_prefill_chunk` in launches of `size` rows
    (of `chunk` positions a chunk) on a state of its own (slot 1 of two,
    where it is by slot), a table of `blocks` pages; a last launch is
    padded to `size` with `pad_with`.  Returns (state, last logits)."""
    state = cfg.init_state(max(17, blocks + 1), 8, 2, chunk)
    run = bound(decoding.paged_prefill_chunk, cfg)
    table = jnp.arange(1, blocks + 1, dtype=jnp.int32)
    slot = {"slot": jnp.int32(1)} if cfg.state_by_slot else {}
    for start in range(0, len(tokens), size):
        toks = np.full((size,), pad_with, np.int32)
        nv = min(size, len(tokens) - start)
        toks[:nv] = tokens[start:start + nv]
        state, last, *_ = run(params, state, jnp.asarray(toks), table,
                              jnp.int32(start), jnp.int32(nv), **slot)
    return state, last


def a_slot_reused_by_a_second_request(fam, e, c, lengths=(60, 45)):
    """Two requests one after the other, the second in slot 0 again: both
    streams are the reference's greedy ones and no prefix is hit.  Returns
    (the prompts, what the engine's statistics gained, its ticks)."""
    prompts = [prompt(n, 11 + i) for i, n in enumerate(lengths)]
    since = Since(e)
    for tokens in prompts:
        out = e.generate(tokens, max_tokens=6)      # slot 0, both
        assert len(out) == 6 and fam.is_greedy(e, c, tokens, out)
    stats = since.stats()
    assert stats["prefix_hits"] == 0
    return prompts, stats, since.ticks(stats)


# -- faults put into a built engine (`on_the_engine`) ------------------------------
def as_float8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def score_keeps(e, monkeypatch, keep):
    """What `score`'s two programs leave in the cache goes through
    `keep(cache)` (rounded to 8-bit floats, say) before the next reads
    it."""
    e.score(np.ones((1, 9), np.int64), 8, routing=True)   # builds them
    for name in ("_score_chunk", "_score_step"):
        inner = getattr(e, name)

        def program(*a, _inner=inner, **kw):
            cache, *rest = _inner(*a, **kw)
            return (keep(cache), *rest)

        monkeypatch.setattr(e, name, program)


def program_with(e, ref, monkeypatch, params):
    """The program runs on `params`, the reference on the stated ones."""
    stated, plain = e.params, ref.forward
    monkeypatch.setattr(e, "params", params)
    monkeypatch.setattr(ref, "forward",
                        lambda p, *a, **kw: plain(stated, *a, **kw))


# -- other models lower to the programs they lowered to ----------------------------
SMALL_SHAPES = dict(lanes=4, pages=8, chunk=32, blocks=17, block_size=8,
                    drafts=3)
WIDE_SHAPES = dict(lanes=8, pages=16, chunk=64, blocks=33, block_size=16,
                   drafts=4)


def lowered_digest(name, program, *, lanes, pages, chunk, blocks, block_size,
                   drafts, state=True):
    """sha256 (16 hex digits) of the StableHLO text (`lowered.as_text()`:
    no locations) of one served program of the preset `name`, lowered for
    abstract arguments of the shapes given: "chunk", "burst" (`lanes`
    steps), "copy_block", "verify" (`drafts` candidates a lane),
    "score_step" (the step `score` runs, its routing handed out) or
    "forward" (`transformer.forward` over (2, 64) tokens).  `state`
    False: the pool alone (`init_paged_cache`), as before sequences had
    other state.  A family's file holds the digests it took on its
    parent's tree: a PR that means to change these programs replaces
    them and says so."""
    import hashlib

    cfg = configs.get(name)
    own = getattr(cfg, "init_params", None)
    params = jax.eval_shape(
        lambda: own(jax.random.key(0)) if own
        else models.init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(
        lambda: decoding.init_sequence_state(
            cfg, blocks, block_size, num_slots=lanes, prefill_chunk=chunk)
        if state else decoding.init_paged_cache(cfg, blocks, block_size))
    chunk_fn, burst, _ = decoding.make_paged_engine_fns(cfg)
    by_slot = getattr(cfg, "state_by_slot", False)
    key = jax.eval_shape(lambda: jax.random.key(0))

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    tables = (arr(lanes, pages), arr(lanes), arr(lanes, dtype=jnp.bool_))
    sampled = (arr(lanes, dtype=jnp.float32), key)
    slots = {"slots": arr(lanes)} if by_slot else {}
    if program == "chunk":
        lowered = chunk_fn.lower(params, cache, arr(chunk), arr(pages), arr(),
                                 arr(), **({"slot": arr()} if by_slot else {}))
    elif program == "burst":
        lowered = burst.lower(params, cache, arr(lanes), *tables, *sampled,
                              n_steps=lanes, **slots)
    elif program == "copy_block":
        lowered = jax.jit(decoding.copy_block).lower(cache, arr(), arr())
    elif program == "verify":
        lowered = decoding.make_paged_spec_fns(cfg).lower(
            params, cache, arr(lanes, drafts), *tables, *sampled)
    elif program == "score_step":
        step = jax.jit(decoding._bind_cfg(decoding.paged_decode_step, cfg),
                       static_argnames=("routing",))
        lowered = step.lower(
            params, cache, arr(lanes), *tables, **slots,
            **({"routing": True} if cfg.n_experts else {}))
    else:
        from ray_tpu.models.transformer import forward

        lowered = jax.jit(lambda p, t: forward(p, t, cfg)).lower(
            params, arr(2, 64))
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
