"""What the tests of the engine's run-ahead tick share (no test of its
own): a `PagedLLMEngine` whose loop thread is parked so that the test
steps `_tick()` itself, requests put straight onto its queue, the
step-by-step reference of a greedy stream, and the scenario in which
requests of different `max_tokens` join and leave mid-stream, so that the
lane map and the width tier change between consecutive bursts."""
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import decoding
from ray_tpu.serve.llm import _Request


def park(e):
    """Stop `e`'s loop thread: ticks run only when the test calls
    `tick`, so what is in flight between them is the test's to see."""
    e._stop = True
    e._work.set()
    e._thread.join(timeout=30)
    assert not e._thread.is_alive()
    return e


def tick(e, n=1):
    for _ in range(n):
        with e._tick_lock:
            e._tick()


def submit(e, prompt, max_tokens, temperature=0.0, stream=False):
    req = _Request(list(map(int, prompt)), max_tokens, temperature,
                   stream=stream)
    e._obs_submit(req, None)
    e.stats["requests"] += 1
    e._pending_put(req)
    return req


def run_until_done(e, reqs, limit=400):
    for _ in range(limit):
        if all(r.done.is_set() for r in reqs):
            return
        tick(e)
    raise AssertionError("requests never finished")


def _jitted(fn, cfg):
    return jax.jit(decoding._bind_cfg(fn, cfg))


def step_reference(e, prompt, n_tokens, bound=_jitted):
    """The greedy continuation of `prompt` one step at a time: the
    prompt through `paged_prefill_chunk`, then `paged_decode_step` on one
    lane, on a sequence state of its own.  Nothing of the engine's tick,
    its lane map or its burst.  `bound(fn, cfg)`: the jitted `fn` of
    `cfg` (a fresh one; `served_contract.bound` remembers)."""
    cfg = e.cfg
    state = decoding.init_sequence_state(
        cfg, e._b_max + 1, e.block_size, num_slots=1,
        prefill_chunk=e.prefill_chunk)
    table = jnp.arange(1, e._b_max + 1, dtype=jnp.int32)
    chunk = bound(decoding.paged_prefill_chunk, cfg)
    step = bound(decoding.paged_decode_step, cfg)
    by_slot = getattr(cfg, "state_by_slot", False)
    prompt = list(map(int, prompt))
    for start in range(0, len(prompt), e.prefill_chunk):
        toks = np.zeros((e.prefill_chunk,), np.int32)
        nv = min(e.prefill_chunk, len(prompt) - start)
        toks[:nv] = prompt[start:start + nv]
        state, last, *_ = chunk(
            e.params, state, jnp.asarray(toks), table, jnp.int32(start),
            jnp.int32(nv), **({"slot": jnp.int32(0)} if by_slot else {}))
    out = [int(jnp.argmax(last))]
    lanes_kw = {"slots": jnp.zeros((1,), jnp.int32)} if by_slot else {}
    while len(out) < n_tokens:
        state, logits = step(
            e.params, state, jnp.asarray(out[-1:], jnp.int32), table[None],
            jnp.asarray([len(prompt) + len(out) - 1], jnp.int32),
            jnp.ones((1,), bool), **lanes_kw)
        out.append(int(jnp.argmax(logits[0])))
    return out


def ticks_of(e):
    stats = e.engine_stats()
    return [dict(zip(stats["tick_fields"], t)) for t in stats["tick_log"]]


def run_join_and_leave(e, vocab=500):
    """Three requests decode at width 4; three short ones join them
    (width 8: two bursts of six lanes, one of five) and leave again
    (width 4 again).  `e`: parked, >= 6 slots, a chunk that holds the
    three prompts.  Returns the requests, all finished."""
    rng = np.random.default_rng(3)
    burst = e.max_burst

    def prompt(n):
        return rng.integers(1, vocab, (n,))

    first = [submit(e, prompt(7), 5 * burst + 3),
             submit(e, prompt(4), 5 * burst + 1),
             submit(e, prompt(9), 6 * burst)]
    tick(e, 2)            # prompts prefilled, the first burst on its way
    assert all(not r.prefilling for r in first)
    assert e._inflight is not None
    late = [submit(e, prompt(5), 2 * burst + 2),
            submit(e, prompt(3), 2 * burst),
            submit(e, prompt(6), 3 * burst - 1)]
    run_until_done(e, first + late)
    assert e._inflight is None and all(r is None for r in e._slots)
    return first + late


def join_and_leave(e, vocab=500, bound=_jitted):
    """`run_join_and_leave`, each stream held to the step-by-step
    reference, and the tick log to the scenario: the tiers go 4, 8, 4,
    and every burst but the first is launched while the one before it is
    unread.  Returns the widths of the ticks that launched a burst."""
    n_logged = len(ticks_of(e))
    for r in run_join_and_leave(e, vocab):
        assert r.error is None and len(r.out_tokens) == r.max_tokens
        assert r.out_tokens == step_reference(e, r.prompt, r.max_tokens,
                                              bound)
    assert e.allocator.snapshot()["blocks_active"] == 0
    launched = [t for t in ticks_of(e)[n_logged:] if t["lanes"]]
    # a busy period's first burst has none before it; every other has
    assert [t["ahead"] for t in launched] == [0] + [1] * (len(launched) - 1)
    widths = [t["width"] for t in launched]
    changes = [w for i, w in enumerate(widths)
               if i == 0 or w != widths[i - 1]]
    assert changes == [4, 8, 4], widths
    return widths
