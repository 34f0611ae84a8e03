"""Continuous-batching LLM engine for TPU serving.

`PagedLLMEngine` (generate / generate_stream / engine_stats): KV lives in
a flat pool of fixed-size blocks (models/decoding.py PagedKVCache); each
request holds a block table, blocks are allocated on demand
(serve/kv_cache.py KVBlockAllocator), shared between requests with a
common prompt prefix (refcounted copy-on-write), and long prompts prefill
in chunks interleaved with decode bursts so active streams' inter-token
latency stays bounded during prefill storms.  Concurrency is bounded by
pool occupancy, not slot count.  Given a mesh, the same programs run
tensor-parallel: parameters and the pool's KV heads split over `tp`.

Use standalone or as a Serve deployment (`LLMDeployment`) — replicas
each own an engine; the pow-2 router spreads requests.
"""
from __future__ import annotations

import contextlib
import functools
import math
import queue
import threading
import time
import uuid
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu.util import compile_cache, tracing


# Canonical home is the typed error tree (the wire-typed-errors lint
# rule keeps every boundary-crossing error there); re-exported here for
# the historical import path.
from ray_tpu.exceptions import StreamQueueFullError  # noqa: F401


class _Request:
    __slots__ = ("prompt", "max_tokens", "temperature", "out_tokens",
                 "done", "error", "slot", "submitted_at", "admitted_at",
                 "prefill_at", "first_token_at", "prefill_span", "chunks",
                 "chunk_s", "chunk_tokens", "token_q", "dropped", "blocks",
                 "pos", "prefilling", "no_register", "trace",
                 "last_emit_wall", "ahead", "record", "decode_from",
                 "decode_span", "lanes_sum", "bursts", "given")

    def __init__(self, prompt, max_tokens, temperature, stream=False):
        from ray_tpu.core.config import get_config

        self.prompt = prompt
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.out_tokens: List[int] = []
        self.done = threading.Event()
        self.error: Optional[BaseException] = None
        self.slot = -1
        # The edges of the request's phases up to its first token, all
        # on time.time() (the clock of the spans and of a client's own
        # stamps): submitted -> admitted into a slot (queue_wait) ->
        # launch of its first prefill chunk (prefill_wait) -> first
        # token (the prefill span).  Contiguous, so the three phases
        # sum to the engine's TTFT exactly.
        self.submitted_at = time.time()
        self.admitted_at: Optional[float] = None
        self.prefill_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        # The open `serve.engine.prefill` span (None when tracing is
        # off) and what its chunks summed to: their count, their own
        # wall time, their tokens.
        self.prefill_span: Optional[tracing.Span] = None
        self.chunks = 0
        self.chunk_s = 0.0
        self.chunk_tokens = 0
        # Serve trace context ({"trace_id": <request id>, ...}, None when
        # tracing is off) — engine tick spans parent under it.
        self.trace: Optional[dict] = None
        self.last_emit_wall: Optional[float] = None
        # Streaming consumers read tokens as the engine emits them.
        # BOUNDED: a consumer that stops reading must not grow replica
        # RSS without limit — at the bound the stream drops with an
        # explicit error (the engine frees the slot/blocks).
        self.token_q: Optional["queue.Queue"] = (
            queue.Queue(maxsize=max(1, get_config().serve_stream_queue_max))
            if stream else None)
        self.dropped = False
        self.blocks: List[int] = []   # paged engine: owned pool blocks
        self.pos = 0                  # paged engine: tokens prefilled
        self.prefilling = True        # paged engine: not yet decoding
        # Tokens of this request in a burst that is launched and not yet
        # read: the host has counted them, only the device holds them.
        self.ahead = 0
        # From the first token on: the request's `request_phases` record
        # (completed at its end), the phase clock's reading at the first
        # token, the open `serve.engine.decode` span (None when tracing
        # is off), and the lanes of the bursts it was read from, summed.
        self.record: Optional[dict] = None
        self.decode_from: Optional[List[float]] = None
        self.decode_span: Optional[tracing.Span] = None
        self.lanes_sum = self.bursts = 0
        # A model that generates by diffusion over blocks: the tokens of
        # the context behind its last whole block, which stand as given
        # rows of the next block the request fills (`_begin_decode`).
        self.given: List[int] = []
        # Resumed contexts embed generated tokens in `prompt` — never
        # publish them as a reusable prompt prefix.
        self.no_register = False

    def emit(self, tok: int) -> None:
        self.out_tokens.append(tok)
        if self.token_q is not None and not self.dropped:
            try:
                self.token_q.put_nowait(tok)
            except queue.Full:
                self.dropped = True
                self.error = StreamQueueFullError(
                    f"stream consumer fell {self.token_q.maxsize} tokens "
                    f"behind; stream dropped "
                    f"(RAY_TPU_SERVE_STREAM_QUEUE_MAX)",
                    queue_max=self.token_q.maxsize)


# One record of engine_stats()["tick_log"], in this order (the stats
# carry the names as "tick_fields", so a reader needs no copy of them).
TICK_FIELDS = ("start", "tick_s", "decode_s", "prefill_s", "sample_s",
               "lanes", "width", "prefill_tokens", "routed_here",
               "kv_read_tokens", "reset_s", "experts_read", "ahead",
               "starved_s", "moe_tiles", "index_scored_tokens",
               "kv_selected_tokens", "blocks", "passes", "block_tokens",
               "ring_slots")
# Behind them in the records of a model whose residual is several streams,
# and of no other (`engine_stats()["tick_fields"]` says which a log has).
_HC_RES_DEFECT = "hc_res_defect"
# Before it in the records of a model that holds a share of experts chosen
# group by group, and of no other.
_GROUP_OPEN_ROWS = "group_open_rows"
# Behind them in the records of a model whose layers attend to a learned
# selection, and of no other: 100 x the reads of its burst that took the
# selection as a mask.
_SELECT_MASKED = "select_masked"
# Behind them in the records of a model with linear layers, and of no
# other: the state rows (a lane's state in one linear layer) one step of
# the tick's burst read, and wrote as many, and the chunks of the rule its
# prefill launches carried (rows over `linear_chunk`, a launch's padded
# tail counted: the program runs it).
_LINEAR_STATE = ("linear_state_rows", "delta_chunks")
# Behind them in the records of a model with conv layers, and of no other:
# the rows of state (a lane's kept rows in one conv layer) one step of the
# tick's burst read, and wrote as many.
_CONV_STATE = "conv_state_rows"
# Behind them in the records of a model whose stack runs more than once,
# and of no other: the layers one step of the tick's burst ran a lane, the
# passes x the layers (0 for a tick without a burst).
_LOOP_PASSES = "loop_passes"
# What a request's record gains at its end (None until then).
_DECODE_KEYS = ("decode_s", "n_out", "burst_read_s", "first_read_s",
                "host_s", "lanes_seen")
_EXPERTS_READ = TICK_FIELDS.index("experts_read")
_ROUTED_HERE = TICK_FIELDS.index("routed_here")
_MOE_TILES = TICK_FIELDS.index("moe_tiles")


class _TickAccounts:
    """What one paged-engine tick did, written by its phases from the
    clock reads they take anyway (nothing per lane or per token) and
    folded into one tick-log record by PagedLLMEngine._tick."""
    __slots__ = ("decode_s", "prefill_s", "sample_s", "lanes", "width",
                 "prefill_tokens", "kv_read_tokens", "reset_s",
                 "routed_at", "defect_at", "experts_read", "ahead",
                 "starved_s",
                 "index_scored_tokens", "kv_selected_tokens", "blocks",
                 "passes", "block_tokens", "ring_slots", "select_masked",
                 "linear_state_rows", "delta_chunks", "conv_state_rows")

    def __init__(self):
        self.decode_s = self.prefill_s = self.sample_s = self.reset_s = \
            self.experts_read = self.starved_s = self.select_masked = 0.0
        self.lanes = self.width = self.prefill_tokens = 0
        self.kv_read_tokens = self.ahead = 0
        self.index_scored_tokens = self.kv_selected_tokens = 0
        self.blocks = self.passes = self.block_tokens = self.ring_slots = 0
        self.linear_state_rows = self.delta_chunks = 0
        self.conv_state_rows = 0
        # Where the tick's `routed_here` and `moe_tiles` are summed on the
        # device (`_count_routed`); -1: the tick launched nothing that
        # counts.
        self.routed_at = -1
        # Likewise where the largest defect of the tick's stream mixes is
        # kept (`hc_res_defect`); -1: the tick launched nothing that mixes.
        self.defect_at = -1


class _Burst:
    """A decode burst that is launched and not yet read, and what its
    read needs: the token matrix, the count of experts visited and, from
    a model that selects positions, of selections read as a mask (all
    still on the device), lane by lane the request, the tokens the host
    counted for it at the launch, whether they are its last and how many
    of the lane's tokens come before them (the given rows of a block: 0
    for a next-token model), the
    launch's time and its number among the engine's launches, and the
    record of the tick that launched it, which enters the tick log at
    the read, when `experts_read` and `select_masked` are known."""
    __slots__ = ("tok_mat", "visited", "masked", "lanes", "t0", "seq", "row")

    def __init__(self, tok_mat, visited, masked, lanes, t0, seq):
        self.tok_mat, self.visited, self.masked = tok_mat, visited, masked
        self.lanes, self.t0, self.seq = lanes, t0, seq
        self.row: Optional[list] = None


# The leaf phases of the engine's loop thread, in the order of
# engine_stats()["phase_seconds"] (see _PhaseClock).
PHASES = ("wait", "admit", "burst_launch", "burst_read", "emit",
          "chunk_launch", "first_read", "book")
(_WAIT, _ADMIT, _BURST_LAUNCH, _BURST_READ, _EMIT, _CHUNK_LAUNCH,
 _FIRST_READ, _BOOK) = range(len(PHASES))


class _PhaseClock:
    """Where the engine's loop thread spends its time.  The thread is at
    every moment in exactly one leaf of PHASES; `enter` credits the time
    since the last switch to the leaf that ends.  A leaf is at once an
    entry of `seconds` (cumulative, on time.time(), the clock of the
    records and the spans) and a `jax.profiler.TraceAnnotation` named
    `serve.engine.phase.<leaf>`: an event on the device trace's clock
    whenever anyone profiles the process, one check of an atomic when
    nobody does.  The leaves are flat: the open annotation is closed
    before the next is opened, so the seconds sum to the thread's wall
    time and a reduction that charges an idle gap to every span over it
    charges it once.

      wait          `_work.wait` with nothing to do, and taking the tick
                    lock (another thread holds it: score, warmup, ...)
      admit         the `_admit_one` loop
      burst_launch  `_decode_tick` up to the read: blocks, lane arrays,
                    the dispatch of the burst (or the verify window),
                    the host's books of the launch
      burst_read    the blocking read of a burst's tokens
      emit          tokens to their requests: the loop over a read
                    burst's lanes, a first token's `_begin_decode`,
                    `_finish`
      chunk_launch  `_prefill_tick` up to and including the chunk's
                    dispatch and `_obs_prefill`
      first_read    a finished prompt's `_sample_one` and `int(tok)`
      book          the rest of a tick: prefix registration, the tail
                    block's copy, preemption, the tick's record

    The clock moves only inside `_tick` (`ticking`): what another thread
    does under the tick lock (`score`, `warmup`, `import_prefix`,
    `export_streams`, `shutdown`'s last read) switches nothing, and the
    loop thread is in `wait` meanwhile."""
    __slots__ = ("seconds", "leaf", "since", "ticking", "note", "_open")

    def __init__(self, note):
        self.seconds = [0.0] * len(PHASES)
        self.leaf, self.since, self.ticking = _WAIT, time.time(), False
        self.note, self._open = note, None

    def enter(self, leaf: int) -> float:
        """Switch to `leaf`; returns the time of the switch."""
        now = time.time()
        if not self.ticking:
            return now
        self.seconds[self.leaf] += now - self.since
        self.since = now
        if leaf != self.leaf:
            if self._open is not None:
                self._open.__exit__(None, None, None)
            self._open = self.note("serve.engine.phase." + PHASES[leaf])
            self._open.__enter__()
            self.leaf = leaf
        return now

    def read(self, now: float) -> List[float]:
        """The seconds by leaf as at `now`: the running leaf's part
        included, so two readings differ by exactly the time between
        them."""
        out = list(self.seconds)
        out[self.leaf] += now - self.since
        return out


def _slot_state(cfg) -> str:
    """What a sequence of `cfg` keeps by slot, for a refusal's message."""
    return ("recurrent state" if getattr(cfg, "recurrent", False)
            else "a ring of window KV")


def _snapshot(log: deque) -> tuple:
    """A foreign thread's copy of a bounded log its one owner thread
    appends to meanwhile (engine_stats() runs on the replica's gauge
    thread every second): references only, microseconds, and taken
    again if an append landed inside the copy."""
    while True:
        try:
            return tuple(log)
        except RuntimeError:     # deque mutated during iteration
            continue


# The rows one `paged_prefill_chunk` launch may carry, for a model whose
# sequences keep only paged KV.  A launch streams every weight once,
# whatever its rows: at 128 rows a bf16 weight does 128 multiply-adds
# where a v5e balances at ~240 (197 TFLOP/s over 819 GB/s), so the launch
# waits for memory and a 3,000-token prompt reads the model 24 times.
# Measured (PR 37, one TPU v5e, the bare jitted program at published
# widths, median of 7 launches each waited for: ms a launch, the host's
# round trip of ~1.1 ms included; us a token beside it):
#
#          Mistral-7B widths, depth 8 (3.8 GB a launch)   Mixtral-8x7B, depth
#   rows   start 0       2,048        3,584-3,840         3 (9.2 GB), start 0
#     32    7.67 (240)    8.15 (255)   8.45 (264)         12.78 (399)
#     64    7.78 (122)    8.22 (128)   8.51 (133)         13.72 (214)
#    128    7.95 (62.1)   8.52 (66.6)  9.05 (70.7)        14.00 (109)
#    256    8.91 (34.8)   9.77 (38.2) 10.72 (41.9)        15.96 (62.3)
#    512   14.55 (28.4)  16.05 (31.4) 16.85 (32.9)        27.55 (53.8)
#   1024   26.29 (25.7)  28.76 (28.1) 30.01 (29.3)        52.12 (50.9)
#
#   one decode burst of 8 steps at width 4, ms (a step): Mistral 1 lane at
#   500 / 3,000 positions 47.1 (5.89) / 55.4 (6.92), 3-4 lanes the same;
#   Mixtral 1 lane 35.6 (4.45) / 38.6 (4.83), 3-4 lanes 52.9-56.8
#
# (Mixtral's chunk multiplies every row by every expert that any row is
# routed to, all 8 at start 0 as in traffic; at later starts, over a pool
# of like rows, fewer were hit and a launch of 32-256 rows read 8-13 ms.)
# (i) A token's time falls by 41-44% from 128 to 256 rows, by 14-21% from
# 256 to 512, and by 4-8% of the device's own time from 512 to 1,024 (5-11%
# with the round trip), in both shapes: past 512 rows it has stopped
# falling (less than a tenth a doubling), while the launch a decoding lane
# waits behind doubles.  (ii) Beside a burst a launch may not take the
# device longer than the burst: a decode step is worth 83 rows of a
# 512-row launch at Mixtral's widths (4.45 ms over 53.8 us) and 179 at
# Mistral's (5.89 over 32.9), so 64 rows a step of the burst is safe in
# both, and 512 rows beside 8 steps read 14.6-27.6 ms beside 35.6-55.4.
_CHUNK_TOP_ROWS = 512   # (i) past it the device's time a token stops falling
_ROWS_A_STEP = 64       # (ii) prompt rows that cost no more than a decode step


class _SetupSpans:
    """A replica's start as span records (`tracing.Span.finish()`'s
    shape, one `trace_id`), kept by its engine and read through
    `engine_stats()["setup"]`: `serve.setup` (root: `LLMDeployment`'s
    constructor) over `.device_init`, `.params`, `.engine_build`,
    `.warmup` and, one a launch of `warmup()`, `.warmup.tier`.  An
    engine built on its own has the last three and no root.  Where
    serve tracing is on the records go to the tracing buffer as every
    span does (`ray-tpu timeline` then shows the start beside the
    requests); the kill switch silences that sink alone.  All on
    `time.time()`, the compile log's clock (`util/compile_cache.py`)."""
    SPANS_KEPT = 256        # a start is 5 spans and one a tier

    def __init__(self):
        self.trace_id = "setup-" + uuid.uuid4().hex[:12]
        self.root: Optional[tracing.Span] = None
        self.records: deque = deque(maxlen=self.SPANS_KEPT)

    def open(self, name: str, parent: Optional[tracing.Span] = None,
             **attrs) -> tracing.Span:
        """A span that starts now, under `parent` or else the root."""
        parent = parent or self.root
        return tracing.Span(
            name, self.trace_id,
            parent.span_id if parent is not None else None, attrs)

    def close(self, span: tracing.Span, **attrs) -> None:
        span.attrs.update(attrs)
        self.records.append(span.finish(buffered=tracing.serve_enabled()))

    @contextlib.contextmanager
    def span(self, name: str, parent: Optional[tracing.Span] = None,
             **attrs):
        """`open` and `close` around a block that completes."""
        s = self.open(name, parent, **attrs)
        yield s
        self.close(s)


class PagedLLMEngine:
    """Paged/block KV-cache engine: the one served engine.

    Engine tick: [admit waiting requests] -> [launch one fused decode
    burst over every DECODING slot] -> [read the burst launched a tick
    ago, emit its tokens] -> [one prefill launch for the oldest
    PREFILLING slot, as wide as the tick's budget].  Decode never waits
    for a whole prompt: a max-length prompt occupies one launch of
    device time per tick, and beside a burst that launch is held under
    the burst's own time (`_prefill_budget`), bounding the inter-token
    latency of active streams.

    `prefill_chunk` is the unit and the floor of that budget, and the
    widest launch of a model whose slots keep rings (they hold window +
    prefill_chunk rows).  A model whose sequences are pool blocks alone
    is given wider tiers above it, in powers of two, up to the rows that
    make a launch compute-bound: at `prefill_chunk` = 128 a launch
    streams every weight to multiply 128 rows by it.  So is a model
    with state by slot that says a launch's rows lay none of it out
    (`launch_spans_chunks`: a state-space model whose program takes
    m x `prefill_chunk` rows as m chunks and hands the state from one
    to the next itself); for it `prefill_chunk` is the positions of one
    chunk of its recurrence, and no tier under it is built (`__init__`
    says why).

    The host runs one burst ahead of its own reads, never more: the
    next burst needs nothing the host has to read first.  Lengths,
    tables and the lane set are the host's own arithmetic, advanced at
    the launch; a request that ends by `max_tokens` or `max_len` ends at
    a step known before its last burst runs, and leaves its slot at that
    launch (its blocks go at the read); each lane's input token is the
    last one its slot sampled, kept on the device (`_last_dev`, by slot)
    and gathered there.  So while one burst runs the next is queued
    behind it, and a tick lasts as long as the device's work or the
    host's, whichever is longer.  Only EOS and a dropped stream are
    learnt from the tokens, one burst late: the burst ahead's tokens for
    such a lane are dropped at their read, matched to the request and
    not to the slot.  Whatever needs the tokens on the host reads the
    burst in flight first (`_drain`): speculation (drafts come from the
    emitted context, so with `speculation_k` the engine reads every
    burst in the tick that launched it), `_preempt`, `score`, `warmup`,
    `export_streams`, `import_prefix`, `shutdown`.

    Admission: a request needs pool blocks covering its (non-shared)
    prompt remainder.  When the pool can't cover it, the request WAITS
    at the head of the queue (no error) until completions free blocks.

    The state of a sequence is asked of the model
    (`models.decoding.init_sequence_state`), and has up to three kinds:

    - paged KV (`kv_paged`): every position of the layers that keep
      them all, in pool blocks the `KVBlockAllocator` hands out and a
      block table names, rows of the model's own shape (K and V by
      head; one latent row for `models.mla_moe`, which brings its own
      state and step and keeps nothing else).  Every model has it; for
      a `TransformerConfig` and for such a model it is all there is,
      and the blocks *are* the sequence: prefix sharing, copy-on-write,
      speculation (a rejected draft is rolled back by length alone),
      `export_streams` / `import_prefix` all rest on that, and act on
      whatever leaves the state pools (`models.decoding.pooled_leaves`).
    - bounded window KV (`kv_window`): a ring of window + prefill_chunk
      positions a slot for each sliding-window layer, indexed by the
      engine's slot, owned by whoever holds the slot.  State by slot
      that is not recurrent: a row is seen only at the position it was
      last written for, so nothing is zeroed when a slot changes hands
      (a stale row lies at a position the new sequence has not reached,
      and is masked) or when `_preempt` sends a stream to re-prefill.
    - recurrent state (`recurrent`): a state-space layer's conv rows
      and state by slot.  It is zeroed when a request is admitted to the
      slot (`_reset_slot_state`, counted in the tick's `reset_s`),
      carried from chunk to chunk of a prefill, left untouched by a
      chunk's padded tail and by idle lanes, and zeroed again when
      `_preempt` sends a stream to re-prefill.

    Two questions are asked of the configuration.  `state_by_slot`
    (rings or recurrent state): the blocks are not the sequence, so the
    engine turns prefix sharing off itself (a hit would skip positions
    whose state nobody kept), hands the served programs the lanes'
    slots, and refuses, with a ValueError, a mesh at construction and
    `export_streams` / `import_prefix` when called: snapshots of state
    are what each would need.  `recurrent`: state is zeroed at admission
    and preemption as above.  `speculation_k >= 2` is refused for
    either: a rejected draft has advanced a recurrence, and has written
    ring rows that no test yet shows are never seen.

    `diffusion_block` (B > 0: a model that generates by diffusion over
    blocks, `models.decoding.paged_denoise_burst`): a step no longer
    yields one token a sequence.  A prompt is prefilled in whole blocks
    of B and nothing is sampled from it; its last len % B tokens stand
    as given rows of the first block the request fills
    (`_begin_decode`).  A decode tick launches, for every decoding lane,
    `max_burst // B` blocks of `denoise_steps` + 1 forward passes each
    and counts up to `max_burst` tokens a lane from them (fewer where
    given rows or `max_tokens` cut a block), lengths are whole blocks
    throughout, and **the first token comes from the first burst's
    read**, where `ttft_s` ends.  The burst's inputs are the host's own
    (given rows, else the mask token), so bursts run ahead as any
    other's.  A registered prefix is the prompt's whole pages (a page
    is whole blocks: `block_size % B == 0`) and carries no logits.
    `speculation_k >= 2` (a draft is verified against next-token
    logits, which this model has none of), a mesh, `export_streams` /
    `import_prefix` and prefill offload are refused with the reason.

    A third, of a
    model with experts: does it hold one rank's share of them
    (`models.decoding.counts_routed`)?  Its chunk and burst then hand
    out the top-k choices that fell on the share, and a tick's record
    carries their sum (`routed_here`, `_count_routed`).  A fourth: is
    its residual several streams (`models.decoding.counts_defect`)?
    Its chunk and burst then hand out, last, how far their stream mixes
    stopped from the doubly stochastic matrices, and a tick's record
    carries the largest (`hc_res_defect`).
    """
    TICKS_KEPT = 4096

    def __init__(self, cfg, params, *, num_slots: int = 32,
                 max_len: int = 1024, block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 eos_id: Optional[int] = None, seed: int = 0,
                 max_burst: int = 8, prefix_sharing: Optional[bool] = None,
                 speculation_k: Optional[int] = None,
                 speculation_ngram: Optional[int] = None,
                 store=None, mesh=None,
                 setup: Optional[_SetupSpans] = None):
        """`setup`: the spans of the start this engine is part of
        (`LLMDeployment` hands its own over); None: the engine's own."""
        self._setup = setup or _SetupSpans()
        build_span = self._setup.open("serve.setup.engine_build")
        import jax
        import jax.numpy as jnp

        from ray_tpu.core.config import get_config
        from ray_tpu.models.decoding import (
            counts_defect,
            counts_groups,
            counts_masked,
            counts_routed,
            init_sequence_state,
            make_paged_engine_fns,
            make_paged_spec_fns,
            paged_cache_shardings,
            put_last,
            sample_one,
            take_last,
        )
        from ray_tpu.ops.attention import ring_slots_read
        from ray_tpu.serve.kv_cache import KVBlockAllocator

        knobs = get_config()
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.block_size = block_size or knobs.kv_block_size
        # Default pool budget: every slot at max_len, +1 for the null
        # block.
        self.num_blocks = (num_blocks or knobs.kv_block_count
                           or (num_slots * max_len) // self.block_size + 1)
        self.prefill_chunk = prefill_chunk or knobs.serve_prefill_chunk
        # Shape tiers (power-of-two) keep device work proportional to
        # LOAD, not capacity: a burst over 3 active streams runs at
        # width 4, not num_slots; a 16-token chunk compiles at width 32,
        # not prefill_chunk.  One compile per tier.
        self._width_tiers = self._tiers(4, num_slots)
        self._chunk_tiers = self._tiers(32, self.prefill_chunk)
        self.eos_id = eos_id
        self.max_burst = max(1, max_burst if eos_id is None else
                             min(max_burst, 4))
        # A model that generates by diffusion over blocks of `_block`
        # positions (0: a next-token model): a burst is whole blocks, a
        # launch's rows and a page whole blocks too, and what a forward
        # pass is to a burst's count of experts visited differs
        # (`_burst_passes`: a step; a block's passes).
        self._block = int(getattr(cfg, "diffusion_block", 0))
        self._burst_passes = self.max_burst
        if self._block:
            b = self._block
            self.max_burst = max(b, self.max_burst // b * b)
            self._burst_passes = (self.max_burst // b
                                  * (cfg.denoise_steps + 1))
            misfit = [n for n in [self.block_size, *self._chunk_tiers]
                      if n % b]
            if misfit:
                raise ValueError(
                    f"{cfg.name!r} fills blocks of {b} positions: a page "
                    f"(block_size {self.block_size}) and every prefill "
                    f"launch ({self._chunk_tiers}) must be whole blocks, "
                    f"{misfit} are not; a registered prefix ends on a "
                    f"page and a chunk may not split a block's rows, which "
                    f"see each other")
        # A model whose sequences keep state by slot (window rings,
        # recurrent state), and whether some of it is recurrent.
        self._by_slot = bool(getattr(cfg, "state_by_slot", False))
        self._recurrent = bool(getattr(cfg, "recurrent", False))
        # State by slot of which the model says that a launch's rows lay
        # none of it out: its program takes m x `prefill_chunk` rows as
        # m chunks, the state carried from one to the next.
        self._spans_chunks = self._by_slot and bool(
            getattr(cfg, "launch_spans_chunks", False))
        if not self._by_slot or self._spans_chunks:
            # Pool blocks alone, or such state: a launch is generic in
            # its rows, so the tiers go on above `prefill_chunk` (no
            # prompt reaches max_len rows).  Rings were laid out for
            # `prefill_chunk` rows a launch and stop there.
            wide = 2 * self.prefill_chunk
            while wide <= min(_CHUNK_TOP_ROWS, max_len):
                self._chunk_tiers.append(wide)
                wide *= 2
        if self._spans_chunks:
            # Under `prefill_chunk` such a launch is one chunk of fewer
            # positions, and a narrower program saves the device little
            # or nothing: granite-4.0-h-small's bare chunk program takes
            # 15.9 / 14.5 / 14.8 / 16.4 ms at 32 / 64 / 128 / 256 rows and
            # 15.5 at 256 with 20 of them valid (a launch is its 9.5 GB
            # of weights; a v5e, PERF.md section 7, PR 56), while each
            # tier is 0.9-1.4 s of every start.  So none is built, and a
            # prompt's last launch takes `prefill_chunk` rows.
            self._chunk_tiers = [t for t in self._chunk_tiers
                                 if t >= self.prefill_chunk]
        # The widest launch beside a decode burst: see _prefill_budget.
        beside = max(self.prefill_chunk, _ROWS_A_STEP * self.max_burst)
        self._chunk_beside_burst = max(
            t for t in self._chunk_tiers if t <= beside)
        # Prompt-lookup speculative decoding on the paged pool (opt-in,
        # knob-defaulted): each tick verifies K candidates per slot in
        # one width-K call; drafts come from n-gram matches in the
        # slot's own context.  Exact under greedy decoding; sampling
        # slots degrade to normal decode.
        if speculation_k is None:
            speculation_k = knobs.serve_speculation_k
        if speculation_ngram is None:
            speculation_ngram = knobs.serve_speculation_ngram
        self._spec_k = speculation_k if speculation_k >= 2 else 0
        if self._block and self._spec_k:
            raise ValueError(
                f"speculation_k={speculation_k} with {cfg.name!r}: a draft "
                f"is verified against the logits of the next token, and "
                f"this model's row predicts the token at its own position; "
                f"its blocks are filled by denoising passes instead")
        if self._by_slot and self._spec_k:
            raise ValueError(
                f"speculation_k={speculation_k} with {cfg.name!r}: a "
                f"rejected draft has already " + (
                    "advanced the recurrent state, and there is no "
                    "snapshot to roll it back to" if self._recurrent else
                    "written rows of the window rings, and nothing yet "
                    "shows that no later query sees them"))
        self._spec_ngram = max(1, speculation_ngram)
        # The free-margin _maybe_finish keeps must cover whichever
        # advance is larger — a burst OR a spec window — without
        # inflating the burst depth itself.
        self._advance_margin = max(self.max_burst, self._spec_k)
        self._b_max = math.ceil(max_len / self.block_size)
        prefix_sharing = (knobs.kv_block_prefix_sharing
                          if prefix_sharing is None else prefix_sharing)
        if self._by_slot:
            # A hit would skip positions whose state nobody kept.
            prefix_sharing = False
        self._jax = jax
        self._jnp = jnp
        self._rng = jax.random.key(seed)
        cache_sh = None
        if mesh is not None:
            # Tensor-parallel serving: params split over the mesh `tp`
            # axis (TP_RULES), the pool split on its kv-heads axis — the
            # SAME jitted engine programs run unchanged; GSPMD propagates
            # the shardings and inserts the all-reduces after wo/w_down.
            # This is how a model too big for one chip serves: a sharding
            # annotation, not an engine fork.
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ray_tpu.models.transformer import param_logical_axes
            from ray_tpu.parallel.mesh import AXIS_TENSOR
            from ray_tpu.parallel.sharding import (
                TP_RULES,
                param_shardings,
                shard_pytree,
            )

            if self._by_slot:
                raise ValueError(
                    f"{cfg.name!r} keeps {_slot_state(cfg)} by slot: it is "
                    f"served by the paged engine on one device (no mesh)")
            if self._block:
                raise ValueError(
                    f"{cfg.name!r} generates by diffusion over blocks: its "
                    f"burst, a scan of passes that rewrite a block's K / V "
                    f"in place, has not been shown to agree under a `tp` "
                    f"split of the pool; it is served on one device (no "
                    f"mesh)")
            if getattr(cfg, "init_state", None) is not None:
                raise ValueError(
                    f"{cfg.name!r} brings its own sequence state: the "
                    f"mesh's `tp` split is of the KV heads of a k / v pool "
                    f"(`paged_cache_shardings`), and this state has none "
                    f"to split (a latent row is one head's); it is served "
                    f"on one device (no mesh)")
            tp = int(mesh.shape.get(AXIS_TENSOR, 1))
            for dim_name, dim in (("n_kv_heads", cfg.n_kv_heads),
                                  ("n_heads", cfg.n_heads),
                                  ("d_ff", cfg.d_ff),
                                  ("vocab_size", cfg.vocab_size)):
                if dim % tp:
                    raise ValueError(
                        f"tensor parallelism {tp} does not divide "
                        f"{dim_name}={dim} for model {cfg.name!r} — "
                        f"pick a tp that divides all sharded dims")
            # Shard from HOST copies so the unsharded model never has
            # to fit on one chip (pass host arrays from params_loader
            # for models that genuinely don't).
            params = shard_pytree(
                jax.device_get(params),
                param_shardings(param_logical_axes(cfg), mesh, TP_RULES))
            cache_sh = paged_cache_shardings(mesh)
            self._rng = jax.device_put(self._rng, NamedSharding(mesh, P()))
        self.params = params
        self.cache = init_sequence_state(
            cfg, self.num_blocks, self.block_size, num_slots=num_slots,
            prefill_chunk=self.prefill_chunk, shardings=cache_sh)
        self._state_bytes = self.cache.resident_bytes()
        # The slots whose rings a burst of each width reads in a window
        # layer (the tick's `ring_slots`).
        self._ring_slots = {
            w: ring_slots_read(w, num_slots + 1)
            if self._state_bytes["kv_window"] else 0
            for w in self._width_tiers}
        self._reset_state = (jax.jit(cfg.reset_slot, donate_argnums=(0,))
                             if self._recurrent else None)
        self._score_step = self._score_chunk = None
        # KV positions one decode step sees over lanes of given lengths:
        # the model's own count (the planes a position keeps, a full
        # layer's once a pass of the stack), or one plane a layer over
        # every position.
        self._kv_read_tokens = getattr(cfg, "kv_read_tokens", None) or (
            lambda lengths: cfg.n_layers * sum(lengths))
        # A model whose full layers attend to a learned selection of their
        # positions says what a launch's rows score and then attend
        # (`selection_counts(start, rows)`, one lane); None from any other.
        self._selection_counts = (
            cfg.selection_counts if getattr(cfg, "index_top_k", 0) else None)
        # Layers whose FFN is a set of experts: the burst's count of
        # experts visited is per layer and step over these.
        self._expert_layers = (
            getattr(cfg, "n_expert_layers", cfg.n_layers)
            if getattr(cfg, "n_experts", 0) > 0 else 0)
        # A model that holds one rank's share of its experts: its chunk
        # and burst also hand out the top-k choices that fell on the
        # share, and a launch that groups its rows by expert the tiles it
        # multiplied beside them (`ops.moe.moe_mlp_dropless`).  They are
        # summed a tick on the device and read with the records, never
        # inside a tick (`_count_routed`).
        self._routed_sums = None
        self.tick_fields = TICK_FIELDS
        if counts_routed(cfg):
            # Experts chosen group by group: a third count, the rows whose
            # kept groups hold an expert held here (`group_open_rows`).
            width = 2
            if counts_groups(cfg):
                width = 3
                self.tick_fields += (_GROUP_OPEN_ROWS,)
            self._routed_sums = jnp.zeros((self.TICKS_KEPT + 8, width),
                                          jnp.int32)
            self._routed_next = 0
            # `n`: the choices alone, (choices, tiles), or all three.
            self._add_routed = jax.jit(
                lambda sums, at, n, fresh: sums.at[at].set(
                    jnp.where(fresh, 0, sums[at])
                    + jnp.pad(n.reshape(-1), (0, width - n.size))))
        # A model that attends to a learned selection: its burst hands
        # out how many reads took the selection as a mask, read with the
        # burst's tokens and logged as a share of its steps x the layers
        # that select (a row at position 0 scores one position in each).
        self._masked_at = 0
        if counts_masked(cfg):
            self._masked_at = len(self.tick_fields)
            self.tick_fields += (_SELECT_MASKED,)
            self._select_reads = \
                self._burst_passes * cfg.selection_counts(0, 1)[0]
        # A model with linear layers: what its bursts and launches carry
        # of their state is the host's own arithmetic.
        self._linear_layers = (
            cfg.n_of("linear") if getattr(cfg, "linear_chunk", 0)
            and self._recurrent else 0)
        if self._linear_layers:
            self.tick_fields += _LINEAR_STATE
        self._conv_layers = (
            cfg.n_of("conv") if getattr(cfg, "conv_kernel", 0)
            and self._recurrent else 0)
        if self._conv_layers:
            self.tick_fields += (_CONV_STATE,)
        # A model whose stack runs more than once: the layers a step of
        # its burst runs a lane.
        self._loop_passes = (
            cfg.loop_passes * cfg.n_layers
            if getattr(cfg, "loop_passes", 1) > 1 else 0)
        if self._loop_passes:
            self.tick_fields += (_LOOP_PASSES,)
        # A model of several residual streams: the largest defect of a
        # tick's mixes, kept on the device as the sums above are.
        self._defects = None
        if counts_defect(cfg):
            self.tick_fields += (_HC_RES_DEFECT,)
            self._defects = jnp.zeros((self.TICKS_KEPT + 8,), jnp.float32)
            self._defect_next = 0
            self._max_defect = jax.jit(
                lambda kept, at, d, fresh: kept.at[at].set(
                    jnp.maximum(jnp.where(fresh, 0.0, kept[at]), d)))
        self._prefill_chunk_fn, self._decode, self._copy_block = \
            make_paged_engine_fns(cfg)
        if self._spec_k:
            self._verify = make_paged_spec_fns(cfg)
        self._sample_one = jax.jit(sample_one)
        bytes_per_block = self._state_bytes["kv_paged"] // self.num_blocks
        self.allocator = KVBlockAllocator(
            self.num_blocks, self.block_size, store=store,
            bytes_per_block=bytes_per_block if store is not None else 0,
            prefix_sharing=prefix_sharing)
        # Host-side engine state: per-slot block tables + lengths (the
        # compiled step only ever sees fixed (S, B_max) arrays).
        self._tables = np.zeros((num_slots, self._b_max), np.int32)
        self._lengths = np.zeros((num_slots,), np.int32)
        # The last token each slot sampled, on the host (what the reads
        # have seen) and on the device (what the bursts have sampled;
        # entry num_slots is the idle lanes').  A lane's next input is
        # the host's where nothing of its request is in flight, else
        # the device's.
        self._last_tokens = np.zeros((num_slots,), np.int32)
        self._last_dev = jnp.zeros((num_slots + 1,), jnp.int32)
        if mesh is not None:
            self._last_dev = jax.device_put(self._last_dev,
                                            self._rng.sharding)
        self._take_last = jax.jit(take_last)
        self._put_last = jax.jit(put_last)
        self._inflight: Optional[_Burst] = None
        self._read_at = 0.0           # when the last burst was read
        self._slots: List[Optional[_Request]] = [None] * num_slots
        self._prefillq: deque = deque()   # slots awaiting prefill chunks
        self._pending: deque = deque()
        self._pending_lock = threading.Lock()
        self._work = threading.Event()
        self._stop = False
        # Serializes whole engine ticks against the foreign-thread KV
        # surface (import_prefix / export_streams): those read and
        # replace self.cache, which a mid-tick decode would otherwise
        # race.  Uncontended cost is one lock per tick.
        self._tick_lock = threading.Lock()
        self.stats = {"requests": 0, "tokens_generated": 0,
                      "completed": 0,
                      "prefix_hits": 0, "prefix_misses": 0,
                      "prefill_chunks": 0,
                      # prompt tokens carried, by the launch's rows
                      "prefill_launch_tokens": dict.fromkeys(
                          self._chunk_tiers, 0),
                      "queue_waits": 0,
                      "preemptions": 0, "adopted_blocks": 0,
                      "migrated_blocks": 0, "migrate_fallbacks": 0,
                      "disagg_prefills": 0,
                      "spec_proposed": 0, "spec_accepted": 0,
                      "index_scored_tokens": 0, "kv_selected_tokens": 0,
                      "blocks": 0, "passes": 0, "block_tokens": 0,
                      "state_resets": 0, "state_rebuilds": 0}
        self._request_phases: deque = deque(
            maxlen=self.REQUEST_PHASES_KEPT)
        # engine_stats()["tick_log"]: one tuple per tick that progressed
        # (see _tick), from the accounts the tick's phases keep as they
        # go.  `stats` is cumulative since the process began; a log lets
        # a reader take any window's ticks by their start and gives a
        # median where a counter gives a mean.
        self._tick_log: deque = deque(maxlen=self.TICKS_KEPT)
        self._acct = _TickAccounts()
        self._clock = _PhaseClock(jax.profiler.TraceAnnotation)
        # What the host knows of the device's queue: the programs it has
        # launched, counted, and since when the queue is known to be
        # empty (0.0: not known).  A blocking read of the newest
        # launch's result returns to an empty queue; the next launch
        # ends the stretch, summed a tick as `starved_s` (_mark_launch).
        self._launches = 0
        self._idle_from = 0.0
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        self._setup.close(build_span, num_blocks=self.num_blocks,
                          kv_bytes=sum(self._state_bytes.values()))

    # -- the request-facing surface --------------------------------------
    @staticmethod
    def _resume_ctx(prompt_tokens, max_tokens, resume_tokens):
        """Fold an interrupted stream's already-emitted tokens into the
        admission context.  The resumed request prefills
        `prompt + resume` — the same full-context recompute the paged
        engine's preemption path runs — and generates only the REMAINING
        `max_tokens - len(resume)` tokens, so a failover caller that
        kept the emitted prefix sees an exactly-once token sequence."""
        if not resume_tokens:
            return list(prompt_tokens), max_tokens, False
        ctx = list(prompt_tokens) + list(resume_tokens)
        return ctx, max(0, max_tokens - len(resume_tokens)), True

    def generate(self, prompt_tokens: List[int], *, max_tokens: int = 64,
                 temperature: float = 0.0,
                 timeout: Optional[float] = 300,
                 resume_tokens: Optional[List[int]] = None,
                 trace: Optional[dict] = None) -> List[int]:
        ctx, remaining, resumed = self._resume_ctx(
            prompt_tokens, max_tokens, resume_tokens)
        if len(ctx) >= self.max_len:
            raise ValueError(f"prompt ({len(ctx)}) >= max_len")
        if resumed and remaining == 0:
            return []
        req = _Request(ctx, remaining, temperature)
        req.no_register = resumed
        self._obs_submit(req, trace)
        self.stats["requests"] += 1
        self._pending_put(req)
        if not req.done.wait(timeout):
            raise TimeoutError("generation timed out")
        if req.error is not None:
            raise req.error
        return req.out_tokens

    def generate_stream(self, prompt_tokens: List[int], *,
                        max_tokens: int = 64, temperature: float = 0.0,
                        timeout: Optional[float] = 300,
                        resume_tokens: Optional[List[int]] = None,
                        trace: Optional[dict] = None):
        """Yield tokens as the engine produces them (TTFT = first yield;
        the continuous-batching loop keeps decoding other slots while the
        consumer reads).  `resume_tokens` re-admits an interrupted
        stream: the engine recomputes KV for prompt+resume and yields
        only the continuation."""
        ctx, remaining, resumed = self._resume_ctx(
            prompt_tokens, max_tokens, resume_tokens)
        if len(ctx) >= self.max_len:
            raise ValueError(f"prompt ({len(ctx)}) >= max_len")
        if resumed and remaining == 0:
            return
        req = _Request(ctx, remaining, temperature, stream=True)
        req.no_register = resumed
        self._obs_submit(req, trace)
        self.stats["requests"] += 1
        self._pending_put(req)
        deadline = time.monotonic() + (timeout or 300)
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError("generation timed out")
            try:
                tok = req.token_q.get(timeout=min(remaining, 0.25))
            except queue.Empty:
                # A dropped stream may not fit its end sentinel into the
                # full queue — the done event is the fallback signal.
                if req.done.is_set() and req.token_q.empty():
                    if req.error is not None:
                        raise req.error
                    return
                continue
            if tok is None:
                if req.error is not None:
                    raise req.error
                return
            yield tok

    def _pending_put(self, req: "_Request") -> None:
        with self._pending_lock:
            self._pending.append(req)
        self._work.set()

    def shutdown(self):
        self._stop = True
        self._work.set()
        self._thread.join(timeout=5)
        # A request that left its slot at its last burst's launch ends
        # at that burst's read.
        try:
            if self._tick_lock.acquire(timeout=5):
                try:
                    self._drain()
                finally:
                    self._tick_lock.release()
        finally:
            self.allocator.release()

    def engine_stats(self, records: bool = True) -> Dict[str, Any]:
        """The cumulative counters and, with `records`, the bounded
        logs and what is computed from them.  The replica's gauge loop
        asks every second and reads counters only: it passes False."""
        s = dict(self.stats)
        s["prefill_launch_tokens"] = dict(s["prefill_launch_tokens"])
        if records:
            s["request_phases"] = _snapshot(self._request_phases)
        # Seconds of the loop thread by leaf phase since the engine
        # began, the running leaf's part included.
        s["phase_seconds"] = dict(zip(
            PHASES, self._clock.read(time.time())))
        s.update(self.allocator.snapshot())
        # Resident bytes of the sequences' state by kind, and how often
        # recurrent state was zeroed (admissions and preemptions) and
        # rebuilt (preempted streams whose re-prefill finished).
        s["state"] = {**self._state_bytes,
                      "state_resets": s.pop("state_resets"),
                      "state_rebuilds": s.pop("state_rebuilds")}
        if records:
            s["tick_log"] = self._with_routed(_snapshot(self._tick_log))
            s["tick_fields"] = self.tick_fields
            # The replica's start on one clock: its spans, and every
            # program the process has traced, lowered and loaded or
            # compiled (a tier compiled inside serving is there too).
            s["setup"] = self.setup_records()
        s["queue_depth"] = len(self._pending)
        s["active"] = sum(1 for r in self._slots if r is not None)
        return s

    def setup_records(self) -> Dict[str, list]:
        """`engine_stats()["setup"]`: the `serve.setup*` spans of this
        replica's start and the process's compile log."""
        return {"spans": list(_snapshot(self._setup.records)),
                "compile_log": compile_cache.log()}

    def warmup(self) -> None:
        """Compile every width/chunk tier up front (benchmarks; serving
        just compiles tiers lazily as load ramps).  Inactive-lane calls
        scatter into the null block — garbage no request reads.

        Recorded (`engine_stats()["setup"]["spans"]`): `serve.setup.warmup`
        from entry to return and, under it, one `serve.setup.warmup.tier`
        a launch, which is the call into the jitted program: its trace,
        lowering, load or compile (by name in the compile log beside
        the spans) and enqueue.  No launch is waited for, so a tier's
        first execution overlaps the next tier's load and has no time of
        its own here, and the last ones still run at the return."""
        import jax.numpy as jnp

        tier = functools.partial(self._setup.span, "serve.setup.warmup.tier")
        with self._setup.span("serve.setup.warmup",
                              width_tiers=list(self._width_tiers),
                              chunk_tiers=list(self._chunk_tiers)) as whole:
            self._drain()
            for w in self._width_tiers:
                z = np.zeros((w,), np.int32)
                with tier(whole, program=self._decode.__name__,
                          kind="burst", width=w):
                    self._launch_burst(
                        [], w, self._burst_input([], w),
                        np.zeros((w, self._b_max), np.int32), z,
                        np.zeros((w,), bool), np.zeros((w,), np.float32))
                if self._spec_k:
                    with tier(whole, program=self._verify.__name__,
                              kind="verify", width=w):
                        self.cache, _, _, self._rng = self._verify(
                            self.params, self.cache,
                            jnp.zeros((w, self._spec_k), jnp.int32),
                            jnp.zeros((w, self._b_max), jnp.int32),
                            jnp.asarray(z), jnp.zeros((w,), bool),
                            jnp.zeros((w,), jnp.float32), self._rng)
            for c in self._chunk_tiers:
                with tier(whole, program=self._prefill_chunk_fn.__name__,
                          kind="chunk", rows=c):
                    self.cache, _, *routed = self._prefill_chunk_fn(
                        self.params, self.cache, jnp.zeros((c,), jnp.int32),
                        jnp.zeros((self._b_max,), jnp.int32), jnp.int32(0),
                        jnp.int32(0), **self._slot_kw(self.num_slots))
                    self._count_routed(routed)

    def gauges(self) -> Dict[str, float]:
        """Cheap autoscaling signals (riding the syncer push)."""
        snap = self.allocator.snapshot()
        return {"queue_depth": float(len(self._pending)),
                "active": float(sum(1 for r in self._slots
                                    if r is not None)),
                "occupancy": snap["occupancy"]}

    # -- engine loop ----------------------------------------------------
    @staticmethod
    def _tiers(lo: int, hi: int) -> List[int]:
        out = []
        w = lo
        while w < hi:
            out.append(w)
            w *= 2
        out.append(hi)
        return out

    def _tier_for(self, tiers: List[int], n: int) -> int:
        for t in tiers:
            if n <= t:
                return t
        return tiers[-1]

    def _free_slot(self) -> int:
        for i, r in enumerate(self._slots):
            if r is None:
                return i
        return -1

    def _table_row(self, slot: int, blocks: List[int]) -> None:
        self._tables[slot, :] = 0
        self._tables[slot, :len(blocks)] = blocks

    # -- state by slot (models that keep more than paged KV) -------------
    def _slot_kw(self, slot: int) -> Dict[str, Any]:
        """The prefill chunk's `slot` argument: the engine slot whose
        ring and recurrent state the chunk reads and writes (num_slots:
        the null slot).  Nothing for a model whose state is the pool
        alone: its programs are called as they always were."""
        return ({"slot": self._jnp.int32(slot)} if self._by_slot else {})

    def _lane_slots(self, idx: List[int], width: int) -> np.ndarray:
        """Lane j is engine slot idx[j]; the lanes past them are idle
        and point at the null slot."""
        slots = np.full((width,), self.num_slots, np.int32)
        slots[:len(idx)] = idx
        return slots

    def _lanes_kw(self, idx: List[int], width: int) -> Dict[str, Any]:
        """The burst's `slots` argument, for a model that keeps state
        by slot."""
        if not self._by_slot:
            return {}
        return {"slots": self._jnp.asarray(self._lane_slots(idx, width))}

    def _burst_input(self, idx: List[int], width: int):
        """What a burst over the slots `idx` starts from, by lane, as the
        host knows it.  A next-token model: `host_tok` (width,), a lane's
        token where the host holds it (a first token, or every burst of
        the request is read), else -1 (the device's vector has it).  A
        model that fills blocks: (tokens (width, B), open rows (width, B)
        bool) of each lane's first block, its request's given rows first
        and the mask token in the open ones."""
        if not self._block:
            host_tok = np.full((width,), -1, np.int32)
            for j, i in enumerate(idx):
                if not self._slots[i].ahead:
                    host_tok[j] = self._last_tokens[i]
            return host_tok
        toks = np.full((width, self._block), self.cfg.mask_token_id,
                       np.int32)
        still = np.ones((width, self._block), bool)
        for j, i in enumerate(idx):
            given = self._slots[i].given
            toks[j, :len(given)] = given
            still[j, :len(given)] = False
        return toks, still

    def _launch_burst(self, idx: List[int], width: int, first, tables,
                      lengths, active, temps):
        """The burst of the model's kind over `first` (`_burst_input`),
        and no read.  A next-token model, three launches: the gather of
        the lanes' input tokens (`first[j]` where it is >= 0, else the
        last token slot idx[j] sampled), the burst, the scatter of its
        last row back by slot.  A model that fills blocks, one: every
        input is the host's.  Returns (token matrix, experts visited, a
        selecting model's reads by the mask or None), on the device."""
        jnp = self._jnp
        if self._block:
            self._mark_launch()
            self.cache, tok_mat, self._rng, visited = self._decode(
                self.params, self.cache, jnp.asarray(first[0]),
                jnp.asarray(first[1]), jnp.asarray(tables),
                jnp.asarray(lengths), jnp.asarray(active),
                jnp.asarray(temps), self._rng,
                n_blocks=self.max_burst // self._block)
            return tok_mat, visited, None
        host_tok = first
        slots = jnp.asarray(self._lane_slots(idx, width))
        self._mark_launch()
        self.cache, tok_mat, self._rng, visited, *routed = self._decode(
            self.params, self.cache,
            self._take_last(self._last_dev, slots, jnp.asarray(host_tok)),
            jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(active),
            jnp.asarray(temps), self._rng, n_steps=self.max_burst,
            **({"slots": slots} if self._by_slot else {}))
        self._last_dev = self._put_last(self._last_dev, slots, tok_mat)
        masked = routed.pop() if self._masked_at else None
        self._count_routed(routed)
        return tok_mat, visited, masked

    def _mark_launch(self) -> None:
        """A burst, a verify window or a prefill chunk is about to be
        dispatched.  If the device's queue was known to be empty, it was
        so since `_idle_from`, with work in the engine's hands: the
        stretch goes to the tick's `starved_s`."""
        self._launches += 1
        if self._idle_from:
            self._acct.starved_s += time.time() - self._idle_from
            self._idle_from = 0.0

    def _count_routed(self, handed) -> None:
        """`handed`: what a chunk or a burst handed out behind its own
        results.  From a model that holds a share of its experts the
        top-k choices that fell on the share (from a launch that grouped
        its rows by expert with the tiles it multiplied); then, from a
        model of several residual streams, the largest defect of the
        launch's mixes; nothing from any other model.  Each is added (the
        defect: its largest kept), on the device, to what the tick that
        launched it holds: one small launch and no read, so that a chunk
        stays queued behind the host and a burst ahead of its read.
        `_with_routed` reads both when the records are asked for."""
        handed = list(handed)
        acct = self._acct
        if self._routed_sums is not None and handed:
            fresh = acct.routed_at < 0
            if fresh:
                acct.routed_at = \
                    self._routed_next % self._routed_sums.shape[0]
                self._routed_next += 1
            self._routed_sums = self._add_routed(
                self._routed_sums, self._jnp.int32(acct.routed_at),
                handed.pop(0), fresh)
        if self._defects is not None and handed:
            fresh = acct.defect_at < 0
            if fresh:
                acct.defect_at = self._defect_next % self._defects.shape[0]
                self._defect_next += 1
            self._defects = self._max_defect(
                self._defects, self._jnp.int32(acct.defect_at),
                handed.pop(0), fresh)

    def _with_routed(self, log: tuple) -> tuple:
        """The tick log with each record's `routed_here` and `moe_tiles`
        (and `group_open_rows`, where the log has it) read from the
        device's sums and its `hc_res_defect` from the
        device's largest (0 where the tick counted none).  A record is
        among the last TICKS_KEPT, so its sums have not been reused."""
        if (self._routed_sums is None and self._defects is None) or not log:
            return log
        sums = None if self._routed_sums is None \
            else np.asarray(self._routed_sums)
        defects = None if self._defects is None \
            else np.asarray(self._defects)

        def read(t):
            t = list(t)
            if sums is not None:
                counted = map(int, sums[t[_ROUTED_HERE]]) \
                    if t[_ROUTED_HERE] >= 0 else (0,) * sums.shape[1]
                t[_ROUTED_HERE], t[_MOE_TILES], *more = counted
                if more:                # behind the fields every log has
                    t[len(TICK_FIELDS)] = more[0]
            if defects is not None:     # the record's last field
                t[-1] = float(defects[t[-1]]) if t[-1] >= 0 else 0.0
            return tuple(t)

        return tuple(read(t) for t in log)

    def _count_selection(self, lanes) -> None:
        """`lanes`: (first position, rows) of each lane of a launch.  What
        a model that selects positions scores and then attends there goes
        to the tick's `index_scored_tokens` / `kv_selected_tokens` and to
        the cumulative counters; nothing from any other model."""
        if self._selection_counts is None:
            return
        for start, rows in lanes:
            scored, selected = self._selection_counts(start, rows)
            self._acct.index_scored_tokens += scored
            self._acct.kv_selected_tokens += selected
            self.stats["index_scored_tokens"] += scored
            self.stats["kv_selected_tokens"] += selected

    def _reset_slot_state(self, req: "_Request", slot: int) -> None:
        """Zero `slot`'s recurrent state: `req` was admitted to it, or
        was preempted and will re-prefill.  A launch, counted in the
        tick's `reset_s`; the span is the request's own."""
        if not self._recurrent:
            return
        t0 = time.time()
        self.cache = self._reset_state(self.cache, self._jnp.int32(slot))
        t1 = time.time()
        self._acct.reset_s += t1 - t0
        self.stats["state_resets"] += 1
        tracing.record_serve_span(req.trace, "serve.engine.state_reset",
                                  t0, t1, slot=slot)

    def _admit_one(self) -> bool:
        import jax.numpy as jnp

        slot = self._free_slot()
        if slot < 0:
            return False
        with self._pending_lock:
            req = self._pending[0] if self._pending else None
        if req is None:
            return False
        bs = self.block_size
        n = len(req.prompt)
        if self._block:
            # What is prefilled is the prompt's whole blocks, and what is
            # shared its whole pages: a hit never carries logits.
            fill = n // self._block * self._block
            shared, covered, meta = self.allocator.lookup_prefix(
                req.prompt[:n // bs * bs])
        else:
            fill = n
            shared, covered, meta = self.allocator.lookup_prefix(req.prompt)
        if not self._block and covered == n and meta is None and shared:
            # Whole-prompt chain without stored logits (evicted): fall
            # back to re-prefilling the tail chunk.
            self.allocator.free(shared[-1:])
            shared = shared[:-1]
            covered = len(shared) * bs
        need = math.ceil(n / bs) - len(shared)
        # Admission wants one burst of decode growth on top of the
        # prompt — cuts (but can't eliminate; preemption is the
        # backstop) admit-then-deadlock on growth blocks.
        headroom = need + math.ceil(self.max_burst / bs)
        alloc = ((self.allocator.alloc(need)
                  if self.allocator.can_alloc(headroom) else None)
                 if need > 0 else [])
        if alloc is None:
            # Pool exhausted: the request WAITS at the queue head (no
            # error); completions free blocks and wake the loop.
            self.allocator.free(shared)
            self.stats["queue_waits"] += 1
            return False
        with self._pending_lock:
            self._pending.popleft()
        self._obs_admitted(req)
        blocks = shared + alloc
        req.blocks = blocks
        req.slot = slot
        req.pos = covered
        self._slots[slot] = req
        self._table_row(slot, blocks)
        self._lengths[slot] = 0
        self._reset_slot_state(req, slot)
        if covered > 0:
            self.stats["prefix_hits"] += 1
        if self._block and covered == fill:
            # Nothing to prefill: the prompt's whole blocks are shared
            # pages, or it has none.  Its tail opens the first block.
            if covered == 0:
                self.stats["prefix_misses"] += 1
            self._begin_decode(req, None)
            return True
        if covered == n:
            # Whole-prompt hit: sample the first token from the stored
            # last-logits under THIS request's temperature — no prompt
            # forward at all.  COW the (shared) partial tail before
            # decode appends into it.
            clock = self._clock
            try:
                self._cow_tail(req)
                clock.enter(_FIRST_READ)
                tok, self._rng = self._sample_one(
                    meta, jnp.float32(req.temperature), self._rng)
                tok = int(tok)
                self._idle_from = clock.enter(_EMIT)
                self._begin_decode(req, tok)
            except BaseException as e:  # noqa: BLE001
                self._fail_request(req, e)
            clock.enter(_ADMIT)
            return True
        if covered == 0:
            self.stats["prefix_misses"] += 1
        self._prefillq.append(slot)
        return True

    def _cow_tail(self, req: "_Request", n_ctx: Optional[int] = None
                  ) -> None:
        """Give `req` an exclusively-owned, writable tail block (device
        copy when the tail is shared or registered)."""
        import jax.numpy as jnp

        n = len(req.prompt) if n_ctx is None else n_ctx
        if n % self.block_size == 0 or not req.blocks:
            return  # aligned: first append allocates a fresh block
        tail = req.blocks[-1]
        new, copied = self.allocator.cow(tail)
        if copied:
            self.cache = self._copy_block(self.cache, jnp.int32(new),
                                          jnp.int32(tail))
            req.blocks[-1] = new
            self._table_row(req.slot, req.blocks)

    def _begin_decode(self, req: "_Request", first_tok: Optional[int]
                      ) -> None:
        # KV written so far = the prefilled context (a preempted request
        # re-enters here with out_tokens already emitted).
        n_ctx = len(req.prompt) + len(req.out_tokens)
        if self._block:
            # A model that fills blocks: the context's whole blocks are
            # in, its tail stands as given rows of the block the request
            # fills first, and nothing has been sampled: the first token
            # comes from the first burst's read (`_harvest`).
            whole = n_ctx // self._block * self._block
            req.given = (req.prompt + req.out_tokens)[whole:]
            req.prefilling = False
            self._lengths[req.slot] = whole
            self._maybe_finish(req.slot)
            return
        if req.first_token_at is None:
            self._obs_first_token(req)
        req.prefilling = False
        req.emit(first_tok)
        self._last_tokens[req.slot] = first_tok
        self._lengths[req.slot] = n_ctx
        self._maybe_finish(req.slot)

    def _fail_request(self, req: "_Request", e: BaseException) -> None:
        req.error = e
        slot = req.slot
        if 0 <= slot < self.num_slots and self._slots[slot] is req:
            self._slots[slot] = None
            self._tables[slot, :] = 0
        if slot in self._prefillq:
            self._prefillq.remove(slot)
        self.allocator.free(req.blocks)
        req.blocks = []
        if req.token_q is not None:
            try:
                req.token_q.put_nowait(None)
            except queue.Full:
                pass
        req.done.set()

    def _prefill_budget(self) -> int:
        """Prompt tokens this tick's prefill launches may carry.  (i)
        With nobody decoding: the widest chunk tier, the rows past which
        the device's time a token stops falling (`_CHUNK_TOP_ROWS`; a
        model whose slots keep rings has no tier above `prefill_chunk`).
        (ii) Beside a burst (this tick launched one): no more rows than
        take the device as long as the burst does, `_ROWS_A_STEP` a
        step of it -- the ITL bound, in the one form the host can
        check without waiting for the device -- and never under
        `prefill_chunk`, the budget's unit and floor."""
        if self._acct.lanes:
            return self._chunk_beside_burst
        return self._chunk_tiers[-1]

    def _prefill_tick(self) -> bool:
        """Prefill launches in FIFO order under the tick's TOKEN budget
        (`_prefill_budget`): the prompt at the head of the queue gets
        one launch of the widest tier the budget allows, so a long
        prompt consumes the whole budget in one wide launch (then
        yields the device back to decode -- the ITL bound), while a
        tickful of short prompts batches several narrow launches into
        the same budget (admission isn't serialized to one prompt per
        tick)."""
        import jax.numpy as jnp

        clock = self._clock
        clock.enter(_CHUNK_LAUNCH)
        budget = self._prefill_budget()
        progressed = False
        while self._prefillq and budget > 0:
            slot = self._prefillq[0]
            req = self._slots[slot]
            if req is None:
                self._prefillq.popleft()
                continue
            try:
                t0 = clock.enter(_CHUNK_LAUNCH)
                # Preempted requests re-prefill their WHOLE context —
                # prompt plus the tokens already emitted (the stream
                # keeps every token; only the KV is recomputed).
                ctx = req.prompt + req.out_tokens
                n = len(ctx)
                if not req.blocks:   # preemption freed them: re-alloc
                    # Resume only with one burst of growth headroom on
                    # top of the context — otherwise the resumed
                    # request immediately re-stalls on the blocks it
                    # just freed and ping-pongs with the survivor.
                    bs = self.block_size
                    headroom = math.ceil((n + self.max_burst) / bs)
                    alloc = (self.allocator.alloc(math.ceil(n / bs))
                             if self.allocator.can_alloc(headroom)
                             else None)
                    if alloc is None:
                        self.stats["queue_waits"] += 1
                        break        # wait for completions to free blocks
                    req.blocks = alloc
                    self._table_row(slot, req.blocks)
                if self._block:
                    # The pages cover the whole context; what is prefilled
                    # is its whole blocks (the tail: `_begin_decode`).
                    n = n // self._block * self._block
                nv = min(budget, n - req.pos)
                c = self._tier_for(self._chunk_tiers, nv)
                nv = min(nv, c)
                toks = np.zeros((c,), np.int32)
                toks[:nv] = ctx[req.pos:req.pos + nv]
                # The row is copied: on the CPU backend `jnp.asarray` of
                # a view shares the host's memory with a program that has
                # only been launched, and `_cow_tail` below rewrites it.
                self._mark_launch()
                self.cache, last_logits, *routed = self._prefill_chunk_fn(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(self._tables[slot].copy()),
                    jnp.int32(req.pos), jnp.int32(nv),
                    **self._slot_kw(slot))
                self._count_routed(routed)
                self._count_selection([(req.pos, nv)])
                req.pos += nv
                budget -= nv
                progressed = True
                self.stats["prefill_chunks"] += 1
                self.stats["prefill_launch_tokens"][c] += nv
                acct = self._acct
                acct.prefill_s += self._obs_prefill(req, t0, nv, c)
                acct.prefill_tokens += nv
                if self._linear_layers:
                    acct.delta_chunks += self._linear_layers * (
                        c // min(c, self.cfg.linear_chunk))
                if req.pos >= n:
                    clock.enter(_BOOK)
                    self._prefillq.popleft()
                    if self._recurrent and req.out_tokens:
                        self.stats["state_rebuilds"] += 1
                    if self._block:
                        # The prompt's whole pages, without logits: a
                        # block's passes write only behind them.
                        if not req.out_tokens and not req.no_register:
                            bs = self.block_size
                            self.allocator.register_prefix(
                                req.prompt[:len(req.prompt) // bs * bs],
                                req.blocks)
                        self._begin_decode(req, None)
                        continue
                    if not req.out_tokens and not req.no_register:
                        # Publish the prompt's blocks for prefix reuse
                        # BEFORE our own appends diverge the tail (COW
                        # keeps the registered copy pristine).  Resumed
                        # contexts contain generated tokens — not
                        # reusable prompts; skip.
                        self.allocator.register_prefix(
                            req.prompt, req.blocks, meta=last_logits)
                    self._cow_tail(req, n)
                    t_sample = clock.enter(_FIRST_READ)
                    tok, self._rng = self._sample_one(
                        last_logits, jnp.float32(req.temperature),
                        self._rng)
                    # The host's read of the token waits for every
                    # chunk still queued on the device: launches return
                    # long before their chunks have run.  The sampler
                    # was the newest launch: the read returns to an
                    # empty queue.
                    tok = int(tok)
                    self._idle_from = clock.enter(_EMIT)
                    acct.sample_s += self._idle_from - t_sample
                    self._begin_decode(req, tok)
            except BaseException as e:  # noqa: BLE001
                if self._prefillq and self._prefillq[0] == slot:
                    self._prefillq.popleft()
                self._fail_request(req, e)
        return progressed

    def _ensure_blocks(self, req: "_Request", upto: int) -> bool:
        """Extend `req`'s table to cover positions [0, upto) — alloc on
        demand.  False = pool exhausted; the slot sits out this burst
        (it resumes when completions free blocks)."""
        need = math.ceil(upto / self.block_size) - len(req.blocks)
        if need <= 0:
            return True
        alloc = self.allocator.alloc(need)
        if alloc is None:
            return False
        req.blocks.extend(alloc)
        self._table_row(req.slot, req.blocks)
        return True

    def _decode_tick(self) -> bool:
        """Launch the next burst over the decoding slots, then read the
        one launched a tick ago (see the class docstring).  For a model
        that generates by diffusion over blocks the burst is
        `max_burst // B` blocks a lane, each `denoise_steps` + 1 forward
        passes of B rows: a lane's length moves by `max_burst` positions
        as any other's, and it is counted `max_burst` tokens less the
        given rows of its first block (the prompt's tail) and whatever
        `max_tokens` cuts off its last."""
        self._clock.enter(_BURST_LAUNCH)
        burst = self.max_burst
        # One tick advances either a burst (burst tokens of KV) or a
        # spec window (K tokens of KV); cover whichever is larger so
        # the spec/burst choice below never re-runs allocation.
        # `_lengths` already counts the burst in flight, so this covers
        # the burst ahead of it.
        adv = max(burst, self._spec_k)
        idx: List[int] = []
        stalled: List[int] = []
        for i, req in enumerate(self._slots):
            if req is None or req.prefilling:
                continue
            if self._ensure_blocks(req, int(self._lengths[i]) + adv):
                idx.append(i)
            else:
                stalled.append(i)
        prev = self._inflight
        try:
            if not idx:
                if prev is not None:
                    # Nothing to launch: the burst in flight is read,
                    # and what its requests free is seen next tick.
                    self._drain()
                    return True
                if len(stalled) >= 2:
                    # Deadlock: every decoder needs growth blocks and
                    # the pool is exhausted by the decoders themselves
                    # -- nobody can finish to free blocks.  Preempt the
                    # youngest (vLLM-style recompute preemption): its
                    # blocks free the others; it re-prefills
                    # prompt+emitted later.
                    self._preempt(max(stalled,
                                      key=lambda i:
                                      self._slots[i].submitted_at))
                return False
            # Compact the active slots into the smallest width tier:
            # device work tracks the number of LIVE streams, not the
            # configured capacity (a ramp-up tick with 3 decoders runs a
            # width-4 burst, not a num_slots-wide one).  All per-slot
            # state but the last tokens is host-side, so lane mapping is
            # row selection, and a gather by slot for the tokens.
            w = self._tier_for(self._width_tiers, len(idx))
            self._acct.lanes, self._acct.width = len(idx), w
            self._acct.kv_read_tokens = self._kv_read_tokens(
                [int(self._lengths[i]) for i in idx])
            self._acct.ring_slots = self._ring_slots[w]
            self._acct.linear_state_rows = self._linear_layers * len(idx)
            self._acct.conv_state_rows = self._conv_layers * len(idx)
            first = self._burst_input(idx, w)
            tables = np.zeros((w, self._b_max), np.int32)
            lengths = np.zeros((w,), np.int32)
            active = np.zeros((w,), bool)
            temps = np.zeros((w,), np.float32)
            for j, i in enumerate(idx):
                req = self._slots[i]
                tables[j] = self._tables[i]
                lengths[j] = self._lengths[i]
                active[j] = True
                temps[j] = req.temperature
            if self._spec_k and self._spec_tick(idx, tables, lengths,
                                                active, temps):
                self._count_selection(
                    (int(n), self._spec_k) for n in lengths[:len(idx)])
                return True
            self._count_selection(
                (int(n), burst) for n in lengths[:len(idx)])
            t0 = time.time()
            tok_mat, visited, masked = self._launch_burst(
                idx, w, first, tables, lengths, active, temps)
            self._acct.decode_s = time.time() - t0
            # The host's books move at the launch.  A request whose
            # last token is in this burst leaves its slot now: the next
            # launch is without it, and whoever takes the slot is
            # launched after this burst, so runs after it.
            lanes = []
            for i in idx:
                req = self._slots[i]
                self._lengths[i] += burst   # KV written for every step
                # Given rows come first among a lane's tokens and are not
                # the request's to emit.
                skip, req.given = len(req.given), []
                n = min(burst - skip, req.max_tokens - len(req.out_tokens)
                        - req.ahead)
                req.ahead += n
                last = self._ends_at(req, len(req.out_tokens) + req.ahead)
                if last:
                    self._slots[i] = None
                    self._tables[i, :] = 0
                lanes.append((req, n, last, skip))
            if self._block:
                acct = self._acct
                acct.blocks = len(idx) * burst // self._block
                acct.passes = self._burst_passes
                acct.block_tokens = sum(lane[1] for lane in lanes)
                for k in ("blocks", "passes", "block_tokens"):
                    self.stats[k] += getattr(acct, k)
            self._inflight = _Burst(
                tok_mat, visited if self._expert_layers else None, masked,
                lanes, t0, self._launches)
            if prev is not None:
                self._acct.ahead = 1
                self._harvest(prev)
            if self._spec_k:
                self._drain()       # drafts come from the emitted context
        except BaseException as e:  # noqa: BLE001
            self._fail_all(e, prev)
        return True

    def _harvest(self, b: "_Burst") -> None:
        """Read a launched burst's tokens and emit them, each lane's to
        the request that was in it at the launch."""
        t0 = self._clock.enter(_BURST_READ)
        # Tokens and counts, every copy started before any is waited
        # for: a second read after the first costs 0.6 ms.
        tok_mat, visited, masked = self._jax.device_get(
            (b.tok_mat, b.visited, b.masked))         # (burst, w), counts
        experts = 0.0 if visited is None else \
            int(visited) / (self._burst_passes * self._expert_layers)
        masked = 0.0 if masked is None else \
            100.0 * int(masked) / self._select_reads
        # By lane, in the order of the positions: (w, burst), from a
        # matrix by step, or by block and row (blocks, w, B).
        tok_mat = tok_mat.T if tok_mat.ndim == 2 else \
            tok_mat.transpose(1, 0, 2).reshape(tok_mat.shape[1], -1)
        t1 = self._clock.enter(_EMIT)
        if b.seq == self._launches and self._clock.ticking:
            self._idle_from = t1    # nothing was launched behind it
        self._acct.decode_s += t1 - t0
        if b.row is None:           # read in the tick that launched it
            self._acct.experts_read = experts
            self._acct.select_masked = masked
        else:
            b.row[_EXPERTS_READ] = experts
            if self._masked_at:
                b.row[self._masked_at] = masked
            self._tick_log.append(tuple(b.row))
        # The burst's turn on the device began when the one before it
        # was read, if that was after its launch.
        began, self._read_at = max(b.t0, self._read_at), t1
        for j, (req, n, last, skip) in enumerate(b.lanes):
            if req.done.is_set():
                continue    # ended at an earlier read: EOS, a dropped
                #             stream; these are the burst ahead's tokens
            if req.first_token_at is None:
                # A model that fills blocks samples nothing at the end of
                # its prefill: its first token is this read's.
                self._obs_first_token(req)
            req.ahead -= n
            req.lanes_sum += len(b.lanes)
            req.bursts += 1
            n0, eos = len(req.out_tokens), False
            for tok in tok_mat[j, skip:skip + n].tolist():
                req.emit(tok)
                self.stats["tokens_generated"] += 1
                if self.eos_id is not None and tok == self.eos_id:
                    eos = True
                    break
            self._obs_burst(req, began, t1, len(req.out_tokens) - n0)
            if self._slots[req.slot] is req:
                self._last_tokens[req.slot] = req.out_tokens[-1]
            if last or eos or req.dropped:
                self._finish(req)

    def _drain(self) -> None:
        """Read the burst in flight, if any: after this the host holds
        every token the device has sampled."""
        b, self._inflight = self._inflight, None
        if b is None:
            return
        try:
            self._harvest(b)
        except BaseException as e:  # noqa: BLE001
            self._fail_all(e, b)
            raise

    def _fail_all(self, e: BaseException,
                  also: Optional["_Burst"] = None) -> None:
        """Fail every request the engine holds: in a slot, or only in a
        burst that is launched and not read (`also`: one the caller has
        taken out of `_inflight`)."""
        bursts, self._inflight = (self._inflight, also), None
        held = [r for r in self._slots if r is not None]
        held += [lane[0] for b in bursts if b is not None
                 for lane in b.lanes]
        for req in held:
            if not req.done.is_set():
                self._fail_request(req, e)

    def _spec_tick(self, idx: List[int], tables, lengths, active,
                   temps) -> bool:
        """One speculative verify tick over the compacted decode lanes.
        Returns False when too few slots carry a draft (caller falls
        back to the plain burst — no wasted K-wide call).  Greedy
        acceptance is exact; an accepted padding token is by definition
        the true greedy continuation, so padding needs no masking.  Called
        from inside _decode_tick's try block after _ensure_blocks extended
        every participating table to cover the K window, so the kernel's
        scatter is always in-bounds and always lands in exclusively-
        owned blocks (COW at decode start + fresh growth allocs) —
        rejected drafts are rolled back by length arithmetic alone."""
        import jax.numpy as jnp

        from ray_tpu.models.decoding import ngram_propose

        k = self._spec_k
        w = tables.shape[0]
        cand = np.zeros((w, k), np.int32)
        drafted = 0
        greedy_active = 0
        for j, i in enumerate(idx):
            req = self._slots[i]
            cand[j, 0] = self._last_tokens[i]
            props = []
            if req.temperature == 0.0:
                greedy_active += 1
                ctx = req.prompt + req.out_tokens
                props = ngram_propose(ctx, k - 1, self._spec_ngram)
            for col in range(1, k):
                cand[j, col] = (props[col - 1] if col - 1 < len(props)
                                else self._last_tokens[i])
            if props:
                drafted += 1
        # Only when a MAJORITY of the greedy lanes carry a draft: lanes
        # without one (and sampling lanes) advance a single token per
        # spec tick, so a lone drafted lane must not preempt the
        # max_burst-deep decode for everyone else.
        if drafted == 0 or 2 * drafted < greedy_active \
                or 2 * greedy_active < len(idx):
            return False
        # All k-1 candidate columns of every greedy lane count as
        # proposed: padding can accept too, and accepted must never
        # exceed proposed.
        self.stats["spec_proposed"] += (k - 1) * greedy_active
        t0 = time.time()
        self._mark_launch()
        self.cache, tok_out, accepted, self._rng = self._verify(
            self.params, self.cache, jnp.asarray(cand),
            jnp.asarray(tables), jnp.asarray(lengths),
            jnp.asarray(active), jnp.asarray(temps), self._rng)
        self._clock.enter(_BURST_READ)
        tok_out = np.asarray(tok_out)              # (w, k)
        accepted = np.asarray(accepted)            # (w,)
        # The window was the newest launch: the queue is empty.
        t1 = self._idle_from = self._clock.enter(_EMIT)
        self._acct.decode_s = t1 - t0
        for j, i in enumerate(idx):
            req = self._slots[i]
            a = int(accepted[j])
            self.stats["spec_accepted"] += a
            # KV was written for the whole K window; only a+1 positions
            # are real.  Advancing lengths by a+1 IS the rollback: the
            # paged masks (kv_pos <= position) treat the stale tail as
            # garbage and the next decode overwrites it in place.
            self._lengths[i] += a + 1
            req.lanes_sum += len(idx)
            req.bursts += 1
            n0 = len(req.out_tokens)
            for tok in tok_out[j, :a + 1]:
                tok = int(tok)
                if len(req.out_tokens) >= req.max_tokens:
                    break  # over-generated tail: trim
                req.emit(tok)
                self._last_tokens[i] = tok
                self.stats["tokens_generated"] += 1
                if self.eos_id is not None and tok == self.eos_id:
                    break
            self._obs_burst(req, t0, t1, len(req.out_tokens) - n0)
            self._maybe_finish(i)
        return True

    def _preempt(self, slot: int) -> None:
        """Evict a stalled decoder: free its blocks (unblocking the
        others) and queue it for full-context re-prefill.  The stream
        keeps every emitted token — only KV is recomputed."""
        req = self._slots[slot]
        # Its re-prefill is over prompt + emitted: every token of it
        # that the device holds is read first.
        self._drain()
        self._clock.enter(_BOOK)
        if self._slots[slot] is not req:
            return                  # that read ended it
        self.allocator.free(req.blocks)
        req.blocks = []
        self._tables[slot, :] = 0
        self._lengths[slot] = 0
        self._reset_slot_state(req, slot)
        req.pos = 0
        req.prefilling = True
        self._prefillq.append(slot)
        self.stats["preemptions"] += 1

    def _ends_at(self, req: "_Request", n_out: int) -> bool:
        """Whether `req` ends once it has `n_out` tokens, by count
        alone: `max_tokens`, or the room `max_len` leaves."""
        return (n_out >= req.max_tokens
                or len(req.prompt) + n_out
                >= self.max_len - 1 - self._advance_margin)

    def _maybe_finish(self, slot: int) -> None:
        """End the request in `slot` if what the host has read of it
        says so (nothing of it is in flight)."""
        req = self._slots[slot]
        if req is None:
            return
        tok = req.out_tokens[-1] if req.out_tokens else None
        hit_eos = self.eos_id is not None and tok == self.eos_id
        if hit_eos or req.dropped \
                or self._ends_at(req, len(req.out_tokens)):
            self._finish(req)

    def _finish(self, req: "_Request") -> None:
        """`req` is over: its slot, if it still holds one, and its
        blocks are free.  A burst that is launched and not read may
        still write those blocks: whatever takes them is launched
        later, so runs later."""
        if self._slots[req.slot] is req:
            self._slots[req.slot] = None
            self._tables[req.slot, :] = 0
        self.allocator.free(req.blocks)
        req.blocks = []
        self._finish_request(req)
        self._work.set()   # freed blocks may unblock the queue head

    def _finish_request(self, req: "_Request") -> None:
        """Complete one request: stats + stream sentinel + done event."""
        self.stats["completed"] += 1
        if req.record is not None:
            self._obs_decode_end(req)
        if req.token_q is not None:
            try:
                req.token_q.put_nowait(None)  # stream sentinel
            except queue.Full:
                pass  # dropped stream: done event carries the signal
        req.done.set()

    def _tick(self) -> bool:
        """One engine tick; the caller holds _tick_lock.  A tick that
        progressed leaves one record of TICK_FIELDS in the tick log; the
        record of a tick whose burst is still unread at its end enters
        the log when that burst is read (the next tick), in its place.
        `start`, `tick_s`: time.time() around this body, never the wait
        on _work.  `decode_s`: the launch of this tick's burst (or spec
        window) plus the wait for the read of the burst before it (of
        this tick's own, where the engine reads every burst at once:
        speculation).  `prefill_s`: the chunks' own
        launch times summed (a launch returns before its chunk has run;
        the device's time for it is waited for in the next `decode_s`
        or `sample_s`).  `sample_s`: the host's reads of the first
        tokens of the prompts this tick finished.  `lanes` of `width`:
        decoding lanes in the tier of the burst this tick launched (0
        of 0 for a tick that only read one).  `prefill_tokens`: prompt
        tokens the chunks carried.  `routed_here`: top-k choices of
        those prompt rows and of the burst's lanes, over the burst's
        steps and the expert layers, that fell on experts held here (a
        model that holds one rank's share of its experts counts them; 0
        from any other; the total is top_k x the rows x the layers).
        `moe_tiles` (the record's last field), from the same models: the
        tiles the tick's launches that grouped their rows by expert
        multiplied, over the expert layers (`ops.moe.grouped_tile_rows`
        rows each: the chunks; a burst visits and counts none), so
        `routed_here` of a tick without a burst over `moe_tiles` x the
        tile's rows is the share of multiplied rows that were routed.
        So tick_s - decode_s -
        prefill_s - sample_s is the tick's time in which the host
        neither waited for the device nor launched: with a burst ahead
        it is no longer time the device stood still for.
        `kv_read_tokens`: KV positions one step of the burst sees, summed
        over its lanes and the planes that are read (the model's count:
        the planes a position keeps x the lanes' lengths where every
        layer keeps every position, one plane a layer, or one a layer
        and pass of a stack run more than once).  `reset_s`: launches
        that zeroed the recurrent state
        of slots this tick admitted to or preempted (0 for a model that
        has none).  `experts_read`: distinct experts the live lanes of
        the burst this tick launched were routed to, and so read, per
        layer and step: the mean over the burst's steps and the model's
        expert layers (0.0 for a model without experts, or a tick
        without a burst).  `ahead`: 1 where the tick launched its burst
        while the one before it was still unread, so that the device had
        it queued behind that one; 0 for a busy period's first burst and
        for a tick without a burst.
        `starved_s`: the part of the tick (and of the wait before it) in
        which the host knew the device's queue to be empty while the
        engine held work: from the return of a blocking read of the
        newest launch's result (a finished prompt's first token, a
        burst with nothing launched behind it) to the dispatch of the
        next burst or chunk, or to the tick's end.  The lower bound of
        the device's idle time that the host causes: what the device
        idled before such a read returned only a trace can show.
        `index_scored_tokens`, `kv_selected_tokens` (behind `moe_tiles`),
        from a model whose full layers attend to a learned selection
        (`cfg.selection_counts`; 0 from any other): the positions the
        indexer scored for the tick's prefill rows and its burst's lanes
        and steps (a row at position p scores p + 1, in every full
        layer), and the positions those rows then attended (at most
        `index_top_k` each): the host's count from the lengths, as
        `kv_read_tokens`.
        `blocks`, `passes`, `block_tokens` (behind them), from a
        model that generates by diffusion over blocks (0 from any other):
        the blocks the burst's lanes filled (lanes x `max_burst` // B),
        the forward passes the burst ran, each over every lane (blocks a
        lane x (`denoise_steps` + 1)), and the tokens counted for its
        lanes at the launch: under blocks x B where a prompt's tail or
        `max_tokens` cuts a block.  For such a model `experts_read` is per
        layer and pass.
        `ring_slots` (the record's last): the slots whose rings one step
        of the tick's burst reads in a window layer
        (`ops.attention.ring_slots_read`: the burst's width where it is
        narrow against the slots, else every slot and the null slot; 0
        for a model without rings or a tick without a burst).
        `group_open_rows` (behind them, in the records of a model that
        holds a share of experts chosen group by group and of no other):
        of the rows `routed_here` counts over, summed over the expert
        layers likewise, those whose kept groups hold an expert held
        here, the rows that *can* route here (the program's count,
        `ops.moe._kept_groups`).
        `select_masked` (behind them, in the records of a model whose
        layers attend to a learned selection and of no other): 100 x the
        reads of the tick's burst that took the selection as a mask over
        the lanes' live pages, of its steps x the layers that select (the
        program's count, `ops.attention._attend_masked`'s own predicate,
        read with the burst's tokens; 0 for a tick without a burst and on
        a platform that fetches).
        `linear_state_rows`, `delta_chunks` (behind them, in the records
        of a model with linear layers and of no other): the state rows (a
        lane's state in one linear layer) one step of the tick's burst
        reads, and writes as many: live lanes x linear layers; and the
        chunks of the delta rule the tick's prefill launches carried, over
        the linear layers (a launch's rows over `linear_chunk`).
        `conv_state_rows` (behind them, in the records of a model with
        conv layers and of no other): the lanes' kept rows one step of the
        tick's burst reads, and writes as many: live lanes x conv layers
        (each `conv_kernel` - 1 rows of the model's width).
        `loop_passes` (behind them, in the records of a model whose stack
        runs more than once and of no other): the layers one step of the
        tick's burst runs a lane, `loop_passes` x `n_layers` (0 for a tick
        without a burst): each a stream of that layer's weights and a read
        of a plane of the pool.
        `hc_res_defect` (behind them, in the records of a model whose
        residual is several streams and of no other: `tick_fields` of
        the stats names a log's fields): the largest |row sum - 1|
        and |column sum - 1| of the projected stream-to-stream matrices
        over the valid rows and the mixes of the tick's chunks and of its
        burst's steps, reduced on the device and read with the records.

        The tick runs on the phase clock (`_PhaseClock`): it starts in
        `admit`, its parts switch the leaf as they go, and it ends in
        `wait`, in which the loop stays until its next tick."""
        clock = self._clock
        clock.ticking = True
        start = clock.enter(_ADMIT)
        acct = self._acct = _TickAccounts()
        progressed = False
        try:
            # Admit as many waiting requests as slots + blocks allow.
            while self._admit_one():
                progressed = True
            progressed |= self._decode_tick()
            progressed |= self._prefill_tick()
            end = clock.enter(_BOOK)
            if self._idle_from:
                # Starved up to here and on into the next tick, if the
                # engine still holds work; else idle for want of it.
                if self._pending or any(r is not None
                                        for r in self._slots):
                    acct.starved_s += end - self._idle_from
                    self._idle_from = end
                else:
                    self._idle_from = 0.0
            if progressed:
                row = [start, end - start, acct.decode_s,
                       acct.prefill_s, acct.sample_s, acct.lanes,
                       acct.width, acct.prefill_tokens,
                       acct.routed_at if self._routed_sums is not None
                       else 0,
                       acct.kv_read_tokens, acct.reset_s,
                       acct.experts_read, acct.ahead, acct.starved_s, 0,
                       acct.index_scored_tokens, acct.kv_selected_tokens,
                       acct.blocks, acct.passes, acct.block_tokens,
                       acct.ring_slots]
                if _GROUP_OPEN_ROWS in self.tick_fields:
                    row.append(0)       # read with `routed_here`
                if self._masked_at:
                    row.append(acct.select_masked)
                if self._linear_layers:
                    row += [acct.linear_state_rows, acct.delta_chunks]
                if self._conv_layers:
                    row.append(acct.conv_state_rows)
                if self._loop_passes:
                    row.append(self._loop_passes if acct.lanes else 0)
                if self._defects is not None:
                    row.append(acct.defect_at)
                b = self._inflight
                if b is not None and b.row is None:
                    b.row = row     # this tick's burst: logged at its read
                else:
                    self._tick_log.append(tuple(row))
        finally:
            clock.enter(_WAIT)
            clock.ticking = False
        return progressed

    def _loop(self):
        while not self._stop:
            with self._tick_lock:
                progressed = self._tick()
            if not progressed:
                self._work.wait(timeout=0.02)
                self._work.clear()

    # -- scoring -----------------------------------------------------------
    def _score_blocks(self, seqs, n_prompt: int, open_rows, on_device,
                      route_kw, got, taken, noised) -> None:
        """`score`'s half for a model that fills blocks: every block of
        `seqs` behind `n_prompt` twice through `_score_step`
        (`paged_block_pass`), all lanes a pass: with its open rows holding
        the mask token (its logits to `got`, its experts to `noised`), then
        committed with its own tokens (its experts to `taken`).
        `on_device`: the lanes' tables and which of them are live."""
        jnp, b = self._jnp, self._block
        lanes, total = seqs.shape
        tables, active = on_device
        w = tables.shape[0]
        for i in range(n_prompt, total, b):
            still = np.ones((lanes, b), bool) if open_rows is None \
                else np.asarray(open_rows)[:, (i - n_prompt) // b]
            block = np.zeros((2, w, b), np.int32)
            block[:, :lanes] = seqs[:, i:i + b]
            block[0, :lanes][still] = self.cfg.mask_token_id
            at = jnp.where(active, i, 0).astype(jnp.int32)
            for toks, kept in zip(block, (noised, taken)):
                self.cache, logits, *route = self._score_step(
                    self.params, self.cache, jnp.asarray(toks), tables, at,
                    active, **route_kw)
                if kept is noised:
                    for lane in range(lanes):
                        got[lane].extend(logits[lane])
                if route:                  # (L, w, B, k) -> (w, B, L, k)
                    route = np.asarray(route[0]).transpose(1, 2, 0, 3)
                    for lane in range(lanes):
                        kept[lane].append(route[lane])

    def score(self, seqs, n_prompt: int, routing: bool = False,
              open_rows=None):
        """Logits by the engine's own programs, for a comparison with a
        reference.  Each row of `seqs` (lanes, n_prompt + steps) gets a
        slot and blocks of its own: its first `n_prompt` tokens are
        prefilled through the engine's jitted chunk program, in the
        launches an idle engine's tick would use (its widest chunk tier,
        then the last launch padded to its tier as a served one is; with
        `routing`, to the widest tier for a model whose launch spans
        chunks, so that one routed program is built),
        then the rest is teacher-forced, all lanes a
        step, through `paged_decode_step` (the function the burst scans;
        the burst itself returns sampled tokens, never logits) at the
        engine's width tier, every kind of sequence state included.
        Returns per lane the logits at positions n_prompt - 1 .. the
        last: 1 + steps arrays of (V,).  With `routing` (a model with
        experts) returns (those, per lane the experts the program took
        at every position, prompt positions too, in every layer:
        (n_prompt + steps, L, top_k) int32; from a model that also selects
        positions a dict of that under "experts" and, under "selected",
        the positions its full layers attended, (n_prompt + steps, full
        layers, index_top_k)), through the same two programs compiled to
        hand them out.  The engine must be idle;
        its state is left as after requests that finished.

        A model that generates by diffusion over blocks of B: `n_prompt`
        and the rest are whole blocks; the first `n_prompt` tokens are
        prefilled as above, and every later block goes twice through the
        pass the burst scans (`paged_block_pass`), all lanes a pass: once
        with the rows `open_rows[lane, block]` ((lanes, blocks, B) bool;
        None: all) holding the mask token, whose logits are what is
        returned, then with its own tokens, the commit that leaves its
        K / V.  Returns per lane the logits of positions n_prompt .. the
        last (row i predicts position i itself), B arrays of (V,) a block,
        and with `routing` per lane a dict: "clean", the experts taken at
        every position by the prefill and the commits, (total, L, top_k),
        and "noised", those the passes over open rows took, (total -
        n_prompt, L, top_k)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.decoding import (
            _bind_cfg, paged_block_pass, paged_decode_step,
            paged_prefill_chunk)

        seqs = np.asarray(seqs)
        lanes, total = seqs.shape
        if lanes > self.num_slots or total > self.max_len:
            raise ValueError(f"score(): {lanes} lanes of {total} tokens do "
                             f"not fit {self.num_slots} slots of "
                             f"{self.max_len}")
        b = self._block
        if b and (n_prompt % b or total % b):
            raise ValueError(f"score(): {self.cfg.name!r} fills blocks of "
                             f"{b}: a prompt of {n_prompt} and {total} "
                             f"tokens in all are not whole blocks")
        if self._score_step is None:
            self._score_step = jax.jit(
                _bind_cfg(paged_block_pass if b else paged_decode_step,
                          self.cfg), donate_argnums=(1,),
                static_argnames=("routing",))
        chunk_fn, route_kw = self._prefill_chunk_fn, {}
        if routing:
            if self._score_chunk is None:
                self._score_chunk = jax.jit(
                    _bind_cfg(paged_prefill_chunk, self.cfg),
                    donate_argnums=(1,), static_argnames=("routing",))
            chunk_fn, route_kw = self._score_chunk, {"routing": True}
        per_lane = math.ceil(total / self.block_size)
        top = self._chunk_tiers[-1]     # an idle engine's budget
        # The routed program is score()'s own, built a shape.  For a
        # model whose launch spans chunks a last launch of fewer tokens
        # is the widest one with a shorter valid prefix: one routed
        # chunk program is built, of the rows a window's launches have.
        one_shape = routing and self._spans_chunks
        got: List[List[Any]] = [[] for _ in range(lanes)]
        taken: List[List[Any]] = [[] for _ in range(lanes)]
        with self._tick_lock:
            self._drain()
            if any(r is not None for r in self._slots) or self._pending:
                raise RuntimeError("score() needs an idle engine")
            blocks = self.allocator.alloc(lanes * per_lane)
            if blocks is None:
                raise RuntimeError("score(): the pool cannot hold "
                                   f"{lanes} x {total} positions")
            try:
                w = self._tier_for(self._width_tiers, lanes)
                tables = np.zeros((w, self._b_max), np.int32)
                for lane in range(lanes):
                    tables[lane, :per_lane] = blocks[
                        lane * per_lane:(lane + 1) * per_lane]
                    if self._recurrent:
                        self.cache = self._reset_state(self.cache,
                                                       jnp.int32(lane))
                    for start in range(0, n_prompt, top):
                        nv = min(top, n_prompt - start)
                        toks = np.zeros(
                            (top if one_shape else
                             self._tier_for(self._chunk_tiers, nv),),
                            np.int32)
                        toks[:nv] = seqs[lane, start:start + nv]
                        self.cache, last, *route = chunk_fn(
                            self.params, self.cache, jnp.asarray(toks),
                            jnp.asarray(tables[lane]), jnp.int32(start),
                            jnp.int32(nv), **self._slot_kw(lane),
                            **route_kw)
                        if routing:            # (L, C, k) -> (nv, L, k)
                            taken[lane].append(jax.tree.map(
                                lambda a: np.asarray(a).swapaxes(0, 1)[:nv],
                                route[0]))
                    if not b:
                        got[lane].append(last)     # position n_prompt - 1
                active = np.arange(w) < lanes
                on_device = (jnp.asarray(tables), jnp.asarray(active))
                lanes_kw = self._lanes_kw(list(range(lanes)), w)
                noised: List[List[Any]] = [[] for _ in range(lanes)]
                if b:
                    self._score_blocks(seqs, n_prompt, open_rows, on_device,
                                       route_kw, got, taken, noised)
                else:
                    for i in range(n_prompt, total):
                        tok = np.zeros((w,), np.int32)
                        tok[:lanes] = seqs[:, i]
                        at = np.where(active, i, 0).astype(np.int32)
                        self.cache, logits, *route = self._score_step(
                            self.params, self.cache, jnp.asarray(tok),
                            on_device[0], jnp.asarray(at), on_device[1],
                            **lanes_kw, **route_kw)
                        if routing:                # (L, w, k) -> (w, L, k)
                            route = jax.tree.map(
                                lambda a: np.asarray(a).swapaxes(0, 1),
                                route[0])
                        for lane in range(lanes):
                            got[lane].append(logits[lane])     # position i
                            if routing:
                                taken[lane].append(jax.tree.map(
                                    lambda a: a[lane][None], route))
            finally:
                self.allocator.free(blocks)
        if routing and b:
            return got, [{"clean": np.concatenate(t),
                          "noised": np.concatenate(n)}
                         for t, n in zip(taken, noised)]
        if routing:
            return got, [jax.tree.map(lambda *a: np.concatenate(a), *t)
                         for t in taken]
        return got

    # -- disaggregated serving / live migration -------------------------
    def _refuse_if_by_slot(self, what: str) -> None:
        if self._block:
            raise ValueError(
                f"{what} with {self.cfg.name!r}: it generates by diffusion "
                f"over blocks; a frame ends with a partial page and the "
                f"last token's logits, of which this model samples "
                f"nothing, and no test yet holds a shipped stream of it "
                f"to a local one")
        if self._by_slot:
            raise ValueError(
                f"{what} with {self.cfg.name!r}: its sequences keep "
                f"{_slot_state(self.cfg)} by slot, and pool blocks alone "
                f"are not a sequence; shipping or adopting one needs a "
                f"snapshot of that state, which this engine does not take")

    def import_prefix(self, tokens: List[int], kv, block_size: int,
                      last_logits=None) -> int:
        """Adopt a KV frame computed by ANOTHER engine (a dedicated
        prefill actor's handoff, or a draining replica's live-migration
        export) into this engine's block pool: allocate blocks, scatter
        the frame on-device, register the prefix, park the blocks
        cached-free.  The next admission of a prompt starting with
        ``tokens`` walks the ordinary prefix-hit path — zero recompute.

        Returns the number of blocks imported; 0 when the frame can't
        be adopted (geometry mismatch, pool exhausted, sharing off) —
        the caller falls back to recompute.  Thread-safe against the
        engine loop (tick lock)."""
        import numpy as np

        from ray_tpu.models.decoding import frame_fits, scatter_blocks

        self._refuse_if_by_slot("import_prefix")
        kv = np.asarray(kv)
        n_need = -(-len(tokens) // self.block_size)
        if (block_size != self.block_size
                or not frame_fits(self.cache, kv.shape)
                or kv.shape[2] < n_need):
            return 0
        meta = (self._jnp.asarray(last_logits)
                if last_logits is not None else None)
        with self._tick_lock:
            self._drain()
            blocks = self.allocator.adopt(tokens, meta=meta)
            if blocks is None:
                return 0
            self.cache = scatter_blocks(self.cache, blocks,
                                        kv[:, :, :len(blocks)])
            # Our allocation reference retires; registered blocks park
            # cached-free with contents intact, exactly like a finished
            # request's published prefix.
            self.allocator.free(blocks)
            return len(blocks)

    def export_streams(self) -> List[Dict[str, Any]]:
        """Snapshot every in-flight DECODING stream as a migration
        ticket: the context tokens whose KV is already written (the
        last emitted token's KV is pending as the next decode input, so
        it stays out) plus the device frame of the covering blocks.
        The receiving engine `import_prefix`s the frame and the
        handle's resume protocol re-admits prompt+emitted — which then
        prefix-hits the imported chain and recomputes at most one
        partial block instead of the whole context.  Exact KV roundtrip
        keeps a greedy stream's continuation byte-identical to never
        having moved."""
        import jax
        import numpy as np

        from ray_tpu.models.decoding import gather_blocks

        self._refuse_if_by_slot("export_streams")
        out: List[Dict[str, Any]] = []
        bs = self.block_size
        with self._tick_lock:
            self._drain()
            for i, req in enumerate(self._slots):
                if req is None or req.prefilling or req.token_q is None:
                    continue
                rid = (req.trace or {}).get("trace_id")
                if not rid:
                    continue  # untraceable: recompute fallback applies
                n_kv = int(self._lengths[i])
                ctx = req.prompt + req.out_tokens
                n_kv = min(n_kv, len(ctx))
                nb = min(len(req.blocks), -(-n_kv // bs)) if n_kv else 0
                if nb <= 0:
                    continue
                frame = np.asarray(jax.device_get(
                    gather_blocks(self.cache, req.blocks[:nb])))
                out.append({"request_id": rid,
                            "tokens": list(ctx[:n_kv]),
                            "block_size": bs, "kv": frame})
        return out

    # -- serving observability ------------------------------------------
    # Spans attribute each engine phase (queue_wait / prefill_wait /
    # prefill > prefill_chunk / decode_burst) to the request's trace;
    # histograms decompose TTFT / ITL per app; `_request_phases` keeps
    # the same edges per request for engine_stats().  Spans gate on
    # req.trace (None when the RAY_TPU_SERVE_TRACE_ENABLED kill switch
    # is off); histograms and the record fill either way.  The app tag
    # is learned lazily from traced requests — standalone engines
    # (bench, unit tests) report under "-".
    _app_hint = "-"
    # engine_stats()["request_phases"]: the last requests that got a
    # first token, one dict each (see _obs_first_token, _obs_decode_end).
    REQUEST_PHASES_KEPT = 1024

    def _obs_submit(self, req: "_Request",
                    trace: Optional[dict]) -> None:
        # Direct engine use (no proxy/handle upstream) mints its own
        # trace so span coverage — and the overhead the kill switch
        # removes — is identical with and without the HTTP front.
        req.trace = (trace if trace is not None
                     else tracing.serve_ctx(uuid.uuid4().hex))

    def _obs_app(self, req: "_Request") -> str:
        app = req.trace.get("app") if req.trace else None
        if app:
            self._app_hint = app
            return app
        return self._app_hint

    def _obs_admitted(self, req: "_Request") -> None:
        from ray_tpu.serve import observability

        now = req.admitted_at = time.time()
        tracing.record_serve_span(req.trace, "serve.engine.queue_wait",
                                  req.submitted_at, now,
                                  tokens=len(req.prompt))
        observability.observe_phase(self._obs_app(req), "queue_wait",
                                    now - req.submitted_at)

    def _obs_prefill(self, req: "_Request", t0: float,
                     n_tokens: int, rows: int) -> float:
        """One prefill chunk of `n_tokens` prompt tokens in a launch of
        `rows` (its tier) launched at `t0`; returns its wall time.
        A request's first chunk ends its prefill_wait and opens its
        `serve.engine.prefill` span, the parent of every chunk up to the
        first token.  A preempted request's re-prefill comes after its
        first token: its chunks are marked `resumed` and stay out of the
        TTFT phases."""
        from ray_tpu.serve import observability

        t1 = time.time()
        app = self._obs_app(req)
        ctx, attrs = req.trace, {}
        if req.first_token_at is not None:
            attrs["resumed"] = 1
        else:
            if req.prefill_at is None:
                req.prefill_at = t0
                tracing.record_serve_span(
                    ctx, "serve.engine.prefill_wait", req.admitted_at, t0,
                    tokens=len(req.prompt))
                observability.observe_phase(app, "prefill_wait",
                                            t0 - req.admitted_at)
                req.prefill_span = tracing.open_serve_span(
                    ctx, "serve.engine.prefill", t0)
            req.chunks += 1
            req.chunk_s += t1 - t0
            req.chunk_tokens += n_tokens
            ctx = tracing.child_ctx(ctx, req.prefill_span)
        tracing.record_serve_span(ctx, "serve.engine.prefill_chunk",
                                  t0, t1, tokens=n_tokens, rows=rows,
                                  pos=req.pos, **attrs)
        observability.observe_phase(app, "prefill", t1 - t0)
        return t1 - t0

    def _obs_first_token(self, req: "_Request") -> None:
        """Stamp the first token: it ends the prefill span and with it
        the chain queue_wait + prefill_wait + prefill_span = TTFT, which
        goes into the request_phases record as one dict.  A whole-prompt
        prefix hit launches no chunk: it has no prefill_wait, and its
        prefill_span is the sampling of the stored logits."""
        from ray_tpu.serve import observability

        now = req.first_token_at = req.last_emit_wall = time.time()
        if req.prefill_at is None:
            req.prefill_at = req.admitted_at
        observability.metrics()["ttft"].observe(
            now - req.submitted_at, {"app": self._obs_app(req)})
        if req.prefill_span is not None:
            req.prefill_span.attrs.update(
                tokens=req.chunk_tokens, chunks=req.chunks,
                chunk_s=req.chunk_s)
            req.prefill_span.finish(now)
            req.prefill_span = None
        # The decode half opens here and is filled in at the request's
        # end (_finish_request); until then its keys read None.
        req.decode_from = self._clock.read(now)
        req.decode_span = tracing.open_serve_span(
            req.trace, "serve.engine.decode", now)
        req.record = {
            "id": req.trace["trace_id"] if req.trace else None,
            "submitted": req.submitted_at,
            "queue_wait_s": req.admitted_at - req.submitted_at,
            "prefill_wait_s": req.prefill_at - req.admitted_at,
            "prefill_span_s": now - req.prefill_at,
            "ttft_s": now - req.submitted_at,
            **dict.fromkeys(_DECODE_KEYS)}
        self._request_phases.append(req.record)

    def _obs_decode_end(self, req: "_Request") -> None:
        """The request is over: what the engine's thread did between its
        first token and now, from two readings of the phase clock.  The
        three sums are the whole of `decode_s`, exactly: the time the
        thread waited for a burst's tokens, the time it waited for other
        prompts' first tokens, and every other leaf (launches, emit,
        admit, book, wait).  They complete the request's record and go
        onto its `serve.engine.decode` span, the parent of its
        `decode_burst` spans."""
        now = time.time()
        spent = [b - a for a, b in zip(req.decode_from,
                                       self._clock.read(now))]
        sums = {
            "decode_s": now - req.first_token_at,
            "n_out": len(req.out_tokens),
            "burst_read_s": spent[_BURST_READ],
            "first_read_s": spent[_FIRST_READ],
            "host_s": sum(spent) - spent[_BURST_READ] - spent[_FIRST_READ],
            "lanes_seen": (req.lanes_sum / req.bursts if req.bursts
                           else 0.0)}
        req.record.update(sums)     # the keys are there: no resize under
        #                             a reader on another thread
        if req.decode_span is not None:
            req.decode_span.attrs.update(sums)
            req.decode_span.finish(now)
            req.decode_span = None

    def _obs_burst(self, req: "_Request", t0: float, t1: float,
                   n_new: int) -> None:
        """Per fused-burst, per-request: one decode_burst span, one
        decode_step phase sample, and ONE inter-token-latency sample at
        the burst-mean gap (per-token observes would cost more than the
        decode itself at small models)."""
        if n_new <= 0:
            return
        from ray_tpu.serve import observability

        app = self._obs_app(req)
        tracing.record_serve_span(
            tracing.child_ctx(req.trace, req.decode_span),
            "serve.engine.decode_burst", t0, t1, tokens=n_new)
        observability.observe_phase(app, "decode_step", t1 - t0)
        if req.last_emit_wall is not None and t1 > req.last_emit_wall:
            observability.metrics()["itl"].observe(
                (t1 - req.last_emit_wall) / n_new, {"app": app})
        req.last_emit_wall = t1


def dryrun_tp_serving(cfg, tp: int, *, timeout: float = 45.0) -> None:
    """Compile-and-run check for tensor-parallel serving on the current
    devices (the serving analogue of parallel.pipeline.dryrun_pipeline;
    the driver's multichip dry-run calls this). The short timeout keeps
    a stalled sharded compile failing INSIDE an external ~60s budget
    with a clear error rather than an opaque external kill."""
    import jax

    from ray_tpu.models import init_params
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tp=tp, fsdp=1),
                      devices=jax.devices()[:tp])
    eng = PagedLLMEngine(cfg, init_params(jax.random.key(1), cfg),
                         num_slots=2, max_len=64, block_size=8,
                         prefill_chunk=16, mesh=mesh)
    try:
        out = eng.generate([1, 2, 3], max_tokens=4, timeout=timeout)
        assert len(out) == 4, out
    finally:
        eng.shutdown()


class LLMDeployment:
    """Serve-deployable wrapper: __call__({"tokens": [...], ...}) →
    {"tokens": [...]}.  Build with serve.deployment(LLMDeployment).bind(...).

    Every deployment serves through `PagedLLMEngine`; `engine` accepts
    `"paged"` only (ROADMAP D14).  `tensor_parallel=N` claims N local
    chips as a `tp` mesh and hands it to the engine."""

    def __init__(self, cfg_name, *, engine: str = "paged",
                 num_slots: int = 8, max_len: int = 512, seed: int = 0,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 speculation_k: Optional[int] = None,
                 tensor_parallel: int = 0,
                 prefix_sharing: Optional[bool] = None,
                 disagg: Optional[bool] = None,
                 params_loader: Optional[Callable] = None):
        """`cfg_name`: a registry name (ray_tpu.models.configs) or a
        configuration object (a TransformerConfig, or a model that
        brings its own sequence state such as models.hybrid.HybridConfig)
        — e.g. the config half of
        `ray_tpu.models.from_hf(...)`, with `params_loader` returning
        the converted weights (serve real HF checkpoints).

        The constructor's parts are recorded as the spans of
        `_SetupSpans` and every program it compiles in the compile log:
        `runtime_report()` hands out both."""
        setup = _SetupSpans()
        setup.root = setup.open("serve.setup", num_slots=num_slots,
                                max_len=max_len)
        import jax

        from ray_tpu.models import configs, init_params

        if engine != "paged":
            raise ValueError(
                f"engine={engine!r}: every deployment is served by the "
                f"paged engine (engine='paged', PagedLLMEngine)")
        # Start the compile log before the first compile, so
        # runtime_report() can say what this replica's start cost.
        compile_cache.counts()
        cfg = (configs.get(cfg_name) if isinstance(cfg_name, str)
               else cfg_name)
        setup.root.attrs["cfg"] = cfg.name
        by_slot = bool(getattr(cfg, "state_by_slot", False))
        by_block = bool(getattr(cfg, "diffusion_block", 0))
        if by_block and (tensor_parallel > 1 or disagg):
            raise ValueError(
                f"{cfg.name!r} generates by diffusion over blocks: it is "
                f"served by the paged engine on one device, without "
                f"disaggregated prefill (a prefill actor's frame carries "
                f"a causal prompt's tail page and last logits; this "
                f"model's prompt is prefilled in whole blocks and sampled "
                f"from nowhere)")
        if by_slot and (tensor_parallel > 1 or disagg):
            raise ValueError(
                f"{cfg.name!r} keeps {_slot_state(cfg)} by slot: it is "
                f"served by the paged engine on one device, without "
                f"disaggregated prefill (a shipped KV frame is not its "
                f"sequence)")
        # The process's first touch of the backend, which whatever ran
        # first below would pay unnamed.
        with setup.span("serve.setup.device_init") as span:
            devices = jax.devices()
            span.attrs.update(platform=devices[0].platform,
                              count=len(devices))
        with setup.span("serve.setup.params",
                        loader=bool(params_loader)) as span:
            if params_loader:
                params = params_loader()
            else:   # a model that brings its own stack brings its own
                own_init = getattr(cfg, "init_params", None)
                params = (own_init(jax.random.key(seed)) if own_init
                          else init_params(jax.random.key(seed), cfg))
            jax.block_until_ready(params)
            span.attrs["bytes"] = sum(
                int(x.nbytes) for x in jax.tree_util.tree_leaves(params))
        mesh = None
        if tensor_parallel > 1:
            # Claim N local chips as a tp mesh for this replica (the
            # router still spreads requests across replicas).
            # build_mesh permutes devices so the tp axis sits on
            # contiguous ICI neighborhoods — exactly where per-token
            # all-reduces must live.
            from ray_tpu.parallel.mesh import MeshConfig, build_mesh

            devs = jax.devices()[:tensor_parallel]
            if len(devs) < tensor_parallel:
                raise ValueError(
                    f"tensor_parallel={tensor_parallel} > "
                    f"{len(jax.devices())} visible devices")
            mesh = build_mesh(MeshConfig(tp=tensor_parallel, fsdp=1),
                              devices=devs)
        store = None
        try:
            import ray_tpu.api as _api

            if _api.is_initialized():
                store = getattr(_api._global_worker(), "store", None)
        except Exception:  # noqa: BLE001 standalone use
            store = None
        self.engine = PagedLLMEngine(
            cfg, params, num_slots=num_slots, max_len=max_len,
            block_size=block_size, num_blocks=num_blocks,
            prefill_chunk=prefill_chunk, seed=seed,
            prefix_sharing=prefix_sharing,
            speculation_k=speculation_k, store=store, mesh=mesh,
            setup=setup)
        # Disaggregated serving: this replica decodes; chunked prefill
        # of long prompts offloads to dedicated prefill actors whose
        # finished KV blocks ship back as frames (serve/disagg.py).
        from ray_tpu.core.config import get_config

        if disagg is None:
            disagg = get_config().serve_disagg_enabled \
                and not (by_slot or by_block)
        self._disagg = None
        self.disagg_role = "unified"
        # Prefill actors re-derive weights from (cfg, seed); a custom
        # params_loader would hand them different weights than this
        # replica decodes with — KV frames would silently mismatch.
        if disagg and params_loader is None:
            from ray_tpu.serve.disagg import DisaggPrefillClient

            self._disagg = DisaggPrefillClient(
                cfg_name=cfg_name, seed=seed,
                block_size=self.engine.block_size,
                max_len=max_len)
            self.disagg_role = "decode"
        setup.close(setup.root)

    def set_serve_context(self, app: str, replica_id: str) -> None:
        """Replica-actor hook: lets the disagg client tag its prefill
        actors' gauge pushes with the hosting app."""
        if self._disagg is not None:
            self._disagg.set_serve_context(app, replica_id)

    def _maybe_offload_prefill(self, tokens,
                               trace: Optional[dict] = None) -> None:
        """Disagg hot path: a long prompt whose KV this replica doesn't
        already hold prefills on a dedicated prefill actor; the finished
        blocks ship back as a frame and import into the local pool, so
        the engine's own admission sees a whole-prompt prefix hit and
        the decode loop never runs the long prefill chunks.  Any
        failure (actor down, pool full) degrades to local prefill."""
        if self._disagg is None:
            return
        t0 = time.time()
        try:
            offloaded = self._disagg.prefill_into(self.engine,
                                                  list(tokens))
        except Exception:  # noqa: BLE001 degrade to local prefill
            return
        if offloaded:
            tracing.record_serve_span(trace, "serve.prefill.offload",
                                      t0, time.time(),
                                      tokens=len(tokens))

    def __call__(self, request: dict,
                 _serve_trace: Optional[dict] = None) -> dict:
        self._maybe_offload_prefill(request["tokens"],
                                    trace=_serve_trace)
        toks = self.engine.generate(
            request["tokens"],
            max_tokens=int(request.get("max_tokens", 32)),
            temperature=float(request.get("temperature", 0.0)),
            trace=_serve_trace)
        return {"tokens": toks}

    def stream(self, request: dict, _serve_resume: Optional[dict] = None,
               _serve_trace: Optional[dict] = None):
        """Streaming entry: yields {"token": t} dicts (served over
        chunked HTTP by the proxy; call via handle.remote_streaming).

        `_serve_resume` is the replica-injected failover context
        ({"offset": n, "items": [...]}): the tokens a dead replica
        already delivered are re-admitted through the engine's recompute
        path (resume_tokens) so this replica yields only the
        continuation — no duplicated or re-generated tokens."""
        resume = [it["token"] for it in (_serve_resume or {}).get(
            "items", []) if isinstance(it, dict) and "token" in it]
        if not resume:
            self._maybe_offload_prefill(request["tokens"])
        for tok in self.engine.generate_stream(
                request["tokens"],
                max_tokens=int(request.get("max_tokens", 32)),
                temperature=float(request.get("temperature", 0.0)),
                resume_tokens=resume or None,
                trace=_serve_trace):
            yield {"token": tok}

    def stats(self, _request: Optional[dict] = None) -> dict:
        return self.engine.engine_stats()

    def runtime_report(self, _request: Optional[dict] = None) -> dict:
        """What this replica's process computes on and what its start
        cost — asked of the process that owns the chip, because a
        caller that asked JAX would take it.  `compile_cache`: the
        cache's traffic and the compile log's running totals
        (`compile_cache.counts()`); `setup`, as in `engine_stats()`:
        `compile_log`, one entry per program traced, lowered and loaded
        or compiled, with its seconds by phase and `cache` "hit" /
        "miss" / "off" (`compile_cache.log()`), and `spans`, the
        `serve.setup*` spans of the constructor and of `warmup()`, on
        the log's clock.  `cache: "miss"` after the first start of a
        checkout, or an entry that starts after the `serve.setup` span
        has ended (a tier compiled inside serving), is a stall worth a
        look."""
        from ray_tpu.util.tpu import device_report

        return {"device": device_report(),
                "compile_cache": compile_cache.counts(),
                "setup": self.engine.setup_records()}

    def serve_state(self) -> dict:
        """Replica gauge-loop hook: disagg role + the digests of this
        engine's registered (aligned) prefixes.  Rides the existing
        report_serve_gauges/syncer push into the GCS-resident prefix
        registry (no new RPC plane); the handle's prefix-affinity
        routing reads the merged owner map back out of controller
        routing state."""
        from ray_tpu.core.config import get_config

        cfg = get_config()
        state: dict = {"role": self.disagg_role}
        es = self.engine.engine_stats(records=False)
        if es.get("spec_proposed"):
            state["spec_accept_rate"] = round(
                es.get("spec_accepted", 0) / es["spec_proposed"], 4)
        # The loop thread's seconds by leaf phase: `ray-tpu serve
        # status` shows them as shares.
        state["phase_seconds"] = {k: round(v, 3) for k, v in
                                  es["phase_seconds"].items()}
        if cfg.serve_prefix_registry_enabled:
            state["block_size"] = int(self.engine.block_size)
            state["prefixes"] = self.engine.allocator.prefix_digests(
                limit=cfg.serve_prefix_registry_max_entries)
        return state

    def adopt_kv(self, tokens, kv, block_size: int, last_logits=None,
                 source: str = "migrate") -> int:
        """Import a shipped KV frame (migration ticket / disagg handoff)
        into the hosted engine's pool.  Raises KVMigrationError when the
        engine can't adopt it — the caller's recompute fallback takes
        over.  Returns the number of blocks imported."""
        from ray_tpu.exceptions import KVMigrationError

        n = self.engine.import_prefix(tokens, kv, block_size,
                                      last_logits=last_logits)
        if n <= 0:
            raise KVMigrationError(
                reason=f"import_prefix rejected frame "
                       f"({len(tokens)} tokens, block_size "
                       f"{block_size})")
        key = ("migrated_blocks" if source == "migrate"
               else "adopted_blocks")
        self.engine.stats[key] += n
        return n

    def engine_gauges(self) -> dict:
        """Replica gauge hook: the Replica actor piggybacks these on the
        node daemon's syncer push (serve autoscaling input)."""
        return self.engine.gauges()
