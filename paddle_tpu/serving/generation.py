"""Continuous-batching generation server over the paged KV-cache.

The static decode loop (models/transformer.build_lm_kv_decoder) serves
a CLOSED batch: everyone starts together, nobody leaves until the last
sequence finishes, and a new request waits for the whole batch to
drain.  `GenerationServer` replaces that with the vLLM-style in-flight
schedule:

* ONE resident decode step (build_lm_paged_decoder) runs per tick over
  the active slot set — a single device dispatch per token position;
* the scheduler runs ONE TICK AHEAD of the device: it dispatches tick
  n+1 and only then reads, delivers and evicts tick n.  All tick n+1
  needs from tick n is each decoding slot's sampled token, and that
  stays on the device (selected against the host's prompt tokens just
  before the step); cursors, positions, seeds and the `max_new` finish
  are known to the host without it.  Admission, delivery, the array
  building and the dispatch itself so run under the device's step
  (docs/serving.md "The scheduler loop");
* BETWEEN ticks the scheduler admits queued requests into free slots
  (prefill is folded into the same per-token step: a just-admitted
  sequence is teacher-forced through its prompt positions while
  everyone else decodes), evicts finished sequences IMMEDIATELY and
  returns their KV blocks to the pool;
* admission is keyed to free KV blocks (a request is admitted only
  when its whole prompt+max_new budget fits, so decode can never hit
  an out-of-pool condition mid-sequence), queued requests past their
  deadline are shed at dequeue, and a full queue rejects with
  ServerSaturated at submit;
* every request streams tokens through its own `GenerationStream`
  future, and per-request numerics are bit-identical to running the
  same prompt alone (slot math is independent of batch composition —
  tests/test_generation_serving.py pins this);
* `swap_states` performs the zero-downtime checkpoint hot swap: stop
  admitting, let active sequences drain, swap parameters, resume —
  queued requests wait instead of failing.

`static_batch=True` degrades the scheduler to the drain-then-refill
baseline (admit only into an EMPTY active set) — same compiled step,
same numerics: what tests/test_generation_serving.py measures the
continuous schedule against.

On top of the PR 8 substrate ride the two algorithmic serving
optimizations (docs/serving.md):

* PREFIX CACHING (`prefix_cache=True`, default): admission allocates
  through `PagedKVCache.allocate_prefix`, which shares fully-filled
  prompt blocks already resident from an earlier sequence with the
  same prompt prefix — the cursor then STARTS past the shared
  positions, skipping their prefill ticks entirely.  K/V at a position
  is a deterministic function of the token prefix, so shared blocks
  hold exactly what this sequence's prefill would have written:
  greedy output stays bit-identical to a cold run.
* SPECULATIVE DECODING (`draft_decoder`/`draft_states`, optional): a
  small draft model proposes `spec_k` greedy tokens per tick and the
  target verifies the whole window in ONE `step_window` dispatch.  The
  accept rule is the greedy degenerate of accept/resample — keep
  proposals while they equal the target's own argmax chain, then emit
  the target's next token as the bonus — so the emitted stream is the
  target's greedy output BY CONSTRUCTION; a tick delivers between 1
  and spec_k+1 tokens.  The draft keeps its own KV pool indexed by the
  SAME block tables (admission accounts blocks once; prefix hits warm
  both pools).  Sampled (temperature>0) requests take the plain
  one-token path — their per-(seed, position) PRNG contract is
  untouched.  Prefill is chunked through the same window step
  (spec_k+1 prompt positions per tick) when a draft is armed.
"""
from __future__ import annotations

import functools
import itertools
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import profiler
from ..core.resilience import (fault_injector,
                               sched_fault_armed as _sched_fault)
from ..observability import attribution as obs_attr
from ..observability import flightrecorder
from ..observability import metrics as obs_metrics
from ..observability import tracing as obs_tracing
from .batching import RequestDeadlineExceeded, ServerSaturated
from .kv_cache import KVPoolExhausted, PagedKVCache

_LOG = logging.getLogger(__name__)

__all__ = ["GenerationServer", "GenerationStream",
           "save_generation_model", "load_generation_model"]

MODEL_SPEC_FILENAME = "generation.json"
MODEL_PARAMS_FILENAME = "generation_params.npz"
MODEL_DRAFT_PARAMS_FILENAME = "generation_draft_params.npz"
_SERVER_IDS = itertools.count()
# draft tokens proposed a slot a speculative tick where `spec_k` is None
_SPEC_K = 4
# stats()-backing series are always=True (the stats contract predates
# the PADDLE_TPU_METRICS switch); latency/depth series are gated.
_M_REQUESTS = obs_metrics.counter(
    "paddle_tpu_serving_generation_requests_total",
    "generation requests admitted to a decode slot", ("server",),
    always=True)
_M_TOKENS = obs_metrics.counter(
    "paddle_tpu_serving_generated_tokens_total",
    "generated tokens delivered to request streams", ("server",),
    always=True)
_M_TICKS = obs_metrics.counter(
    "paddle_tpu_serving_decode_ticks_total",
    "resident decode steps dispatched (tokens/tick = active slots)",
    ("server",), always=True)
_M_SHED = obs_metrics.counter(
    "paddle_tpu_serving_generation_shed_total",
    "requests shed instead of decoded, by reason "
    "(saturated: full queue at submit; deadline: expired while queued)",
    ("server", "reason"), always=True)
_M_SWAPS = obs_metrics.counter(
    "paddle_tpu_serving_hot_swaps_total",
    "zero-downtime checkpoint hot swaps completed", ("server",),
    always=True)
_M_LATENCY = obs_metrics.histogram(
    "paddle_tpu_serving_generation_seconds",
    "submit -> last-token wall latency per request", ("server",))
_M_TTFT = obs_metrics.histogram(
    "paddle_tpu_serving_first_token_seconds",
    "submit -> first generated token wall latency", ("server",))
_M_ACTIVE = obs_metrics.gauge(
    "paddle_tpu_serving_active_sequences",
    "sequences currently holding a decode slot", ("server",))
_M_QDEPTH = obs_metrics.gauge(
    "paddle_tpu_serving_generation_queue_depth",
    "requests waiting for admission", ("server",))
_M_DRAFT_PROPOSED = obs_metrics.counter(
    "paddle_tpu_serving_draft_proposed_total",
    "draft-model tokens proposed for target verification "
    "(speculative decoding)", ("server",), always=True)
_M_DRAFT_ACCEPTED = obs_metrics.counter(
    "paddle_tpu_serving_draft_accepted_total",
    "draft proposals accepted by the target's verify step "
    "(accept rate = accepted / proposed)", ("server",), always=True)


class GenerationStream:
    """Per-request streaming future: tokens arrive as the scheduler
    delivers them; `result()` blocks for the full generation.

    for tok in stream:            # streams tokens as they are decoded
        ...
    ids = stream.result()         # or: block until finished

    A failed request raises from both paths; a shed request raises the
    shed error (RequestDeadlineExceeded)."""

    def __init__(self, prompt: Sequence[int], max_new: int):
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new)
        self._cond = threading.Condition()
        self._tokens: List[int] = []
        self._done = False
        self._exc: Optional[BaseException] = None
        self._watchers = 0

    # -- scheduler side -----------------------------------------------------
    def _put(self, tok: int):
        with self._cond:
            self._tokens.append(int(tok))
            # wake waiters per token only when a live iterator streams
            # this request; result()-style waiters block on `done` and
            # a wakeup per token is pure GIL churn on the decode path
            # (it measurably dilutes the continuous-batching win)
            if self._watchers:
                self._cond.notify_all()

    def _finish(self):
        with self._cond:
            self._done = True
            self._cond.notify_all()

    def _fail(self, exc: BaseException):
        with self._cond:
            if not self._done:
                self._exc = exc
                self._done = True
                self._cond.notify_all()

    # -- client side --------------------------------------------------------
    @property
    def done(self) -> bool:
        with self._cond:
            return self._done

    def tokens_so_far(self) -> List[int]:
        with self._cond:
            return list(self._tokens)

    def __iter__(self):
        if _sched_fault("stream.yield-under-lock"):
            # the pre-PR-8 bug, reintroducible ONLY for the schedule
            # checker's regression pin (tests/test_concurrency_
            # analysis.py): yielding with the lock held lets a slow
            # consumer stall the scheduler's _put
            yield from self._iter_yield_under_lock()
            return
        i = 0
        with self._cond:
            self._watchers += 1
        try:
            while True:
                # snapshot under the lock, yield OUTSIDE it: a consumer
                # that processes tokens slowly (a replica writing to a
                # slow TCP client) must never block the scheduler's
                # _put — that would stall every other request's decode
                with self._cond:
                    self._cond.wait_for(
                        lambda: self._done or len(self._tokens) > i)
                    batch = self._tokens[i:]
                    done = self._done  # final: no tokens arrive after
                    exc = self._exc
                for tok in batch:
                    yield tok
                i += len(batch)
                if done:
                    if exc is not None:
                        raise exc
                    return
        finally:
            with self._cond:
                self._watchers -= 1

    def _iter_yield_under_lock(self):
        i = 0
        with self._cond:
            self._watchers += 1
            try:
                while True:
                    self._cond.wait_for(
                        lambda: self._done or len(self._tokens) > i)
                    while i < len(self._tokens):
                        yield self._tokens[i]   # lock HELD across yield
                        i += 1
                    if self._done:
                        if self._exc is not None:
                            raise self._exc
                        return
            finally:
                self._watchers -= 1

    def result(self, timeout: Optional[float] = None) -> List[int]:
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout):
                raise TimeoutError("generation still running")
            if self._exc is not None:
                raise self._exc
            return list(self._tokens)


class _Seq:
    """Scheduler-internal state of one admitted request."""

    __slots__ = ("stream", "tokens", "prompt_len", "max_new", "eos_id",
                 "temperature", "seed", "cur", "slot", "emitted",
                 "t_submit", "t_submit_wall", "t_admit", "t_first",
                 "cached", "expires", "trace_ctx", "draft_next",
                 "prompt_keys", "snap_at", "snap_row", "snap_saved", "cut",
                 "table")

    def __init__(self, stream, max_new, eos_id, temperature, seed,
                 expires, trace_ctx):
        self.stream = stream
        self.tokens = list(stream.prompt)
        self.prompt_len = len(stream.prompt)
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.seed = int(seed) & 0xFFFFFFFF
        # next position to DISPATCH (it advances when a tick goes out,
        # not when its token comes back)
        self.cur = 0
        self.slot = -1
        self.emitted = 0
        self.t_submit = time.perf_counter()
        self.t_submit_wall = time.time()
        # stamped by the scheduler (perf_counter): admission to a slot
        # and the first delivered token split the `serving.request`
        # span into queue_s / prefill_s / decode_s; `cached` is the
        # prompt positions the prefix cache supplied
        self.t_admit = None
        self.t_first = None
        self.cached = 0
        self.expires = expires
        self.trace_ctx = trace_ctx
        # next position the DRAFT model's KV is missing (speculative
        # decoding: the draft trails the target by at most one
        # position after a fully-accepted window)
        self.draft_next = 0
        # chained prefix-cache block keys, computed ONCE at submit
        # (the scheduler re-checks a blocked queue head every tick)
        self.prompt_keys = None
        # a decoder with a lane state under the prefix cache: the cursor
        # after which this prompt's ONE snapshot is saved (its last full
        # block boundary; -1: none), the row its admission restores
        # (None: none), whether its own was saved and the blocks that
        # hit by hash past the last snapshot
        self.snap_at = -1
        self.snap_row = None
        self.snap_saved = 0
        self.cut = 0
        # the request's block table from its admission on (`_Table`)
        self.table = None

    @property
    def positions_needed(self) -> int:
        # the cursor writes K/V at positions 0 .. prompt+max_new-2 (the
        # final emitted token is delivered, never re-attended)
        return self.prompt_len + self.max_new - 1


class _Table:
    """A request's block table as the cache made it at admission (`row`,
    int32 [max_blocks_per_seq]; nobody writes it again: the server copies
    it into `_tables`, and a request's blocks do not change while it
    holds them), and the DMA starts the kernels' issue loop saves over
    it (`saved`: `decoder.starts_saved`'s row a key, a memo the first
    reader of a traced tick's account makes).  A traced tick's account
    holds its lanes' by reference: not the `_Seq`, which has a stream
    and its tokens behind it."""

    __slots__ = ("row", "saved")

    def __init__(self, row):
        self.row = row
        self.saved = None


class _TickAccount:
    """What a traced tick's span is given in place of its counts, one a
    server: `of(...)` returns the function `Span.defer` takes, which
    calls `decoder.tick_counts` on the thread of whoever first reads the
    record (docs/observability.md "Span vocabulary").  The scheduler
    hands it what `build` has made anyway and never calls it.  It holds
    the decoder and the slots' rings, not the server: a record outlives
    `close()`."""

    __slots__ = ("_decoder", "_slots", "_rings", "_ring_saved")

    def __init__(self, decoder, slots, rings):
        self._decoder = decoder
        self._slots = slots
        self._rings = rings
        self._ring_saved = None     # made by the first reader

    def of(self, cur, lanes=None, held=None):
        """`cur`: the step's `positions` at the lanes that tick, in the
        lanes' order (an array of the tick's own).  `lanes`, `held`: the
        mask of those lanes and a list over ALL lanes with their
        `_Table`s; without them the tick is a `step_window` tick, which
        gathers always."""
        return functools.partial(self._counts, cur, lanes, held)

    def _counts(self, cur, lanes, held) -> dict:
        dec = self._decoder
        if lanes is None:
            return dec.tick_counts(cur, self._slots, windowed=True)
        held = [held[i] for i in np.flatnonzero(lanes)]
        new = [t for t in held if t.saved is None]
        if new:
            made = dec.starts_saved(np.stack([t.row for t in new]))
            for i, t in enumerate(new):
                t.saved = {name: rows[i] for name, rows in made.items()}
        saved = ({name: np.stack([t.saved[name] for t in held])
                  for name in held[0].saved} if held else {})
        if self._rings is not None:
            if self._ring_saved is None:
                self._ring_saved = dec.starts_saved(rings=self._rings)
            saved.update((name, rows[lanes])
                         for name, rows in self._ring_saved.items())
        return dec.tick_counts(cur, self._slots, saved=saved)


class _Tick:
    """One dispatched decode tick: `rows` holds (seq, slot, cursor) as
    each sequence went out (the sequence's own cursor and slot move on
    while the tick is in flight), `nxt` and `counts` are the step's
    results, still on the device; `tokens` is `nxt` on the host once
    the tick has been read."""

    __slots__ = ("rows", "nxt", "counts", "tokens")

    def __init__(self, rows, nxt, counts):
        self.rows = rows
        self.nxt = nxt
        self.counts = counts
        self.tokens = None


@functools.lru_cache(maxsize=None)
def _feed_tokens():
    """The jitted select that keeps sampled tokens on the device: a
    slot whose previous position is still in flight is fed that tick's
    sampled token, every other slot the host's (a prompt token, or one
    already read)."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda prev, host, from_prev:
                   jnp.where(from_prev, prev, host))


class GenerationServer:
    """Continuous-batching decode scheduler over one paged decoder.

    decoder/states: a models/transformer.build_lm_paged_decoder bundle
    plus its trained parameter dict (names must match
    decoder.state_names — same unique-name discipline as the other
    generator builders).  `slots` bounds concurrent sequences,
    `kv_blocks` is the preallocated pool budget shared by ALL of them.

    What the decoder keeps beside the table pool (a ring a slot, a
    recurrent state a lane, a plane a pass), what its ticks count and
    what it cannot be served with are the decoder's to say
    (`PagedDecoder`; docs/serving.md "What a decoder tells the
    server"): the server sizes the pools it is asked for, hands
    `_step_tables()` to the step and refuses what `decoder.refuses`
    names.
    """

    def __init__(self, decoder, states, *, slots: int = 8,
                 kv_blocks: int = 64, max_queue: int = 256,
                 place=None, static_batch: bool = False,
                 idle_poll_s: float = 0.005,
                 prefix_cache: bool = True,
                 draft_decoder=None, draft_states=None,
                 spec_k: Optional[int] = None,
                 state_snapshots: Optional[int] = None):
        import jax

        from ..core.executor import TPUPlace

        def _check_states(dec, sts, who):
            missing = [n for n in dec.state_names if n not in sts]
            if missing:
                raise ValueError(
                    f"{who} states missing {len(missing)} decoder "
                    f"parameter(s), e.g. {missing[:3]} — rebuild the "
                    "decoder under the same unique-name state the "
                    "parameters were trained in")
            # matching NAMES are not enough: a spec that rebuilds the
            # decoder at the wrong max_len/d_model would index the
            # position table out of bounds inside jit, where gathers
            # CLAMP — silently wrong tokens instead of an error.
            bad = [(n, tuple(np.shape(sts[n])), want)
                   for n, want in dec.state_shapes.items()
                   if tuple(np.shape(sts[n])) != want]
            if bad:
                n, got, want = bad[0]
                raise ValueError(
                    f"{len(bad)} {who} parameter shape(s) do not match "
                    f"the decoder architecture, e.g. {n}: states {got} "
                    f"vs decoder {want} — the model spec (vocab_size/"
                    "d_model/n_heads/n_layers/block_size*"
                    "max_blocks_per_seq) disagrees with the saved "
                    "parameters")

        _check_states(decoder, states, "target")
        # what a block cannot be served with is the block's to say
        for what, asked in (("draft_model", draft_decoder is not None),
                            ("prefix_cache", prefix_cache)):
            if asked and what in decoder.refuses:
                raise ValueError(decoder.refuses[what])
        if (draft_decoder is None) != (draft_states is None):
            raise ValueError(
                "speculative decoding needs BOTH draft_decoder and "
                "draft_states (or neither)")
        if draft_decoder is not None:
            _check_states(draft_decoder, draft_states, "draft")
            if (draft_decoder.block_size != decoder.block_size
                    or draft_decoder.max_blocks_per_seq
                    != decoder.max_blocks_per_seq):
                raise ValueError(
                    "draft decoder block geometry "
                    f"({draft_decoder.block_size}x"
                    f"{draft_decoder.max_blocks_per_seq}) must match "
                    f"the target ({decoder.block_size}x"
                    f"{decoder.max_blocks_per_seq}) — both pools are "
                    "indexed by the SAME per-sequence block tables")
            if draft_decoder.vocab_size != decoder.vocab_size:
                raise ValueError("draft/target vocab_size mismatch")
        self._decoder = decoder
        self._draft = draft_decoder
        self._spec_k = int(_SPEC_K if spec_k is None else spec_k)
        if draft_decoder is not None and self._spec_k < 1:
            raise ValueError("spec_k must be >= 1 with a draft model")
        self._slots = int(slots)
        self._static = bool(static_batch)
        self._idle_poll_s = float(idle_poll_s)
        place = place or TPUPlace()
        self._device = place.jax_device()
        for dec in (decoder, draft_decoder):
            # selection read the platform the decoder was BUILT for;
            # placement reads the place — a Mosaic kernel over CPU
            # arrays (or donation on a backend without it) must fail
            # here, by name, not inside the first compile
            if dec is not None and dec.platform != self._device.platform:
                raise ValueError(
                    f"decoder was built for platform {dec.platform!r} "
                    f"but {place!r} runs on {self._device.platform!r}"
                    " — pass platform= to build_lm_paged_decoder (or "
                    "use server_from_model_dir, which threads it)")
        self._states = {n: jax.device_put(np.asarray(states[n]),
                                          self._device)
                        for n in decoder.state_names}
        sid = self._sid = str(next(_SERVER_IDS))
        bpb = decoder.bytes_per_block
        if draft_decoder is not None:
            bpb += draft_decoder.bytes_per_block
        # a decoder whose lanes keep a state is served under the prefix
        # cache through SNAPSHOTS of it (docs/serving.md "A snapshot of
        # a lane's state"): `state_snapshots` rows (None: one a slot) of
        # a pool that the decoder makes and its two programs copy into
        # and out of; here a snapshot is a row number
        self._snap_rows = (
            (self._slots if state_snapshots is None
             else int(state_snapshots))
            if prefix_cache and decoder.init_snapshots is not None
            else None)
        self._cache = PagedKVCache(
            kv_blocks, decoder.block_size, decoder.max_blocks_per_seq,
            server_label=f"gen{sid}", prefix_cache=prefix_cache,
            bytes_per_block=bpb, state_snapshots=self._snap_rows,
            snapshot_bytes=decoder.state_bytes_per_lane)
        # int8 pools cannot share a prompt's FINAL block: the
        # block-aligned full-prompt hit re-runs the last prompt
        # position, and an int8 write RE-QUANTIZES the whole shared
        # block in place — mutating bytes other live sequences attend
        # to.  fp32 and bf16 writes touch only their own (block,
        # offset) slot with byte-identical values (decode is
        # deterministic in the prefix), so they keep full sharing;
        # for int8 the submit-time keys drop the last prompt token,
        # which excludes exactly the aligned final block.
        self._kv_int8 = "int8" in (
            decoder.kv_dtype,
            draft_decoder.kv_dtype if draft_decoder is not None else None)
        # +1: device block 0 is the reserved null/scratch block
        # (and of the sliding layers' pool, which holds a whole ring
        # for every slot beside it: the table pool alone decides how
        # many sequences fit)
        ring = decoder.window_blocks_per_seq
        self._pool_k, self._pool_v = decoder.init_pool(
            kv_blocks + 1, self._device,
            window_blocks=ring * self._slots + 1, lanes=self._slots)
        self._snaps = (decoder.init_snapshots(self._snap_rows, self._device)
                       if self._snap_rows else None)
        if draft_decoder is not None:
            self._draft_states = {
                n: jax.device_put(np.asarray(draft_states[n]),
                                  self._device)
                for n in draft_decoder.state_names}
            self._dpool_k, self._dpool_v = draft_decoder.init_pool(
                kv_blocks + 1, self._device)

        # the tick dispatched and not yet read (None: nothing in
        # flight), and what the token select is given in its place
        self._inflight: Optional[_Tick] = None
        # where each iteration's time goes, and the newest iterations
        # that took over four reference periods (`stats()["slow_ticks"]`:
        # the clock's `slow`, which it rebinds whole and never mutates,
        # so `stats` reads it unlocked)
        self._clock = obs_attr.IterationClock(
            ("deliver", "admit", "build", "dispatch"))
        # seconds the newest `admit` spent taking the server's lock
        self._lock_wait = 0.0
        self._no_tokens = jax.device_put(
            np.zeros(self._slots, np.int32), self._device)
        self._active: List[Optional[_Seq]] = [None] * self._slots
        self._tables = np.zeros(
            (self._slots, decoder.max_blocks_per_seq), np.int32)
        # each slot's ring of the sliding layers' pool, fixed for the
        # server's life (None where every layer is full): a sequence
        # admitted to a used slot writes over its predecessor's keys,
        # and the step's mask shows a sequence only positions it has
        # written itself
        rings = decoder.slot_rings(self._slots) if ring else None
        self._rings = (jax.device_put(rings, self._device) if ring
                       else None)
        # what a traced tick's span gets in place of the decoder's
        # counts (`_TickAccount`): made by the span's reader
        self._account = _TickAccount(decoder, self._slots, rings)
        # the last admission left the queue's head waiting for BLOCKS
        # with a slot free (on the next tick's span as `kv_wait`)
        self._kv_wait = False
        self._queue: deque = deque()
        self._max_queue = int(max_queue)
        self._lock = threading.Condition()
        self._stop = False
        self._draining = False
        self._pending_states = None
        self._swap_done = threading.Event()

        self._m_requests = _M_REQUESTS.labels(server=sid)
        self._m_tokens = _M_TOKENS.labels(server=sid)
        self._m_ticks = _M_TICKS.labels(server=sid)
        self._m_shed = _M_SHED.labels(server=sid, reason="saturated")
        self._m_deadline = _M_SHED.labels(server=sid, reason="deadline")
        self._m_swaps = _M_SWAPS.labels(server=sid)
        self._m_latency = _M_LATENCY.labels(server=sid)
        self._m_ttft = _M_TTFT.labels(server=sid)
        self._m_active = _M_ACTIVE.labels(server=sid)
        self._m_qdepth = _M_QDEPTH.labels(server=sid)
        self._m_proposed = _M_DRAFT_PROPOSED.labels(server=sid)
        self._m_accepted = _M_DRAFT_ACCEPTED.labels(server=sid)

        from ..core.compile_cache import compile_cache_dir
        from ..core.executor import xla_compile_counts

        compile_cache_dir()
        c0 = xla_compile_counts()
        t0 = time.perf_counter()
        self._warmup()
        c1 = xla_compile_counts()
        # warm-start accounting (process-wide counters, diffed around
        # THIS warmup): cache_misses == 0 with hits > 0 means every
        # serving executable worth persisting deserialized from the
        # host's compile cache (core/compile_cache.py) — the cold-start
        # contract the autoscaler's scale-out relies on
        self.warmup_stats = {
            "warmup_s": round(time.perf_counter() - t0, 4),
            "compiles": int(c1["compiles"] - c0["compiles"]),
            "compile_seconds": round(
                c1["compile_seconds"] - c0["compile_seconds"], 4),
            "cache_hits": int(c1["cache_hits"] - c0["cache_hits"]),
            "cache_misses": int(c1["cache_misses"]
                                - c0["cache_misses"]),
        }
        self._compiles_after_warmup_base = int(c1["compiles"])
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()

    def _warmup(self):
        """Compile the resident step(s) before the first request:
        serving never pays the trace+compile inside a request's
        latency.  A speculative server compiles the target's window
        step plus the draft's window and single steps; a plain server
        compiles only the one-token step it runs."""
        z = np.zeros(self._slots, np.int32)
        zs = z.astype(np.uint32)
        zt = np.zeros(self._slots, np.float32)
        if self._draft is None:
            # fed as the scheduler feeds it: the tokens come out of the
            # select, on the device (a host array there would be a
            # second signature, compiled inside the first request)
            none = np.zeros(self._slots, bool)
            args = (self._states, self._pool_k, self._pool_v,
                    self._step_tables(), z,
                    _feed_tokens()(self._no_tokens, z, none), zs, zt,
                    none)
            # device time by scope: hlo_scopes() can read the resident
            # step's compiled text later (shapes, no buffers)
            profiler.register_jitted(
                "paged_decoder.step", self._decoder.step, *args,
                compiler_scopes=self._decoder.compiler_scopes)
            nxt, self._pool_k, self._pool_v, *_ = self._decoder.step(
                *args)
            np.asarray(nxt)  # block: compile is done when this returns
            if self._snaps is not None:
                # the two copies, as the scheduler calls them (zeros
                # into row 0, row 0 into lane 0: nothing moves)
                self._save_snapshot(0, 0, register=True)
                self._restore_snapshot(0, 0, register=True)
                import jax

                jax.block_until_ready(self._pool_k)
            return
        w = self._spec_k + 1
        zw = np.zeros((self._slots, w), np.int32)
        nxt, self._pool_k, self._pool_v, *_ = self._decoder.step_window(
            self._states, self._pool_k, self._pool_v, self._tables,
            z, zw, zs, zt, z)
        np.asarray(nxt)
        nxt, self._dpool_k, self._dpool_v = self._draft.step_window(
            self._draft_states, self._dpool_k, self._dpool_v,
            self._tables, z, zw, zs, zt, z)
        np.asarray(nxt)
        nxt, self._dpool_k, self._dpool_v = self._draft.step(
            self._draft_states, self._dpool_k, self._dpool_v,
            self._tables, z, z, zs, zt,
            np.zeros(self._slots, bool))
        np.asarray(nxt)

    def _save_snapshot(self, lane: int, row: int, register=False) -> None:
        """Dispatch the decoder's copy of lane `lane`'s state into
        snapshot `row`, behind every step dispatched so far."""
        args = (self._snaps, self._pool_k, self._pool_v, np.int32(lane),
                np.int32(row))
        if register:
            profiler.register_jitted("paged_decoder.snapshot_save",
                                     self._decoder.snapshot_save, *args)
        self._snaps = self._decoder.snapshot_save(*args)

    def _restore_snapshot(self, lane: int, row: int,
                          register=False) -> None:
        """Dispatch the decoder's copy of snapshot `row` into lane
        `lane`, ahead of every step dispatched from now on."""
        args = (self._pool_k, self._pool_v, self._snaps, np.int32(lane),
                np.int32(row))
        if register:
            profiler.register_jitted("paged_decoder.snapshot_restore",
                                     self._decoder.snapshot_restore, *args)
        self._pool_k, self._pool_v = self._decoder.snapshot_restore(*args)

    # -- client side --------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int, *,
               temperature: float = 0.0, seed: int = 0,
               eos_id: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> GenerationStream:
        """Enqueue one generation request; returns its token stream.

        Requests whose prompt+max_new budget can never fit a sequence's
        block-table capacity are rejected with ValueError up front; a
        full admission queue raises ServerSaturated (backpressure); a
        request still queued when `deadline_ms` passes is shed with
        RequestDeadlineExceeded instead of occupying a slot."""
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        stream = GenerationStream(prompt, max_new_tokens)
        expires = (time.monotonic() + deadline_ms / 1000.0
                   if deadline_ms is not None else None)
        seq = _Seq(stream, max_new_tokens, eos_id, temperature, seed,
                   expires, obs_tracing.current_context())
        need = self._cache.blocks_for(seq.positions_needed)
        if (need > self._cache.max_blocks_per_seq
                or seq.positions_needed > self._decoder.max_len):
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new_tokens} "
                f"needs {need} KV blocks > per-sequence capacity "
                f"{self._cache.max_blocks_per_seq} "
                f"(block_size {self._cache.block_size})")
        if self._cache.prefix_cache:
            keyed = (prompt[:-1] if self._kv_int8 else prompt)
            seq.prompt_keys = self._cache.prompt_keys(keyed)
        with self._lock:
            if self._stop:
                raise RuntimeError("GenerationServer is closed")
            if self._draining:
                # retryable by contract: the replica front maps
                # RuntimeError to a non-fatal wire error, so a router
                # resubmits on a survivor — a draining replica sheds
                # ADMISSION, never an accepted request
                raise RuntimeError(
                    "GenerationServer is draining (graceful scale-in/"
                    "shutdown): submit on another replica")
            if len(self._queue) >= self._max_queue:
                self._m_shed.inc()
                raise ServerSaturated(
                    f"GenerationServer queue full ({self._max_queue} "
                    "pending) — backpressure: retry later or raise "
                    "max_queue")
            self._queue.append(seq)
            self._lock.notify_all()
        if obs_metrics.enabled():
            self._m_qdepth.set(len(self._queue))
        return stream

    def generate(self, prompt_ids, max_new_tokens: int,
                 timeout: Optional[float] = None, **kw) -> List[int]:
        """Synchronous convenience wrapper around submit()."""
        return self.submit(prompt_ids, max_new_tokens, **kw).result(
            timeout)

    def swap_states(self, states: Dict[str, np.ndarray],
                    draft_states: Optional[Dict] = None,
                    wait: bool = True,
                    timeout: Optional[float] = None) -> bool:
        """Zero-downtime checkpoint hot swap: drain -> swap -> resume.

        Admission pauses, active sequences run to completion against
        the OLD parameters (a generation never mixes checkpoints),
        then the new parameters are installed and admission resumes.
        Queued requests are NOT failed — they wait out the drain.

        With a draft armed, pass the new checkpoint's `draft_states`
        too: a stale draft stays CORRECT (the target verifies every
        window) but its accept rate against the new target can
        collapse toward 1/vocab — a silent throughput regression.
        Omitting them keeps the old draft."""
        missing = [n for n in self._decoder.state_names
                   if n not in states]
        if missing:
            raise ValueError(f"swap states missing {missing[:3]}...")
        new_draft = None
        if draft_states is not None:
            if self._draft is None:
                raise ValueError(
                    "draft_states given but this server has no draft "
                    "armed (a draft cannot be armed mid-flight)")
            dmissing = [n for n in self._draft.state_names
                        if n not in draft_states]
            if dmissing:
                raise ValueError(
                    f"swap draft states missing {dmissing[:3]}...")
            new_draft = {n: np.asarray(draft_states[n])
                         for n in self._draft.state_names}
        with self._lock:
            if self._stop:
                raise RuntimeError("GenerationServer is closed")
            if self._pending_states is not None:
                raise RuntimeError("hot swap already in progress")
            self._swap_done.clear()
            self._pending_states = (
                {n: np.asarray(states[n])
                 for n in self._decoder.state_names}, new_draft)
            self._lock.notify_all()
        if wait:
            return self._swap_done.wait(timeout)
        return True

    # -- graceful drain (scale-in / SIGTERM) --------------------------------
    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def drain(self, wait: bool = True,
              timeout: Optional[float] = None) -> bool:
        """Stop ADMITTING new requests and (with `wait`) block until
        everything already accepted — active slots AND the queue — has
        run to completion.  This is the graceful-scale-in half of the
        PR 8 hot-swap machinery: a drained replica has delivered every
        stream it ever accepted, so retiring it afterwards fails
        nothing.  New submits raise RuntimeError (mapped to a
        RETRYABLE wire error by serving/replica.py, so a router
        resubmits on a survivor).  Returns True when fully drained
        within `timeout`; `resume()` re-opens admission for an aborted
        scale-in."""
        with self._lock:
            if self._stop:
                raise RuntimeError("GenerationServer is closed")
            self._draining = True
            self._lock.notify_all()
        if not wait:
            return True
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._lock:
            while not self._stop and (
                    self._queue
                    or any(s is not None for s in self._active)):
                left = (None if deadline is None
                        else deadline - time.monotonic())
                if left is not None and left <= 0:
                    return False
                # the scheduler notifies on evictions; the short cap
                # also covers the error-eviction path, which doesn't
                self._lock.wait(timeout=min(0.05, left)
                                if left is not None else 0.05)
            return not self._stop

    def resume(self) -> None:
        """Re-open admission after drain() (an aborted scale-in: the
        at-least-one-replica invariant found no survivor to retire
        onto)."""
        with self._lock:
            self._draining = False
            self._lock.notify_all()

    @property
    def warm_start(self) -> bool:
        """Whether warmup deserialized its executables from the compile
        cache and wrote none — observed, not configured."""
        ws = self.warmup_stats
        return ws["cache_hits"] > 0 and ws["cache_misses"] == 0

    def stats(self) -> Dict[str, float]:
        """Serving telemetry view (docs/serving.md): request/token/tick
        counters, shed accounting, live occupancy, KV-pool state,
        prefix-cache hit accounting and speculative accept rates."""
        from ..core.executor import xla_compile_counts

        with self._lock:
            active = sum(1 for s in self._active if s is not None)
            qdepth = len(self._queue)
            draining = self._draining
        # process-wide compile counter diffed against this server's
        # post-warmup base: 0 == no XLA compile has happened since
        # warmup (the serving-side analogue of Executor.cache_stats()'s
        # recompiles_after_warmup; in a one-server process — a `cli
        # serve` replica — any nonzero value is a compile paid inside
        # request latency)
        recompiles = int(xla_compile_counts()["compiles"]
                         - self._compiles_after_warmup_base)
        out = {"requests": int(self._m_requests.value),
               "generated_tokens": int(self._m_tokens.value),
               "ticks": int(self._m_ticks.value),
               "shed": int(self._m_shed.value),
               "deadline_expired": int(self._m_deadline.value),
               "hot_swaps": int(self._m_swaps.value),
               "active_sequences": active,
               "queue_depth": qdepth,
               "kv_blocks_free": self._cache.free_blocks,
               "kv_blocks_total": self._cache.num_blocks,
               "kv_pool_utilization": self._cache.utilization(),
               # the sliding layers' rings, one a slot (0 without)
               "kv_window_blocks": 0 if self._rings is None
               else self._rings.size,
               # the Mamba layers' recurrent state over all lanes (0
               # without): float32, resident whatever the lanes hold
               "state_bytes": (self._slots
                               * self._decoder.state_bytes_per_lane),
               # the snapshot pool beside it (0 without): resident too
               "state_snapshot_pool_bytes": (
                   (self._snap_rows or 0)
                   * self._decoder.state_bytes_per_lane),
               "kv_dtype": self._decoder.kv_dtype,
               "decode_kernel":
               self._decoder.kernels["paged_attention_decode"],
               # the expert layer of the step traced last: the Pallas
               # grouped matmul's name or "xla:<reason>"; None for a
               # block without experts
               "expert_kernel": self._decoder.expert_kernel,
               # and of its router's choice: the Pallas call's name or
               # "passes:<reason>"
               "router_choice": self._decoder.router_choice,
               # the same of a delta-rule layer's recurrence
               "delta_kernel": self._decoder.delta_kernel,
               "kv_bytes_resident": (self._cache.used_blocks
                                     * self._cache.bytes_per_block),
               "draft_proposed": int(self._m_proposed.value),
               "draft_accepted": int(self._m_accepted.value),
               "spec_k": self._spec_k if self._draft is not None else 0,
               "draining": draining,
               "recompiles_after_warmup": recompiles,
               "warm_start": self.warm_start,
               # the newest iterations of the scheduler that took over
               # four reference periods, oldest first: `wait_ms` near
               # `ms` puts one in the device or the runtime, anything
               # else names host code by `phase` (docs/serving.md)
               "slow_ticks": [dict(r) for r in self._clock.slow]}
        out.update(self.warmup_stats)
        out.update(self._cache.prefix_stats())
        return out

    def outstanding_tokens(self) -> int:
        """Token budget not yet delivered (active + queued) — the load
        signal the replica router places on (least outstanding)."""
        with self._lock:
            out = sum(s.max_new - s.emitted
                      for s in self._active if s is not None)
            out += sum(s.max_new for s in self._queue)
        return out

    def close(self):
        with self._lock:
            self._stop = True
            self._lock.notify_all()
        self._worker.join(timeout=10)
        err = RuntimeError("GenerationServer closed")
        with self._lock:
            leftovers = ([s for s in self._active if s is not None]
                         + list(self._queue))
            self._active = [None] * self._slots
            self._queue.clear()
        now = time.perf_counter()
        for seq in leftovers:
            self._cache.release(seq)
            self._request_span(seq, now, error="ServerClosed")
            seq.stream._fail(err)
        self._cache.close()
        for fam in (_M_REQUESTS, _M_TOKENS, _M_TICKS, _M_SWAPS,
                    _M_LATENCY, _M_TTFT, _M_ACTIVE, _M_QDEPTH,
                    _M_DRAFT_PROPOSED, _M_DRAFT_ACCEPTED):
            fam.remove(server=self._sid)
        for reason in ("saturated", "deadline"):
            _M_SHED.remove(server=self._sid, reason=reason)

    # -- scheduler ----------------------------------------------------------
    def _shed_expired_locked(self, now: float) -> List[_Seq]:
        shed = []
        kept: deque = deque()
        for seq in self._queue:
            if seq.expires is not None and now >= seq.expires:
                shed.append(seq)
            else:
                kept.append(seq)
        self._queue = kept
        return shed

    def _admit_locked(self) -> List[_Seq]:
        """Move queued requests into free slots, FIFO, while KV blocks
        and slots last.  Head-of-line order is deliberate: skipping a
        big request to admit later small ones would starve it."""
        admitted = []
        self._kv_wait = False
        n_active = sum(1 for s in self._active if s is not None)
        if self._static and n_active:
            return admitted   # drain-then-refill baseline
        if self._pending_states is not None:
            return admitted   # draining for a hot swap
        while self._queue:
            slot = next((i for i, s in enumerate(self._active)
                         if s is None), -1)
            if slot < 0:
                break
            seq = self._queue[0]
            # with snapshots a hit must leave the last prompt position
            # to be run: a recurrence cannot run it twice
            upto = (seq.prompt_len - 1 if self._snap_rows is not None
                    else None)
            if not self._cache.can_admit(seq.positions_needed,
                                         prompt_keys=seq.prompt_keys,
                                         cached_upto=upto):
                # a slot is free and the pool's blocks refuse the head
                self._kv_wait = True
                break
            self._queue.popleft()
            try:
                with obs_attr.phase("generation", "kv_alloc"):
                    table, cached = self._cache.allocate_prefix(
                        seq, seq.positions_needed,
                        prompt_keys=seq.prompt_keys, cached_upto=upto)
            except KVPoolExhausted:
                # can_admit/allocate_prefix disagreeing is a bug, but
                # an unserved admission must back off (head of queue,
                # retried next tick) — never kill the scheduler thread
                self._queue.appendleft(seq)
                break
            # prefix hit: the first `cached` positions already hold
            # this prompt's K/V — start the cursor there and skip
            # their prefill ticks.  A block-ALIGNED full-prompt hit
            # still re-runs the last prompt position (the step must
            # produce the first new token); that write lands in a
            # shared block with byte-identical values — the zero-copy
            # degenerate of copy-on-write.
            seq.cur = min(cached, seq.prompt_len - 1)
            seq.draft_next = seq.cur
            seq.cached = seq.cur
            if upto is not None:
                # the lane starts from the hit's snapshot (dispatched by
                # `_loop`, outside the lock), and saves its own when the
                # cursor passes the prompt's last full block boundary,
                # if that lies past the hit
                bs = self._cache.block_size
                seq.snap_row, seq.cut = self._cache.hit_snapshot(seq)
                boundary = len(seq.prompt_keys) * bs
                seq.snap_at = boundary if boundary > cached else -1
            seq.t_admit = time.perf_counter()
            seq.slot = slot
            self._active[slot] = seq
            self._tables[slot] = table
            seq.table = _Table(table)
            admitted.append(seq)
        return admitted

    def _restore_admitted(self, admitted: List[_Seq]) -> None:
        """Dispatch the restore of every admission that hit a snapshot,
        before the lane's first tick is dispatched: the device runs its
        stream in order, so the tick in flight (in which the lane's last
        occupant may still run a position that is dropped) runs before
        it and the lane's first tick after."""
        for seq in admitted:
            if seq.snap_row is not None:
                with obs_attr.phase("generation", "snapshot_restore"):
                    self._restore_snapshot(seq.slot, seq.snap_row)

    def _evict_locked(self, seq: _Seq):
        self._active[seq.slot] = None
        self._tables[seq.slot] = 0
        seq.slot = -1
        with obs_attr.phase("generation", "kv_release"):
            self._cache.release(seq)

    def _loop(self):
        """The scheduler thread.  Without a draft model it runs one
        tick ahead of the device: an iteration admits, dispatches tick
        n+1 (`_tick`, which then blocks on tick n's tokens) and
        delivers tick n, so everything but the read runs under the
        device's step.  The tick in flight is read with nothing
        dispatched behind it (`_flush`) when no sequence has a position
        left to run, before a hot swap and at close.  A speculative
        server's accept rule is host code over the window's tokens, so
        its tick stays serial (`_tick_spec`, nothing ever in flight).

        Every piece of an iteration's host work lies under one phase
        span, in the order `deliver`, `admit`, `build`, `decode` or
        `prefill`, `sample` (the one place the host waits), and
        `self._clock` takes a clock reading as each ends, with tracing
        on or off (docs/serving.md "The scheduler loop")."""
        clock = self._clock
        while True:
            if clock.start is None:
                clock.begin()
            with obs_attr.phase("generation", "admit") as asp:
                t_lock = time.perf_counter()
                with self._lock:
                    self._lock_wait = time.perf_counter() - t_lock
                    if self._stop:
                        break
                    shed = self._shed_expired_locked(time.monotonic())
                    admitted = self._admit_locked()
                    seqs = [s for s in self._active if s is not None]
                    swap = (self._pending_states
                            if self._pending_states is not None
                            and not seqs else None)
                    qdepth = len(self._queue)
                if asp is not None:
                    asp.set_attr("lock_wait_s", self._lock_wait)
                metrics_on = obs_metrics.enabled()
                for seq in shed:
                    self._m_deadline.inc()
                    self._request_span(seq, time.perf_counter(),
                                       error="RequestDeadlineExceeded")
                    seq.stream._fail(RequestDeadlineExceeded(
                        "request deadline expired while queued for "
                        "admission"))
                if admitted:
                    self._m_requests.inc(len(admitted))
                    if self._snaps is not None:
                        self._restore_admitted(admitted)
                if metrics_on:
                    self._m_qdepth.set(qdepth)
                    self._m_active.set(len(seqs))
                if self._draft is None:
                    # a sequence whose last position is in flight has
                    # nothing left to run: it holds its slot until that
                    # tick is delivered
                    seqs = [s for s in seqs
                            if s.cur < s.positions_needed]
            clock.mark("admit")
            if swap is not None:
                # no sequence holds a slot, but the extra position of
                # one that ended by eos may still be out
                self._flush()
                self._install_states(swap)
                continue
            if not seqs:
                if self._inflight is not None:
                    self._flush()
                    continue
                clock.start = None
                with self._lock:
                    if (not self._queue and not self._stop
                            and self._pending_states is None):
                        self._lock.wait(timeout=self._idle_poll_s)
                continue
            try:
                # chaos hook fires inside _tick/_tick_spec, within the
                # attributed phase block: an error rule fails the
                # sequences holding a slot (they are evicted, their
                # streams get the error) but must never kill the
                # scheduler thread
                if self._draft is None:
                    done = self._tick(seqs)
                else:
                    plans, preds = self._tick_spec(seqs)
            except Exception as e:
                self._fail_active(e)
                continue
            if self._draft is not None:
                self._deliver_spec(plans, preds, metrics_on)
            elif done is not None:
                self._deliver(done, metrics_on)
            clock.mark("deliver")
        self._flush()

    def _tick(self, seqs: List[_Seq]) -> Optional[_Tick]:
        """Dispatch one tick over `seqs`, each at the cursor it is to
        run, and advance their cursors; THEN block on the tokens of the
        tick dispatched before it.  Returns that earlier tick, read and
        ready for `_deliver` (None when nothing was in flight); the new
        one stays in `self._inflight`.  The step's arrays are made
        under the phase `build`; one `serving.decode_tick` span then
        covers both halves, the dispatch (`decode` or `prefill`) and
        the read (`sample`)."""
        prev = self._inflight
        clock = self._clock
        with obs_attr.phase("generation", "build") as bsp:
            # a span is live: the scheduler's own counts come out of
            # this one walk, and what the decoder's are made FROM (the
            # lanes' cursors, mask and tables, all made or held anyway)
            # goes to the tick span as a deferred account; else nothing
            # is computed or kept (a tick span that goes live between
            # this block and the next, once an arming, carries `active`
            # and `ahead` alone)
            live = bsp is not None
            held = [None] * self._slots if live else None
            tokens = np.zeros(self._slots, np.int32)
            positions = np.zeros(self._slots, np.int32)
            temps = np.zeros(self._slots, np.float32)
            seeds = np.zeros(self._slots, np.uint32)
            active = np.zeros(self._slots, bool)
            # slots whose token is the one `prev` sampled, still unread
            from_prev = np.zeros(self._slots, bool)
            rows = []
            prefill = 0
            for seq in seqs:
                slot, cur = seq.slot, seq.cur
                rows.append((seq, slot, cur))
                if cur < len(seq.tokens):
                    tokens[slot] = seq.tokens[cur]
                else:
                    from_prev[slot] = True
                positions[slot] = cur
                temps[slot] = seq.temperature
                seeds[slot] = seq.seed
                active[slot] = True
                if live:
                    held[slot] = seq.table
                    if cur < seq.prompt_len - 1:
                        prefill += 1
            # attribution: dispatch is "prefill" while EVERY ticking
            # sequence is still teacher-forcing its prompt, else
            # "decode" (mixed ticks are decode work for at least one
            # stream); the host-side sync that materializes the sampled
            # tokens is "sample": that is where the host waits for the
            # device
            if live:
                prefilling = prefill == len(rows)
            else:
                prefilling = all(c < s.prompt_len - 1 for s, _, c in rows)
            phase_name = "prefill" if prefilling else "decode"
            tables = self._step_tables()
            attrs = self._tick_attrs(prefill) if live else {}
            account = (self._account.of(positions[active], active, held)
                       if live else None)
        clock.mark("build")
        with obs_tracing.span("serving.decode_tick", active=len(rows),
                              **attrs) as sp:
            if sp is not None:
                sp.set_attr("ahead", int(prev is not None))
                if account is not None:
                    sp.defer(account)
            with obs_attr.phase("generation", phase_name):
                fault_injector().fire("serving.decode")
                fed = _feed_tokens()(
                    self._no_tokens if prev is None else prev.nxt,
                    tokens, from_prev)
                nxt, self._pool_k, self._pool_v, *counts = (
                    self._decoder.step(
                        self._states, self._pool_k, self._pool_v,
                        tables, positions, fed, seeds, temps, active))
                self._inflight = _Tick(rows, nxt, counts)
                for seq in seqs:
                    seq.cur += 1
                self._m_ticks.inc()
                if self._snaps is not None:
                    self._save_passed(seqs)
            clock.mark("dispatch")
            if prev is not None:
                with obs_attr.phase("generation", "sample"):
                    prev.tokens = np.asarray(prev.nxt)
                    self._step_counts(sp, prev.counts)
            self._end_iteration(sp, len(rows))
        return prev

    def _save_passed(self, seqs: List[_Seq]) -> None:
        """Dispatch the save of every sequence whose cursor has just
        passed the last full block boundary of its prompt: behind the
        tick that ran the boundary's last position, ahead of the next.
        One snapshot a prompt; the cache hangs it on the block when the
        tick has been read (`commit_prefix`)."""
        for seq in seqs:
            if seq.cur == seq.snap_at:
                row = self._cache.reserve_snapshot(seq, seq.snap_at)
                if row is not None:
                    with obs_attr.phase("generation", "snapshot_save"):
                        self._save_snapshot(seq.slot, row)
                    seq.snap_saved = 1

    def _end_iteration(self, sp, active: int) -> None:
        """Close the iteration at the end of its read.  One that took
        over four reference periods is kept among the newest 8 of
        `stats()["slow_ticks"]`, goes to the flight recorder where one
        is armed, and marks the live tick span `slow=1` with the host
        time in which this thread did not run."""
        rec = self._clock.end(active=active,
                              lock_wait_ms=1e3 * self._lock_wait)
        if rec is None:
            return
        flightrecorder.note("serving.slow_tick", server=self._sid,
                            **rec)
        if sp is not None:
            sp.set_attr("slow", 1)
            sp.set_attr("offcpu_ms", rec["offcpu_ms"])

    def _step_tables(self):
        """The tables `step` takes: each slot's block table, with the
        slots' rings beside it where the decoder has sliding layers.
        The table is copied: a host array handed to a dispatch may be
        read after the call returns, and eviction and admission
        rewrite `_tables` meanwhile (the rings never change)."""
        if self._rings is None:
            return self._tables.copy()
        return self._tables.copy(), self._rings

    def _tick_attrs(self, prefill: int) -> dict:
        """The scheduler's own counts of the tick being dispatched, for
        its `serving.decode_tick` span: `prefill` of its slots
        teacher-forcing a prompt position (cursor below prompt_len - 1:
        they deliver nothing), `kv_used` of `kv_total` pool blocks
        owned, and `kv_wait`: 1 where the admission before this tick
        left the queue's head waiting with a slot free because
        `can_admit` refused it for blocks.  Only called while a span is
        live.  What the step reads and does at the lanes' cursors is the
        decoder's to count, and the span's reader's to ask for
        (`_TickAccount`)."""
        return {"prefill": prefill,
                "kv_used": self._cache.used_blocks,
                "kv_total": self._cache.num_blocks,
                "kv_wait": int(self._kv_wait)}

    def _step_counts(self, sp, counts) -> None:
        """What a step counted on the device, summed onto the live
        tick span under the decoder's own names (`step_counters`:
        `moe_experts_hit`, distinct experts routed to over all layers,
        for a block with experts, and `moe_rows_held`, the live lanes'
        assignments that fell on experts held here, where the block
        holds a share of them; nothing for one without).  Read
        after the tokens, in the phase that has already blocked: on
        the pipelined path they are the counts of the tick READ, one
        before the tick the span dispatched."""
        if sp is not None and counts:
            for name, value in zip(self._decoder.step_counters, counts):
                sp.set_attr(name, int(np.asarray(value).sum()))

    def _flush(self) -> None:
        """Read and deliver the tick in flight, if any, with nothing
        dispatched behind it (no span: a span is a dispatch; and no
        iteration of the clock's: the next tick begins one)."""
        self._clock.start = None
        tick, self._inflight = self._inflight, None
        if tick is None:
            return
        try:
            with obs_attr.phase("generation", "sample"):
                tick.tokens = np.asarray(tick.nxt)
        except Exception as e:
            self._fail_active(e)
            return
        self._deliver(tick, obs_metrics.enabled())

    def _fail_active(self, exc: BaseException) -> None:
        """A tick failed, at its dispatch or (a device error under
        asynchronous dispatch) at its read: every sequence holding a
        slot is in a tick that is lost with it, so all are evicted and
        fail with the error, and what is in flight is dropped unread."""
        self._inflight = None
        self._clock.start = None
        with self._lock:
            seqs = [s for s in self._active if s is not None]
            for seq in seqs:
                self._evict_locked(seq)
        now = time.perf_counter()
        for seq in seqs:
            self._request_span(seq, now, error=type(exc).__name__)
            seq.stream._fail(exc)

    def _deliver(self, tick: _Tick, metrics_on: bool):
        """Hand the tokens of a tick that has been read to their
        streams, evict and close out the sequences that ended, and
        make the prompt blocks the tick filled shareable: all of it
        under the phase `deliver`."""
        now = time.perf_counter()
        delivered = 0
        finished = []
        with obs_attr.phase("generation", "deliver"):
            for seq, slot, cur in tick.rows:
                if seq.slot < 0:
                    # ended by eos in the tick before this one, which
                    # was read only after this one had gone out with
                    # it: the extra position is computed and dropped
                    continue
                if cur + 1 < seq.prompt_len:
                    continue      # still prefilling: teacher-forced
                tok = int(tick.tokens[slot])
                seq.tokens.append(tok)
                seq.emitted += 1
                delivered += 1
                if seq.emitted == 1:
                    seq.t_first = now
                    if metrics_on:
                        with obs_tracing.activate(seq.trace_ctx):
                            self._m_ttft.observe(now - seq.t_submit)
                seq.stream._put(tok)
                if (seq.emitted >= seq.max_new
                        or (seq.eos_id is not None
                            and tok == seq.eos_id)):
                    finished.append(seq)
            if delivered:
                self._m_tokens.inc(delivered)
            if self._snaps is not None:
                # a sequence that ends with this tick would lose, with
                # its release, the snapshot saved behind its last
                # position: its blocks are committed before it goes
                for seq, _, cur in tick.rows:
                    if seq in finished:
                        self._cache.commit_prefix(seq, cur + 1)
            self._finish_seqs(finished, now, metrics_on)
            # freshly-filled full prompt blocks become shareable once
            # the tick that passed their end has been READ, so a block
            # of a tick that fails is never shared (no-op once a
            # sequence has nothing pending or was evicted)
            for seq, _, cur in tick.rows:
                self._cache.commit_prefix(seq, cur + 1)

    def _finish_seqs(self, finished: List[_Seq], now: float,
                     metrics_on: bool):
        """Evict the sequences a delivery ended, under the lock, and
        close each out."""
        if not finished:
            return
        with self._lock:
            for seq in finished:
                self._evict_locked(seq)
            self._lock.notify_all()
        for seq in finished:
            self._finish_seq(seq, now, metrics_on)

    def _finish_seq(self, seq: _Seq, now: float, metrics_on: bool):
        """Close out a finished sequence: record the end-to-end
        ``serving.request`` span (child of the submitter's context, so
        router/replica hops join into one trace) and observe latency
        with that trace active — the histogram exemplar then points at
        this request's trace."""
        ctx = self._request_span(seq, now) or seq.trace_ctx
        if metrics_on:
            with obs_tracing.activate(ctx):
                self._m_latency.observe(now - seq.t_submit)
        seq.stream._finish()

    def _request_span(self, seq: _Seq, now: float, **attrs):
        """The one `serving.request` span of a request, recorded when
        it ends (finished, shed or failed: the latter carry `error`).
        Its duration splits into `queue_s` (submit to admission),
        `prefill_s` (admission to the first token) and `decode_s`
        (first to last token); a request that never got that far ends
        the split where it stopped.  No child spans: a request's
        lifetime lies on no thread."""
        if not (obs_tracing.enabled() or obs_tracing._listeners):
            return None
        t_admit = seq.t_admit if seq.t_admit is not None else now
        t_first = seq.t_first if seq.t_first is not None else now
        if self._snap_rows is not None:
            saved, restored = seq.snap_saved, int(seq.snap_row is not None)
            attrs.update(
                state_snapshots_saved=saved,
                state_snapshots_restored=restored,
                state_snapshot_bytes=(
                    (saved + restored) * self._decoder.state_bytes_per_lane),
                prefix_blocks_cut=seq.cut)
        return obs_tracing.record_span(
            "serving.request", seq.t_submit_wall, now - seq.t_submit,
            parent=seq.trace_ctx, server=self._sid,
            tokens=seq.emitted, prompt_tokens=seq.prompt_len,
            cached_tokens=seq.cached, prefix_hit_tokens=seq.cached,
            queue_s=t_admit - seq.t_submit,
            prefill_s=t_first - t_admit, decode_s=now - t_first,
            **attrs)

    # -- speculative path ---------------------------------------------------
    def _tick_spec(self, seqs: List[_Seq]):
        """One speculative tick: draft catch-up + k greedy proposals
        per eligible slot, then ONE target step_window verifying the
        whole window.  Returns (plans, preds) for _deliver_spec.

        A plan is (seq, c, m, teacher, n_prop, proposals): `c` the
        cursor at tick start, `m` how many committed tokens sit at the
        window's head (teacher-forced), `n_prop` how many draft tokens
        follow them.  Sampled requests and prefill interiors get
        n_prop=0 — pure (chunked) teacher forcing."""
        w = self._spec_k + 1
        clock = self._clock
        plans = []
        with obs_attr.phase("generation", "build"):
            for seq in seqs:
                c = seq.cur
                m = len(seq.tokens) - c
                n_max = min(w, seq.positions_needed - c)
                teacher = min(m, n_max)
                greedy = seq.temperature == 0.0
                n_prop = (n_max - teacher
                          if greedy and teacher == m else 0)
                plans.append((seq, c, m, teacher, n_prop))
        clock.mark("build")

        # draft catch-up: teacher-force the draft over the window's
        # committed head so its KV tracks the target's (positions a
        # proposal step will re-write are excluded).  Normally one
        # chunk; the loop guards the at-most-one-position lag a fully
        # accepted window leaves behind.  Sampled sequences never
        # propose but STILL keep the draft warm: the prompt blocks
        # they commit to the prefix cache must hold valid draft KV for
        # the greedy sequences that later share them.
        with obs_attr.phase("generation", "draft_verify"):
            while True:
                todo = []
                for seq, c, m, teacher, n_prop in plans:
                    end = c + teacher - (1 if n_prop else 0)
                    if seq.draft_next < end:
                        todo.append(
                            (seq, min(end - seq.draft_next, w)))
                if not todo:
                    break
                pos = np.zeros(self._slots, np.int32)
                toks = np.zeros((self._slots, w), np.int32)
                nv = np.zeros(self._slots, np.int32)
                for seq, n in todo:
                    pos[seq.slot] = seq.draft_next
                    toks[seq.slot, :n] = seq.tokens[
                        seq.draft_next:seq.draft_next + n]
                    nv[seq.slot] = n
                _, self._dpool_k, self._dpool_v = \
                    self._draft.step_window(
                        self._draft_states, self._dpool_k,
                        self._dpool_v, self._tables, pos, toks,
                        np.zeros(self._slots, np.uint32),
                        np.zeros(self._slots, np.float32), nv)
                for seq, n in todo:
                    seq.draft_next += n

            # proposal micro-steps: the draft extends each eligible
            # slot greedily, one position per call, batched across
            # slots; step i feeds the committed frontier token first,
            # then its own previous proposal
            max_prop = max((p[4] for p in plans), default=0)
            proposals: Dict[object, List[int]] = {
                p[0]: [] for p in plans}
            for i in range(max_prop):
                pos = np.zeros(self._slots, np.int32)
                toks = np.zeros(self._slots, np.int32)
                act = np.zeros(self._slots, bool)
                stepping = []
                for seq, c, m, teacher, n_prop in plans:
                    if i >= n_prop:
                        continue
                    base = c + teacher - 1
                    pos[seq.slot] = base + i
                    toks[seq.slot] = (seq.tokens[base] if i == 0
                                      else proposals[seq][-1])
                    act[seq.slot] = True
                    stepping.append(seq)
                nxt, self._dpool_k, self._dpool_v = self._draft.step(
                    self._draft_states, self._dpool_k, self._dpool_v,
                    self._tables, pos, toks,
                    np.zeros(self._slots, np.uint32),
                    np.zeros(self._slots, np.float32), act)
                out = np.asarray(nxt)
                for seq in stepping:
                    proposals[seq].append(int(out[seq.slot]))
                    seq.draft_next = pos[seq.slot] + 1

        # ONE target dispatch verifies/extends every slot's window
        with obs_attr.phase("generation", "build") as bsp:
            pos = np.zeros(self._slots, np.int32)
            toks = np.zeros((self._slots, w), np.int32)
            nv = np.zeros(self._slots, np.int32)
            temps = np.zeros(self._slots, np.float32)
            seeds = np.zeros(self._slots, np.uint32)
            prefill = 0
            for seq, c, m, teacher, n_prop in plans:
                window = seq.tokens[c:c + teacher] + proposals[seq]
                pos[seq.slot] = c
                toks[seq.slot, :len(window)] = window
                nv[seq.slot] = teacher + n_prop
                temps[seq.slot] = seq.temperature
                seeds[seq.slot] = seq.seed
                if bsp is not None and c < seq.prompt_len - 1:
                    prefill += 1
            full_plans = [(seq, c, m, teacher, n_prop, proposals[seq])
                          for seq, c, m, teacher, n_prop in plans]
            attrs = self._tick_attrs(prefill) if bsp is not None else {}
            account = (self._account.of(pos[nv > 0])
                       if bsp is not None else None)
        with obs_tracing.span("serving.decode_tick", active=len(plans),
                              speculative=True, **attrs) as sp:
            if sp is not None and account is not None:
                sp.defer(account)
            with obs_attr.phase("generation", "draft_verify"):
                fault_injector().fire("serving.decode")
                nxt, self._pool_k, self._pool_v, *counts = (
                    self._decoder.step_window(
                        self._states, self._pool_k, self._pool_v,
                        self._tables, pos, toks, seeds, temps, nv))
                self._m_ticks.inc()
            clock.mark("dispatch")
            with obs_attr.phase("generation", "sample"):
                preds = np.asarray(nxt)
                self._step_counts(sp, counts)
            self._end_iteration(sp, len(plans))
        return full_plans, preds

    def _deliver_spec(self, plans, preds: np.ndarray, metrics_on: bool):
        """Greedy accept rule over each slot's verified window: keep
        emitting the target's prediction chain while it agrees with
        the next window token (committed tokens agree by construction;
        draft proposals are ACCEPTED on match), stop at the first
        disagreement with the target's own token as the bonus — the
        emitted stream is exactly the target's one-token-at-a-time
        greedy output."""
        now = time.perf_counter()
        delivered = 0
        proposed = accepted = 0
        finished = []
        with obs_attr.phase("generation", "deliver"):
            for seq, c, m, teacher, n_prop, props in plans:
                n_valid = teacher + n_prop
                window = seq.tokens[c:c + teacher] + props
                emitted: List[int] = []
                j_stop = n_valid - 1   # pure-teacher: no emission
                j = m - 1
                if j < n_valid:
                    while True:
                        tok = int(preds[seq.slot, j])
                        emitted.append(tok)
                        if (seq.emitted + len(emitted) >= seq.max_new
                                or (seq.eos_id is not None
                                    and tok == seq.eos_id)):
                            j_stop = j
                            break
                        if j + 1 < n_valid and tok == window[j + 1]:
                            j += 1   # proposal verified: keep going
                            continue
                        j_stop = j
                        break
                seq.cur = c + j_stop + 1
                proposed += n_prop
                if n_prop:
                    accepted += min(max(len(emitted) - 1, 0), n_prop)
                # the draft's KV is valid only where it processed
                # tokens that ended up committed — never past the
                # bonus token
                seq.draft_next = min(seq.draft_next, seq.cur)
                if emitted:
                    if seq.emitted == 0:
                        seq.t_first = now
                        if metrics_on:
                            with obs_tracing.activate(seq.trace_ctx):
                                self._m_ttft.observe(now - seq.t_submit)
                    seq.tokens.extend(emitted)
                    seq.emitted += len(emitted)
                    delivered += len(emitted)
                    for tok in emitted:
                        seq.stream._put(tok)
                    if (seq.emitted >= seq.max_new
                            or (seq.eos_id is not None
                                and emitted[-1] == seq.eos_id)):
                        finished.append(seq)
            if delivered:
                self._m_tokens.inc(delivered)
            if proposed:
                self._m_proposed.inc(proposed)
            if accepted:
                self._m_accepted.inc(accepted)
            self._finish_seqs(finished, now, metrics_on)
            # freshly-filled full prompt blocks become shareable the
            # moment the cursor passes their end (no-op once a
            # sequence has nothing pending or was evicted)
            for plan in plans:
                self._cache.commit_prefix(plan[0], plan[0].cur)

    def _install_states(self, pending):
        import jax

        states, draft_states = pending
        new = {n: jax.device_put(v, self._device)
               for n, v in states.items()}
        new_draft = ({n: jax.device_put(v, self._device)
                      for n, v in draft_states.items()}
                     if draft_states is not None else None)
        # cached prefix K/V is keyed by token content alone and is
        # valid for exactly ONE parameter version: flush it, or
        # post-swap requests would skip prefill into the OLD
        # checkpoint's K/V and silently emit wrong tokens
        self._cache.flush_prefix()
        with self._lock:
            self._states = new
            if new_draft is not None:
                self._draft_states = new_draft
            self._pending_states = None
            self._lock.notify_all()
        self._m_swaps.inc()
        self._swap_done.set()


# -- model dir format --------------------------------------------------------

def save_generation_model(dirname: str, states: Dict[str, np.ndarray],
                          spec: Dict,
                          draft_states: Optional[Dict] = None) -> str:
    """Persist a generation model: `generation.json` (architecture
    spec: vocab_size/d_model/n_heads/n_layers/d_inner, plus optional
    serving defaults block_size/max_blocks_per_seq/slots/kv_blocks/
    kv_dtype/spec_k and an optional `draft` sub-spec) and one npz of
    parameters.  With `draft_states`, the speculative-decoding draft
    model's parameters land in a second npz and spec["draft"] must
    name its architecture ({d_model, n_heads, n_layers[, d_inner]};
    vocab and block geometry are shared with the target).  The
    directory is what `cli serve` and the replica hot-swap verb
    consume."""
    os.makedirs(dirname, exist_ok=True)
    for key in ("vocab_size", "d_model", "n_heads", "n_layers"):
        if key not in spec:
            raise ValueError(f"spec missing {key!r}")
    if draft_states is not None:
        draft = spec.get("draft")
        if not isinstance(draft, dict):
            raise ValueError(
                "draft_states given but spec['draft'] (the draft "
                "architecture dict) is missing")
        for key in ("d_model", "n_heads", "n_layers"):
            if key not in draft:
                raise ValueError(f"spec['draft'] missing {key!r}")
        np.savez(os.path.join(dirname, MODEL_DRAFT_PARAMS_FILENAME),
                 **{n: np.asarray(v) for n, v in draft_states.items()})
    with open(os.path.join(dirname, MODEL_SPEC_FILENAME), "w") as f:
        json.dump(spec, f, indent=1, sort_keys=True)
    np.savez(os.path.join(dirname, MODEL_PARAMS_FILENAME),
             **{n: np.asarray(v) for n, v in states.items()})
    return dirname


def load_generation_model(dirname: str, with_draft: bool = False):
    """-> (states, spec) saved by save_generation_model; with
    `with_draft=True`, -> (states, spec, draft_states_or_None)."""
    with open(os.path.join(dirname, MODEL_SPEC_FILENAME)) as f:
        spec = json.load(f)
    with np.load(os.path.join(dirname, MODEL_PARAMS_FILENAME)) as z:
        states = {n: z[n] for n in z.files}
    if not with_draft:
        return states, spec
    draft_states = None
    dpath = os.path.join(dirname, MODEL_DRAFT_PARAMS_FILENAME)
    if os.path.exists(dpath):
        with np.load(dpath) as z:
            draft_states = {n: z[n] for n in z.files}
    return states, spec, draft_states


def server_from_model_dir(dirname: str, *, block_size: Optional[int] = None,
                          max_blocks_per_seq: Optional[int] = None,
                          slots: Optional[int] = None,
                          kv_blocks: Optional[int] = None,
                          kv_dtype: Optional[str] = None,
                          spec_k: Optional[int] = None,
                          use_draft: bool = True,
                          place=None,
                          **kw) -> GenerationServer:
    """Build a GenerationServer from a saved model dir.

    Resets the framework unique-name counters to rebuild the decoder
    under the names the parameters were saved with — intended for
    fresh serving processes (cli serve, replicas), not mid-session.
    `kv_dtype` overrides the spec's pool precision; a model dir with
    draft params arms speculative decoding unless `use_draft=False`.
    The decoder is built FOR `place`'s platform (kernel selection and
    pool donation follow the device the server runs on, not the
    process default).

    Executables persist in the host's one compile cache
    (core/compile_cache.py): the first replica on a host compiles, the
    next deserializes (``stats()['warm_start']``)."""
    from ..core import framework as fw
    from ..core.executor import TPUPlace
    from ..models.transformer import build_lm_paged_decoder

    place = place or TPUPlace()
    platform = place.jax_device().platform
    states, spec, draft_states = load_generation_model(
        dirname, with_draft=True)
    bs = int(block_size or spec.get("block_size", 16))
    nb = int(max_blocks_per_seq
             or spec.get("max_blocks_per_seq",
                         -(-int(spec.get("max_len", 256)) // bs)))
    kvd = kv_dtype or spec.get("kv_dtype")
    fw.reset_unique_names()
    _, decoder = build_lm_paged_decoder(
        spec["vocab_size"], bs, nb, d_model=spec["d_model"],
        n_heads=spec["n_heads"], n_layers=spec["n_layers"],
        d_inner=spec.get("d_inner"), kv_dtype=kvd, platform=platform)
    draft_decoder = None
    if draft_states is not None and use_draft:
        dspec = spec["draft"]
        fw.reset_unique_names()
        _, draft_decoder = build_lm_paged_decoder(
            spec["vocab_size"], bs, nb, d_model=dspec["d_model"],
            n_heads=dspec["n_heads"], n_layers=dspec["n_layers"],
            d_inner=dspec.get("d_inner"), kv_dtype=kvd,
            platform=platform)
    else:
        draft_states = None
    server = GenerationServer(
        decoder, states,
        slots=int(slots or spec.get("slots", 8)),
        kv_blocks=int(kv_blocks or spec.get("kv_blocks", 64)),
        draft_decoder=draft_decoder, draft_states=draft_states,
        spec_k=(spec_k if spec_k is not None
                else spec.get("spec_k")), place=place, **kw)
    _publish_static_decode_floor(spec, server)
    return server


def _publish_static_decode_floor(spec: dict, server: GenerationServer):
    """Publish the static roofline floor for the decode phase so the
    collector's calibration detector can band measured-vs-static
    (docs/observability.md "Time attribution").  Skipped, by name, on
    a device the cost model has no peaks for."""
    from ..analysis.cost_model import (analyze_generation_spec,
                                       roofline_seconds,
                                       running_device_kind,
                                       serving_kernel_cost)
    try:
        kind = running_device_kind(server._device)
    except KeyError as e:
        _LOG.info("no static decode floor: %s", e.args[0])
        return
    step = analyze_generation_spec(
        spec, slots=server._slots, device=kind)["kernels"][0]
    # band against the backend the DECODER was built with (the
    # analyzer resolves for this process's platform, the decoder for
    # its own): the calibration ratio must compare measured time to
    # the floor of what runs
    backend = ("pallas" if server._decoder.kernels.get(
        "paged_attention_decode") == "pallas" else "xla")
    if step.get("backend") != backend:
        step = serving_kernel_cost(
            "paged_decode_step", spec, slots=server._slots,
            context=(int(spec.get("block_size", 16))
                     * int(spec.get("max_blocks_per_seq", 64))) // 2,
            kv_dtype=str(spec.get("kv_dtype") or "fp32"),
            backend=backend, device=kind)
    obs_attr.publish_static_floor("generation", {
        "decode": roofline_seconds(step["flops"], step["bytes"], kind),
    })
