"""Paged KV-cache: block-granular memory management for decode.

The dense serving cache ([slots, max_len, d_model] per layer) couples a
sequence's HBM footprint to the WORST-CASE length: a 16-token request
in a 2048-token slot pins 128x the memory it uses, and a long request
cannot start until a whole slot's worth of contiguous cache is free.
The vLLM PagedAttention design decouples the two:

* ONE pool of fixed-size blocks ([n_layers, num_blocks, block_size,
  d_model] for K and for V) is preallocated up front — serving never
  allocates device memory again;
* each sequence owns an ordered BLOCK TABLE of pool indices; logical
  position j lives in table[j // block_size] at offset j % block_size;
* blocks are allocated as a sequence grows past a block boundary-free
  at admit time for the whole admitted budget here, since the scheduler
  (serving/generation.py) admits only requests whose prompt+max_new
  budget fits — and returned to the free list the moment the sequence
  finishes, so long and short sequences share the pool without
  fragmentation (any free block serves any sequence; "fragmentation"
  can only exist inside a sequence's LAST partially-filled block).
  A request's FRESH blocks are handed out side by side and in
  ascending order of id wherever the free supply allows (`_FreeRuns`:
  the lowest run of free ids that holds them all, else the longest
  runs): any blocks in any order are correct, but the decode kernels
  copy a run of consecutive table entries with one DMA descriptor
  (kernels/paged_attention.py).

Block 0 is reserved as the null/scratch block: unallocated table
entries point at it (gathers stay in-bounds; the position mask hides
the values) and inactive decode slots write into it.

PREFIX CACHING (`prefix_cache=True`): millions of users share system
prompts, so fully-filled PROMPT blocks are hash-consed by content —
block i's key is the chained digest of every prompt token through the
end of block i, so a key identifies the block's values exactly (K/V at
a position is a deterministic function of the token prefix).  A new
sequence whose leading prompt blocks hit the table SHARES those blocks
(refcount++) and the scheduler skips their prefill entirely; the share
is copy-on-write in the degenerate, zero-copy sense: shared blocks are
fully filled and the only write a sequence can aim at one (re-running
the last prompt position of a block-aligned hit) writes byte-identical
values, so no copy is ever needed.  On release, a cached block whose
refcount drops to zero is NOT freed — it parks in an LRU of
unreferenced cached blocks and is evicted (hash unregistered, block
reused) only when an allocation finds the free list empty.

SNAPSHOTS (`state_snapshots=n`): a decoder whose layers keep something
a LANE (a recurrent state, a convolution tail) cannot start a sequence
past position 0 from shared K/V alone, so a cached block MAY CARRY a
snapshot id: a row of the server's snapshot pool that holds "the lanes'
state after this block's last position".  A hit run is then cut back to
the longest prefix that ENDS in a block with a snapshot (and that
leaves the position `cached_upto` to be run); the blocks that hit by
hash and lay past it are counted (`prefix_blocks_cut`) and given to the
request fresh.  Snapshots have a count and an LRU of their own, in two
ages: taking a row for a new one drops the least recently used of those
that NO admission has hit, and only when every one has been hit the
least recently used of all (most prompts' snapshots are never hit
again; at a save a request they would otherwise push a shared
document's out between two of its hits, and a document that has lost
its snapshot is run whole by every later request).  The block of a
dropped snapshot stays cached (later hits are shorter, never wrong).
What a snapshot holds is the server's and the decoder's business: here
it is an integer.

This module is the HOST-side manager (free list, refcounts, hash
table, LRU, accounting); the device-side gather/scatter math lives in
models/transformer.build_lm_paged_decoder.
"""
from __future__ import annotations

import bisect
import hashlib
import itertools
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..observability import metrics as obs_metrics

__all__ = ["PagedKVCache", "KVPoolExhausted"]

_CACHE_IDS = itertools.count()
_M_BLOCKS_USED = obs_metrics.gauge(
    "paddle_tpu_serving_kv_blocks_in_use",
    "allocated KV-cache blocks (out of kv_blocks_total)", ("server",),
    always=True)
_M_BLOCKS_TOTAL = obs_metrics.gauge(
    "paddle_tpu_serving_kv_blocks_total",
    "allocatable KV-cache blocks in the preallocated pool", ("server",),
    always=True)
_M_UTIL = obs_metrics.gauge(
    "paddle_tpu_serving_kv_pool_utilization",
    "fraction of the KV block pool currently allocated", ("server",),
    always=True)
_M_PREFIX_HITS = obs_metrics.counter(
    "paddle_tpu_serving_prefix_hits_total",
    "prompt blocks served from the prefix cache (prefill skipped)",
    ("server",), always=True)
_M_PREFIX_MISSES = obs_metrics.counter(
    "paddle_tpu_serving_prefix_misses_total",
    "cacheable prompt blocks that had to be prefilled", ("server",),
    always=True)
_M_BYTES_RESIDENT = obs_metrics.gauge(
    "paddle_tpu_serving_kv_bytes_resident",
    "device bytes of KV data held by live sequences "
    "(referenced blocks x bytes per block, K+V, all layers)",
    ("server",), always=True)


class KVPoolExhausted(RuntimeError):
    """An allocation asked for more blocks than are free.  The scheduler
    treats this as admission backpressure (the request waits for blocks
    to free), never as a crash."""


class _FreeRuns:
    """The free blocks, held as RUNS of consecutive ids (`starts[i]` to
    `ends[i]`, exclusive, ascending and never touching): what an
    admission wants is not any n blocks but n blocks side by side, and
    a list popped from its end hands out, after some churn, runs of ten
    where requests hold a hundred (PERF.md section 6, PR 56).  A few
    hundred runs at most: every operation is a bisection or a walk over
    them, under the cache's lock."""

    def __init__(self, first: int, last: int):
        self.starts, self.ends = [first], [last + 1]
        self.count = last - first + 1

    def __len__(self) -> int:
        return self.count

    def add(self, blk: int) -> None:
        """`blk` comes back: it joins the runs it touches."""
        i = bisect.bisect_left(self.starts, blk)
        left = i > 0 and self.ends[i - 1] == blk
        right = i < len(self.starts) and self.starts[i] == blk + 1
        if left and right:
            self.ends[i - 1] = self.ends.pop(i)
            self.starts.pop(i)
        elif left:
            self.ends[i - 1] = blk + 1
        elif right:
            self.starts[i] = blk
        else:
            self.starts.insert(i, blk)
            self.ends.insert(i, blk + 1)
        self.count += 1

    def take(self, n: int) -> List[int]:
        """`n` blocks (at most `len(self)`) for ONE admission, in
        ascending order: the head of the lowest run that holds them
        all, else the longest runs whole until the rest fits one."""
        taken: List[int] = []
        self.count -= n
        while n:
            lengths = [e - s for s, e in zip(self.starts, self.ends)]
            i = next((i for i, length in enumerate(lengths) if length >= n),
                     None)
            if i is None:
                i = lengths.index(max(lengths))
            got = min(n, lengths[i])
            taken.extend(range(self.starts[i], self.starts[i] + got))
            self.starts[i] += got
            if self.starts[i] == self.ends[i]:
                del self.starts[i], self.ends[i]
            n -= got
        return sorted(taken)


def _chain_block_hashes(tokens: Sequence[int],
                        block_size: int) -> List[bytes]:
    """Chained content digests for each FULL block of `tokens`: key i
    commits to every token through position (i+1)*block_size, so equal
    keys mean equal K/V values (decode is deterministic in the prefix).
    Collision-resistant digests, not Python hash(): a collision would
    alias two different prefixes into one block — silently wrong
    tokens, not a crash."""
    keys = []
    h = b""
    for i in range(len(tokens) // block_size):
        blk = np.asarray(tokens[i * block_size:(i + 1) * block_size],
                         np.int64)
        h = hashlib.sha1(h + blk.tobytes()).digest()
        keys.append(h)
    return keys


class PagedKVCache:
    """Free-list manager over one preallocated pool of KV blocks.

    `num_blocks` is the allocatable budget (the device pool holds one
    extra reserved null block).  `server_label` ties the utilization
    series to the owning GenerationServer's metrics instance.
    `prefix_cache=True` arms block-level prefix caching (hash-consed
    full prompt blocks, refcounted sharing, LRU eviction of
    unreferenced cached blocks).  `bytes_per_block` (device bytes of
    K+V across all layers for one block) feeds the
    `paddle_tpu_serving_kv_bytes_resident` gauge.  `state_snapshots`
    (None: hits need none) is the number of snapshot rows cached blocks
    may carry between them (module docstring); `snapshot_bytes` what one
    holds, for `state_snapshot_bytes`."""

    def __init__(self, num_blocks: int, block_size: int,
                 max_blocks_per_seq: int,
                 server_label: Optional[str] = None,
                 prefix_cache: bool = False,
                 bytes_per_block: int = 0,
                 state_snapshots: Optional[int] = None,
                 snapshot_bytes: int = 0):
        if num_blocks < 1:
            raise ValueError("num_blocks must be >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.prefix_cache = bool(prefix_cache)
        self.bytes_per_block = int(bytes_per_block)
        # device block ids 1..num_blocks (0 is the reserved null block)
        self._free = _FreeRuns(1, self.num_blocks)
        self._owned: Dict[object, List[int]] = {}
        self._ref: Dict[int, int] = {}            # block -> live refs
        self._by_hash: Dict[bytes, int] = {}      # content key -> block
        self._hash_of: Dict[int, bytes] = {}      # block -> content key
        # unreferenced cached blocks, oldest-released first (eviction
        # order); values unused
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        # owner -> [(filled_end_position, key, block)] awaiting commit:
        # a freshly-allocated prompt block becomes shareable only after
        # the scheduler's cursor passes its last position (the K/V is
        # actually written) — registering earlier would let a second
        # sequence skip prefill into a still-empty block
        self._pending: Dict[object, List[Tuple[int, bytes, int]]] = {}
        self._hits = 0
        self._misses = 0
        # snapshots of the lanes' state: None where hits need none, else
        # the rows not in use, {cached block: its row} in LRU order
        # (oldest first), {owner: (boundary, row)} of a snapshot whose
        # copy is dispatched and whose block is not yet shareable, and
        # {owner: (row or None, blocks cut)} of what an admission hit
        self.snapshot_bytes = int(snapshot_bytes)
        self._snap_free: Optional[List[int]] = (
            None if state_snapshots is None or not self.prefix_cache
            else list(range(int(state_snapshots) - 1, -1, -1)))
        self._snap_of: "OrderedDict[int, int]" = OrderedDict()
        self._snap_hit: set = set()     # blocks whose snapshot was hit
        self._snap_pending: Dict[object, Tuple[int, int]] = {}
        self._snap_restore: Dict[object, Tuple[Optional[int], int]] = {}
        self._snaps = {"saved": 0, "restored": 0, "evicted": 0, "cut": 0}
        self._lock = threading.Lock()
        self._sid = server_label or f"kv{next(_CACHE_IDS)}"
        self._m_used = _M_BLOCKS_USED.labels(server=self._sid)
        self._m_total = _M_BLOCKS_TOTAL.labels(server=self._sid)
        self._m_util = _M_UTIL.labels(server=self._sid)
        self._m_hits = _M_PREFIX_HITS.labels(server=self._sid)
        self._m_misses = _M_PREFIX_MISSES.labels(server=self._sid)
        self._m_bytes = _M_BYTES_RESIDENT.labels(server=self._sid)
        self._m_total.set(self.num_blocks)
        self._publish()

    # -- accounting ---------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        """Blocks an allocation can claim RIGHT NOW: the free list plus
        unreferenced cached blocks (evictable).  Admission math and the
        pool-drained invariants see cached-but-idle memory as free."""
        with self._lock:
            return len(self._free) + len(self._lru)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - self.free_blocks

    @property
    def cached_blocks(self) -> int:
        """Blocks currently registered in the prefix hash table
        (referenced or parked in the LRU)."""
        with self._lock:
            return len(self._by_hash)

    def utilization(self) -> float:
        return self.used_blocks / self.num_blocks

    def prefix_stats(self) -> Dict[str, int]:
        with self._lock:
            out = {"prefix_hits": self._hits,
                   "prefix_misses": self._misses,
                   "kv_blocks_cached": len(self._by_hash)}
            if self._snap_free is not None:
                n = self._snaps
                out.update(
                    state_snapshots=len(self._snap_of),
                    state_snapshots_saved=n["saved"],
                    state_snapshots_restored=n["restored"],
                    state_snapshots_evicted=n["evicted"],
                    state_snapshot_bytes=(
                        (n["saved"] + n["restored"]) * self.snapshot_bytes),
                    prefix_blocks_cut=n["cut"])
            return out

    def blocks_for(self, num_positions: int) -> int:
        """Blocks needed to hold `num_positions` KV entries."""
        return -(-int(num_positions) // self.block_size)

    def prompt_keys(self, prompt_tokens: Sequence[int]) -> List[bytes]:
        """Precompute the prompt's chained block keys (submit-time
        memoization hook: the scheduler re-checks a blocked queue head
        every tick, and re-hashing a long system prompt per tick is
        wasted host work under the cache lock)."""
        return _chain_block_hashes(prompt_tokens, self.block_size)

    def _keys(self, prompt_tokens, prompt_keys) -> List[bytes]:
        if not self.prefix_cache:
            return []
        if prompt_keys is not None:
            return prompt_keys
        if prompt_tokens is None:
            return []
        return _chain_block_hashes(prompt_tokens, self.block_size)

    def can_admit(self, num_positions: int,
                  prompt_tokens: Optional[Sequence[int]] = None,
                  prompt_keys: Optional[List[bytes]] = None,
                  cached_upto: Optional[int] = None) -> bool:
        n = self.blocks_for(num_positions)
        if n > self.max_blocks_per_seq:
            return False
        keys = self._keys(prompt_tokens, prompt_keys)
        with self._lock:
            shared, lru_hits, _ = self._count_hits_locked(keys, cached_upto)
            hits = len(shared)
            # hit blocks parked in the LRU are RESURRECTED by the
            # allocation, not consumed as fresh supply — counting them
            # on both sides would admit a request allocate_prefix
            # cannot actually serve
            avail = len(self._free) + len(self._lru) - lru_hits
            return n - hits <= avail

    def _count_hits_locked(self, keys, cached_upto=None
                           ) -> Tuple[int, int, int]:
        """(the leading hit blocks, how many of those sit in the LRU,
        how many hit by hash and are not among them).  Where
        blocks carry snapshots the run is cut back to its last block
        with one whose end is at most `cached_upto` (None: any); the
        rest are the third number."""
        blocks = []
        for key in keys:
            blk = self._by_hash.get(key)
            if blk is None:
                break           # a hit run must be prefix-contiguous
            blocks.append(blk)
        hits = len(blocks)
        if self._snap_free is not None:
            while hits and (
                    blocks[hits - 1] not in self._snap_of or (
                        cached_upto is not None
                        and hits * self.block_size > cached_upto)):
                hits -= 1
        shared = blocks[:hits]
        return (shared, sum(1 for b in shared if b in self._lru),
                len(blocks) - hits)

    def _publish(self):
        used = self.num_blocks - len(self._free) - len(self._lru)
        self._m_used.set(used)
        self._m_util.set(used / self.num_blocks)
        if self.bytes_per_block:
            self._m_bytes.set(used * self.bytes_per_block)

    # -- alloc/free ---------------------------------------------------------
    def _take_block_locked(self) -> Optional[int]:
        """One allocatable block: free list first, else evict the
        least-recently-released unreferenced cached block (its hash is
        unregistered — the content is about to be overwritten)."""
        if self._free:
            return self._free.take(1)[0]
        if self._lru:
            blk, _ = self._lru.popitem(last=False)
            key = self._hash_of.pop(blk)
            self._by_hash.pop(key, None)
            self._drop_snapshot_locked(blk)
            return blk
        return None

    def _drop_snapshot_locked(self, blk: int) -> None:
        """`blk` loses its snapshot, if it carries one: the row is free
        again and the block stays what it was."""
        row = self._snap_of.pop(blk, None)
        self._snap_hit.discard(blk)
        if row is not None:
            self._snap_free.append(row)
            self._snaps["evicted"] += 1

    def allocate(self, owner, num_positions: int) -> np.ndarray:
        """Allocate blocks for `num_positions` under `owner` (one admit
        = one owner, usually the sequence object) and return the padded
        block table [max_blocks_per_seq] int32 (tail entries 0 → the
        null block)."""
        return self.allocate_prefix(owner, num_positions)[0]

    def allocate_prefix(self, owner, num_positions: int,
                        prompt_tokens: Optional[Sequence[int]] = None,
                        prompt_keys: Optional[List[bytes]] = None,
                        cached_upto: Optional[int] = None
                        ) -> Tuple[np.ndarray, int]:
        """Allocate like `allocate`, sharing leading fully-filled
        prompt blocks already in the prefix cache.  Returns (table,
        cached_positions): the first `cached_positions` logical
        positions already hold this prompt's K/V — the scheduler starts
        the cursor there and skips their prefill.  Where blocks carry
        snapshots the shared run ends in a block with one, at or before
        position `cached_upto`, and `hit_snapshot(owner)` then names the
        snapshot the owner's lane starts from."""
        n = self.blocks_for(num_positions)
        if n > self.max_blocks_per_seq:
            raise ValueError(
                f"{num_positions} positions need {n} blocks > "
                f"max_blocks_per_seq {self.max_blocks_per_seq}")
        keys = self._keys(prompt_tokens, prompt_keys)
        with self._lock:
            if owner in self._owned:
                raise ValueError("owner already holds blocks")
            blocks, _, cut = self._count_hits_locked(keys, cached_upto)
            hits = len(blocks)
            for blk in blocks:
                self._ref[blk] = self._ref.get(blk, 0) + 1
                self._lru.pop(blk, None)   # resurrect from eviction
            fresh_start = len(blocks)
            # the fresh blocks side by side where the free runs allow
            # (`_FreeRuns.take`); what they lack is evicted below
            blocks += self._free.take(min(n - fresh_start, len(self._free)))
            for blk in blocks[fresh_start:]:
                self._ref[blk] = 1
            while len(blocks) < n:
                blk = self._take_block_locked()
                if blk is None:
                    # roll back the shared refs: admission backpressure
                    # must leave the accounting untouched
                    for b in blocks[:fresh_start]:
                        self._release_block_locked(b)
                    for b in blocks[fresh_start:]:
                        self._ref.pop(b, None)
                        self._free.add(b)
                    raise KVPoolExhausted(
                        f"need {n} KV blocks, "
                        f"{len(self._free) + len(self._lru)} free "
                        f"(pool {self.num_blocks})")
                self._ref[blk] = 1
                blocks.append(blk)
            # the fresh blocks in ascending order of id (evicted ones
            # among them), so that what lies side by side in the pool
            # lies side by side in the table: the attention kernel
            # copies a run of consecutive table entries with one DMA
            # descriptor (kernels/paged_attention.start_pages).  Prefix
            # hits keep the order the prompt gives them.
            blocks[fresh_start:] = sorted(blocks[fresh_start:])
            self._owned[owner] = blocks
            if keys:
                self._hits += hits
                self._misses += len(keys) - hits
                # freshly-allocated FULL prompt blocks become shareable
                # once commit_prefix sees the cursor pass their end
                self._pending[owner] = [
                    ((i + 1) * self.block_size, keys[i], blocks[i])
                    for i in range(hits, len(keys))]
            if self._snap_free is not None:
                self._snaps["cut"] += cut
                row = None
                if hits:
                    # the lane starts from this block's snapshot, which
                    # is now the most recently used
                    self._snap_of.move_to_end(blocks[hits - 1])
                    self._snap_hit.add(blocks[hits - 1])
                    row = self._snap_of[blocks[hits - 1]]
                    self._snaps["restored"] += 1
                self._snap_restore[owner] = (row, cut)
            self._publish()
        if hits:
            self._m_hits.inc(hits)
        if len(keys) - hits:
            self._m_misses.inc(len(keys) - hits)
        table = np.zeros(self.max_blocks_per_seq, np.int32)
        table[:n] = blocks
        return table, hits * self.block_size

    def hit_snapshot(self, owner) -> Tuple[Optional[int], int]:
        """(the snapshot row `owner`'s admission hit, None where it hit
        none; the blocks that hit by hash and were cut off behind it).
        Asked once: the caller dispatches the restore."""
        with self._lock:
            return self._snap_restore.pop(owner, (None, 0))

    def reserve_snapshot(self, owner, boundary: int) -> Optional[int]:
        """A row for a snapshot of `owner`'s lane after position
        `boundary` - 1 (the end of a full prompt block of its own);
        where none is free, that of the least recently used snapshot
        that no admission has hit, else of the least recently used: the
        caller
        dispatches the copy into it, and `commit_prefix` hangs it on the
        block once that is shareable.  None where the cache keeps no
        snapshots, the owner holds no blocks, or it reserved one
        already."""
        with self._lock:
            if (self._snap_free is None or owner not in self._owned
                    or owner in self._snap_pending):
                return None
            if not self._snap_free and self._snap_of:
                self._drop_snapshot_locked(next(
                    (b for b in self._snap_of if b not in self._snap_hit),
                    next(iter(self._snap_of))))
            if not self._snap_free:
                return None
            row = self._snap_free.pop()
            self._snap_pending[owner] = (int(boundary), row)
            return row

    def commit_prefix(self, owner, filled_upto: int) -> None:
        """Register `owner`'s pending prompt blocks whose last position
        is now < `filled_upto` (the scheduler's cursor: every position
        below it has its K/V written).  Idempotent; a key another
        sequence committed first keeps the FIRST block (this owner's
        copy stays private — identical content, never aliased)."""
        with self._lock:
            pend = self._pending.get(owner)
            if not pend:
                return
            remaining = []
            for end, key, blk in pend:
                if end <= filled_upto:
                    if key not in self._by_hash:
                        self._by_hash[key] = blk
                        self._hash_of[blk] = key
                else:
                    remaining.append((end, key, blk))
            if remaining:
                self._pending[owner] = remaining
            else:
                self._pending.pop(owner, None)
            snap = self._snap_pending.get(owner)
            if snap is not None and snap[0] <= filled_upto:
                # the snapshot goes on the block that is cached under the
                # boundary's key (this owner's, or the first committed:
                # the state after equal tokens is equal), unless that
                # carries one already
                del self._snap_pending[owner]
                blk = next((self._by_hash.get(key) for end, key, _ in pend
                            if end == snap[0]), None)
                if blk is None or blk in self._snap_of:
                    self._snap_free.append(snap[1])
                else:
                    self._snap_of[blk] = snap[1]
                    self._snaps["saved"] += 1

    def _release_block_locked(self, blk: int) -> None:
        r = self._ref.get(blk, 0) - 1
        if r > 0:
            self._ref[blk] = r
            return
        self._ref.pop(blk, None)
        if blk in self._hash_of:
            self._lru[blk] = None      # park: evictable, still cached
        else:
            self._free.add(blk)

    def release(self, owner) -> None:
        """Drop `owner`'s references (idempotent — a sequence evicted
        twice must not double-free).  Shared blocks survive while any
        other sequence references them; cached blocks park in the LRU
        instead of freeing."""
        with self._lock:
            blocks = self._owned.pop(owner, None)
            self._pending.pop(owner, None)
            self._snap_restore.pop(owner, None)
            snap = self._snap_pending.pop(owner, None)
            if snap is not None:
                self._snap_free.append(snap[1])
            if blocks:
                for blk in blocks:
                    self._release_block_locked(blk)
                self._publish()

    def flush_prefix(self) -> None:
        """Invalidate every cached prefix block: cached K/V is keyed by
        token content ONLY, so it is valid for exactly one parameter
        version — a checkpoint hot swap MUST flush or post-swap
        requests would attend over the old checkpoint's K/V.  Parked
        (unreferenced) blocks return to the free list; blocks still
        referenced by live sequences merely lose their registration
        and free normally on release."""
        with self._lock:
            for blk in list(self._lru):
                self._free.add(blk)
            self._lru.clear()
            self._by_hash.clear()
            self._hash_of.clear()
            self._pending.clear()
            # every snapshot goes with the blocks: a state is a function
            # of the parameters as the K/V is
            for blk in list(self._snap_of):
                self._drop_snapshot_locked(blk)
            for _, row in self._snap_pending.values():
                self._snap_free.append(row)
            self._snap_pending.clear()
            self._snap_restore.clear()
            self._publish()

    def refcount(self, block: int) -> int:
        """Live references to `block` (testing/introspection)."""
        with self._lock:
            return self._ref.get(int(block), 0)

    def close(self):
        """Reclaim this pool's registry series (server churn must not
        grow metric dumps without bound)."""
        for fam in (_M_BLOCKS_USED, _M_BLOCKS_TOTAL, _M_UTIL,
                    _M_PREFIX_HITS, _M_PREFIX_MISSES, _M_BYTES_RESIDENT):
            fam.remove(server=self._sid)

    def __repr__(self):
        return (f"PagedKVCache(blocks={self.num_blocks}, "
                f"block_size={self.block_size}, "
                f"free={self.free_blocks}, "
                f"prefix_cache={self.prefix_cache})")
