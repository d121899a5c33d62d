"""Production inference serving.

`paddle_tpu.serving` grew from a single module (resident dynamic
batching for one-shot requests, PR 4's `InferenceServer`) into the
serving subsystem; the import path is unchanged, so every existing
``from paddle_tpu.serving import InferenceServer`` keeps working:

* **batching** — the original resident server: AOT-compiled batch-size
  buckets, window-coalesced dynamic batching, deadline shedding.  The
  right tool for stateless one-shot models (image classifiers).
* **kv_cache** — the paged KV-cache: fixed-size blocks carved out of
  ONE preallocated HBM pool, per-sequence block tables, alloc/free at
  sequence admit/finish.  Long and short sequences share the pool
  without fragmentation (the vLLM PagedAttention memory design), and
  fully-filled prompt blocks are hash-consed so sequences with a
  shared prefix SHARE blocks (refcounted, LRU-evicted when idle) and
  skip the shared prefill entirely.
* **generation** — `GenerationServer`: continuous (in-flight) batching
  for autoregressive decode.  One resident decode step per tick over
  the active sequence set; new requests are admitted into free slots
  BETWEEN ticks (prefill folded into the same per-token step), finished
  sequences are evicted immediately, admission is keyed to free KV
  blocks, and every request streams tokens through its own future.
  Optionally speculative: a small draft model proposes k tokens per
  tick and the target verifies the window in one dispatch (greedy
  output bit-identical by construction).  The KV pool stores fp32,
  bf16 or int8 blocks (`kv_dtype`) — quantize-on-write, dequantize-
  on-gather — trading tolerance for 2-4x the resident sequences.
* **replica** — a TCP front for one `GenerationServer` process
  (JSON-line protocol: generate/ping/swap/stats) so replicas can be
  health-checked, drained, and hot-swapped remotely.

The multi-replica front door (TTL-lease registered replicas,
least-outstanding-tokens placement, retry-on-death, zero-downtime
checkpoint hot-swap) lives in `paddle_tpu.cloud.router`.

See docs/serving.md for the architecture and runbook.
"""
from .batching import (InferenceServer, RequestDeadlineExceeded,
                       ServerSaturated)
from .generation import (GenerationServer, GenerationStream,
                         load_generation_model, save_generation_model,
                         server_from_model_dir)
from .kv_cache import KVPoolExhausted, PagedKVCache
from .replica import (ReplicaError, ReplicaServer, ReplicaShed,
                      replica_call, replica_stream)

__all__ = [
    "InferenceServer",
    "ServerSaturated",
    "RequestDeadlineExceeded",
    "PagedKVCache",
    "KVPoolExhausted",
    "GenerationServer",
    "GenerationStream",
    "save_generation_model",
    "load_generation_model",
    "server_from_model_dir",
    "ReplicaServer",
    "ReplicaError",
    "ReplicaShed",
    "replica_call",
    "replica_stream",
]
