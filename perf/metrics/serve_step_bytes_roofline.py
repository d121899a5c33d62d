"""Share of the HBM roofline that the WHOLE decode step reaches: the bytes
the window's ticks must move, over what the chip could move in the window's
wall seconds.

Bytes: on every `serving.decode_tick` span of the window (the program's span
store between the ends of the tap's first and last spans, as
`sched_kv_copy_covered_share.py` takes it: `span_cpu.window`) `decoder.tick_counts`'s account of
the tick dispatched, `step_bytes_weights` (every array the step reads whole
whatever the traffic) + `step_bytes_cache` (what follows the cursors: each
pool's pages read at that pool's page, the lanes' states and tails read and
written, the rows written) + `expert_bytes` (ONE routed expert's three
matrices) x `moe_experts_hit` (the experts the tick READ routed to, which
comes back with its tokens: one tick earlier, as `moe_experts_roofline` reads
it), summed over every span but the first.  Under `perf/*_bytes.py`'s rule:
what the algorithm needs, not what a compiler emitted, so the share errs low.
Seconds: the wall from the first span's end to the last's, times
`peaks["hbm_bytes_per_s"]`: stalls, host gaps and idle ticks included, which
is what the gap between two tokens is made of.  So 86 says the step's bytes
at the memory's speed explain 86% of the period, and a serving claim has the
rest to win or fewer bytes to read.  Nothing where a tick of the window lacks
the three (a parent before PR 67; a speculative server), where the ring
dropped records, or where a deferred account was lost or failed
(`tracing.dropped_deferred()`, `failed_deferred()`): the sum would be short."""
import os

LAYER = "device"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"

NAMES = ("step_bytes_weights", "step_bytes_cache", "expert_bytes")


def window_bytes(run):
    """-> (weights, cache, experts, seconds): the three sums over the
    window's tick spans but the first, and the wall seconds from the first
    span's end to the last's; None as the module says."""
    import common
    from paddle_tpu.observability import tracing

    if not hasattr(tracing, "dropped_deferred"):
        return None
    ticks = common.load_module(os.path.join(
        os.path.dirname(__file__), "span_cpu.py")).window(
            run, "serving.decode_tick")
    if (not ticks or len(ticks) < 2 or tracing.dropped_deferred()
            or tracing.failed_deferred()
            or not all(n in s["attrs"] for s in ticks for n in NAMES)):
        return None
    attrs = [s["attrs"] for s in ticks[1:]]
    seconds = (ticks[-1]["ts"] + ticks[-1]["dur"]
               - ticks[0]["ts"] - ticks[0]["dur"])
    return (sum(a["step_bytes_weights"] for a in attrs),
            sum(a["step_bytes_cache"] for a in attrs),
            sum(a["expert_bytes"] * a.get("moe_experts_hit", 0)
                for a in attrs),
            seconds)


def compute(run):
    got = window_bytes(run)
    if got is None or got[3] <= 0:
        return None
    return 100.0 * sum(got[:3]) / (run.peaks["hbm_bytes_per_s"] * got[3])
