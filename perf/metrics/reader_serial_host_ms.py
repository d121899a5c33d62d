"""Median, over the window's steps, of the milliseconds from the END of
one step's `executor.fetch` span (the loss has been read: the device is
done and idle) to the END of the next step's `executor.dispatch` span
(the jitted call has returned and the written states are in the scope):
the host's serial section between two steps of a loop that reads its
loss every step, less the readback.  What lies in it: the event handler,
the loop's own lines, the take of the next batch, `Executor.run`'s head,
its feed and its dispatch.  Spans are paired on their own thread, each
dispatch with the newest fetch that ended before it.  Nothing where the
program has no such spans (a loop that leaves its results on the device
records no `executor.fetch`)."""
import statistics

LAYER = "trainer / core.executor"
UNIT = "ms"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
FETCH, DISPATCH = "executor.fetch", "executor.dispatch"


def serial_gaps_ms(spans):
    """The gaps of `spans` (full records), oldest first."""
    ends = sorted((s["ts"] + s["dur"], s["name"], s.get("tid"))
                  for s in spans if s["name"] in (FETCH, DISPATCH))
    read, gaps = {}, []
    for end, name, tid in ends:
        if name == FETCH:
            read[tid] = end
        elif tid in read:
            gaps.append(1e3 * (end - read.pop(tid)))
    return gaps


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    gaps = serial_gaps_ms(s for s in tracing.finished_spans()
                          if lo <= s["ts"] + s["dur"] <= hi)
    return statistics.median(gaps) if gaps else None
