"""Executables a step's `Executor.run` sent to the device besides the
step's own: the mean of `aux_dispatches` on the window's `executor.run`
spans.  2 where the step key is made on the host's side of the step
(`jax.random.key` and `fold_in`, each a dispatch the device's queue and
the host's thread pay for at every call), 0 where the compiled step
makes it from two scalars (`cache_stats()["aux_dispatches"]` is the
sum, spans or none).  Nothing at a parent whose spans lack the
attribute, or where no listener keeps a span store."""
LAYER = "trainer / core.executor"
UNIT = "count"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
NAME = "executor.run"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    counts = [s["attrs"]["aux_dispatches"] for s in tracing.finished_spans()
              if s["name"] == NAME and lo <= s["ts"] + s["dur"] <= hi
              and "aux_dispatches" in s["attrs"]]
    return sum(counts) / len(counts) if counts else None
