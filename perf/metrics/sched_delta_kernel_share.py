"""Share of the window's ticks whose delta-rule layers ran their recurrence
as the Pallas kernel (`paddle_tpu/kernels/delta_rule.py`: a head's matrix
state crosses HBM once in and once out, in place on the lanes' pool) and
not as `lm_block.delta_rule`'s `jax.numpy` lines, which XLA compiles to two
fusions that walk the states three times: the mean of `delta_kernel` (1 or
0, from `decoder.delta_kernel`) on the program's `serving.decode_tick`
spans.  100 or 0 in a run: which path a step takes is decided when it is
traced, from shapes and the platform.  Nothing where the program sets no
such attribute (a block without delta-rule layers, a program before PR 60)
or keeps no span store under a listener."""
LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    kernel = [s["attrs"]["delta_kernel"] for s in tracing.finished_spans()
              if s["name"] == "serving.decode_tick"
              and lo <= s["ts"] + s["dur"] <= hi
              and "delta_kernel" in s["attrs"]]
    return 100.0 * sum(kernel) / len(kernel) if kernel else None
