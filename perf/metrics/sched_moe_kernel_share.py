"""Share of the window's ticks whose expert layer ran as the Pallas grouped
matmul (`paddle_tpu/kernels/grouped_matmul.py`: each expert's matrices meet
its own rows only) and not as `jax.lax.ragged_dot`, which the TPU compiler
runs as a dense product of all rows with all experts: the mean of
`moe_kernel` (1 or 0, from `decoder.expert_kernel`) on the program's
`serving.decode_tick` spans.  100 or 0 in a run: which path a step takes is
decided when it is traced, from shapes, the weights' dtype and the platform.
Nothing where the program sets no such attribute (a block without experts,
a program without the kernel) or keeps no span store under a listener."""
LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    kernel = [s["attrs"]["moe_kernel"] for s in tracing.finished_spans()
              if s["name"] == "serving.decode_tick"
              and lo <= s["ts"] + s["dur"] <= hi
              and "moe_kernel" in s["attrs"]]
    return 100.0 * sum(kernel) / len(kernel) if kernel else None
