"""Share of the window's ticks whose lightning indexer made its selection
with the Pallas call (`paddle_tpu/kernels/select_rows.py`: a block of lanes'
keys in VMEM for all 32 counts of the search for the k-th score, whatever
the compiler does with the rest of the step) and not with
`lm_block.select_rows`' `jax.numpy` lines, which XLA compiles to a `while`
whose keys live where its memory-space assignment happens to leave them (in
HBM in dots3's step of PR 67, 0.17 ms a selection against 0.03): the mean of
`select_kernel` (1 or 0, from `decoder.kernels["index_selection"]`) on the
program's `serving.decode_tick` spans.  100 or 0 in a run: which path a step
takes is decided when it is traced, from shapes and the platform.  Nothing
where the program sets no such attribute (a block without an indexer, a
program before PR 68) or keeps no span store under a listener."""
LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    kernel = [s["attrs"]["select_kernel"] for s in tracing.finished_spans()
              if s["name"] == "serving.decode_tick"
              and lo <= s["ts"] + s["dur"] <= hi
              and "select_kernel" in s["attrs"]]
    return 100.0 * sum(kernel) / len(kernel) if kernel else None
