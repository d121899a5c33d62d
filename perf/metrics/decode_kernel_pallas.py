"""1 where the compiled resident decode step holds a Mosaic custom call
(`tpu_custom_call`: the Pallas paged-attention kernel), 0 where the XLA
gather and reshape path runs.  Read from the compiled step's text."""
LAYER = "kernels"
UNIT = "flag"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def compute(run):
    return run.counters.get("decode_kernel_pallas")
