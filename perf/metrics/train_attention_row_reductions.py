"""Reductions over a score tile's lanes that the training step's forward
attention kernel makes a query row a key block: the kernel module's own
account of the call it makes at the cell's shape
(`kernels.flash_attention.flash_attention_row_reductions`).  2 where the
kernel takes the row maximum and the row sum of every [block_q, block_k]
float32 score tile; 1 where the sum rides the `p . V` product as a column
of ones beside a head's values (head size 64 since PR 58) and the maximum
alone is reduced.  It counts what the program says it does and times
nothing: `train_attention_forward_ms` has the kernel's milliseconds.
Nothing where the module has no such function (a parent before PR 58) or
the kernel is not what runs (no TPU, a sequence under its crossover)."""
LAYER = "kernels"
UNIT = "count"
MOVES = "train_throughput"
SOURCE = "program_counter"


def compute(run):
    import importlib

    import jax
    import jax.numpy as jnp

    # the package's attribute of that name is the function
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    if not hasattr(fa, "flash_attention_row_reductions"):
        return None
    m, t = run.cell.config, run.cell.traffic
    heads = m["num_attention_heads"]
    x = jax.ShapeDtypeStruct(
        (int(t["sequences_per_step"]), int(t["sequence_length"]), heads,
         m["hidden_size"] // heads),
        jnp.bfloat16 if m["amp_bf16"] else jnp.float32)
    return fa.flash_attention_row_reductions(x, x, x, causal=True)
