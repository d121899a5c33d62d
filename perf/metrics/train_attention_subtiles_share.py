"""Score sub-tiles the training step's causal attention kernels compute
for one that holds an element the mask keeps, a layer's forward call and
its backward call together, in percent.  The kernel module's own account
of the calls it makes at the cell's shape
(`kernels.flash_attention.flash_attention_subtiles`: a head's grid in
sub-tiles of block_q x block_q, by the table the backward's kernels read
their cases from): 100 where no kernel computes a sub-tile that lies
wholly above the diagonal, 120 where both take every live [512 x 1024]
tile whole at 2048, 110 where the backward leaves them out and the
forward does not.  It counts what the program says it does and times
nothing: `train_attention_share` has the kernels' seconds.  Nothing where
the module has no such function (a parent before PR 47) or the kernel is
not what runs (no TPU, a sequence under its crossover)."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_throughput"
SOURCE = "program_counter"


def compute(run):
    import importlib

    import jax
    import jax.numpy as jnp

    # the package's attribute of that name is the function
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    if not hasattr(fa, "flash_attention_subtiles"):
        return None
    m, t = run.cell.config, run.cell.traffic
    heads = m["num_attention_heads"]
    x = jax.ShapeDtypeStruct(
        (int(t["sequences_per_step"]), int(t["sequence_length"]), heads,
         m["hidden_size"] // heads),
        jnp.bfloat16 if m["amp_bf16"] else jnp.float32)
    counts = fa.flash_attention_subtiles(x, x, x, causal=True)
    if counts is None:
        return None
    forward, backward, live = counts
    return 100.0 * (forward + backward) / (2 * live)
