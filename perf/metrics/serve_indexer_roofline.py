"""Share of its roofline that the lightning indexer reaches: the least
seconds the chip could take to score the rows under the cursors of the
traced slice's ticks, over the device seconds under the four
`paged_decoder/indexer_*` scopes (queries and weights, the key and its
write, the scores over the table, the selection) in that slice.

Work: `kv_rows_indexed` on the program's `serving.decode_tick` spans
(cursor + 1 summed over the lanes with a sequence and the selecting
layers), summed over the ticks whose middle lies in the slice, times a
row's bytes and operations (`perf/sparse_attention_cost.py`: 256 B and
8192 operations at 32 index heads of 128 in bf16).  The least seconds
are the LARGER of the bytes over the HBM peak and the operations over
the bf16 peak (32 operations a byte: the bytes bound it on the v5e).
The index queries' projection and the selection itself are the
implementation's: the share errs low.  Nothing where the program has no
such attribute or scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/indexer_"


def compute(run):
    import common

    sparse = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_sparse_attention_roofline.py"))
    seconds, rows = sparse.scope_seconds(run, SCOPE), sparse.slice_sum(
        run, "kv_rows_indexed")
    m = run.cell.config
    if not seconds or not rows or "index_n_heads" not in m:
        return None
    need = sparse.cost().indexer_call(
        rows, m["index_n_heads"], m["index_head_dim"],
        {"bf16": 2, "fp32": 4}[run.cell.traffic["kv_dtype"]])
    least = max(need["bytes"] / run.peaks["hbm_bytes_per_s"],
                need["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
