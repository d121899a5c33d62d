"""Share of the traced slice's device seconds under
`paged_decoder/indexer_topk` of the resident decode step: the selection
of `index_topk` rows a lane from the index scores (`lm_block
.select_rows`: the k-th largest score by a search over the scores' bits,
32 counts over the table's rows, then the rows above it and the lowest
of the rows at it).  Read like `serve_indexer_share`; nothing where the
step has no such scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/indexer_topk"


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_ssm_share.py")
    ).scope_share(run, SCOPE)
