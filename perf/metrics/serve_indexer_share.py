"""Share of the traced slice's device seconds under the lightning
indexer's four named scopes of the resident decode step:
`paged_decoder/indexer_q` (the index queries from the normed query
latent, their rotation, a weight a head), `indexer_k` (the position's
one index key, its LayerNorm and rotation, its write into the layer's
plane), `indexer_scores` (the index keys through the table, the heads'
products, relu, the weighted sum) and `indexer_topk` (the selection).
What choosing the rows costs, beside what attending over fewer saves.
Read like `serve_ssm_share`, whose reader it uses: nothing where the
step has no such scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/indexer_"


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_ssm_share.py")
    ).scope_share(run, SCOPE)
