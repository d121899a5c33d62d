"""Share of the traced slice's device seconds under the latent
attention's projections in the resident decode step: the named scopes
`paged_decoder/latent_q` (query down, norm, up, rotation), `latent_kv`
(key/value down, norm, rotation), `latent_absorb` (the two per-head
products either side of the kernel: the query's unrotated part times
the key half of `kv_b`, the context times its value half) and
`attn_out`.  What the latent cache costs OUTSIDE the kernel: seven
weight arrays a layer read every tick whatever the lengths.  Read like
`serve_ssm_share`, whose reader it uses: nothing where the step has no
`latent_` scope (then `attn_out` alone is not this metric's)."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPES = ("paged_decoder/latent_", "paged_decoder/attn_out")


def compute(run):
    import common

    share = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_ssm_share.py")).scope_share
    latent = share(run, SCOPES[0])
    return latent + (share(run, SCOPES[1]) or 0.0) if latent else None
