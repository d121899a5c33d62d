"""Share of the traced slice's device seconds under the gated delta
rule's two GATE scopes of the resident decode step: `paged_decoder/
delta_gates` (the log decay a key channel, from the layer's input
through its decay map, and the write strength a head) and
`delta_gate_norm` (the output gate's map of the input, the norm a head
and their product).  What the gates' maps cost a tick: a low-rank pair
each on one configuration (two matrices of [d, r] and [r, H*K]), ONE
full matrix [d, H*K] each on another (`delta_gate_rank` 0), read whatever
the lanes hold; the pair of cells says what full-rank gates cost beside
their low-rank twin.  Read like `serve_delta_rule_share`, through the
same reader: nothing where the program has no scope table or the step
no such scope, or where under 90% of the device seconds resolve to a
`paged_decoder/` scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPES = ("paged_decoder/delta_gates", "paged_decoder/delta_gate_norm")


def compute(run):
    import common

    share = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_ssm_share.py")).scope_share
    parts = [share(run, scope) for scope in SCOPES]
    return None if None in parts else sum(parts)
