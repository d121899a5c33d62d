"""Share of the traced slice's device seconds under the scopes a
looped block adds to the resident decode step: `paged_decoder/
loop_norm` (the norm on each sub-block's output with the residual add
behind it, two a layer, and the final norm of each pass) and
`paged_decoder/exit_gate` (the gate of each pass and the count of open
gates): some 390 small reductions a tick over 12 rows each, bound by
launches and not by bytes.  Read like `serve_ssm_share`, whose reader
it uses: nothing where the step has no such scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPES = ("paged_decoder/loop_norm", "paged_decoder/exit_gate")


def compute(run):
    import common

    share = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_ssm_share.py")).scope_share
    parts = [share(run, scope) for scope in SCOPES]
    return sum(p or 0.0 for p in parts) if parts[0] else None
