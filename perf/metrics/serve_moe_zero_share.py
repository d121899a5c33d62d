"""Share of the traced slice's device seconds under the named scope
`paged_decoder/moe_zero` of the resident decode step: the identity
experts' part of the expert layer's combine (which of a token's chosen
columns are identity ones, their weights' sum, that sum times the
token's own row, added to what the held experts gave): what
"zero-compute" costs on the device.  A fusion counts under the scope of
its root (`serve_kv_gather_share`, whose reader this uses), and the
identity part's add is the LAST operation of the combine: where the
compiler fuses the combine's unsort, weighing and sum into it they are
read here and not under `moe_combine` (`serve_moe_share` reads all of
`moe_*` either way).  0 where the step has the scope and no device time
resolves to it; nothing where the step has no such scope (a block
without identity experts, a program before PR 48) or the scope table
does not resolve."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/moe_zero"


def compute(run):
    import common
    from paddle_tpu import profiler

    share = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_kv_gather_share.py")
    ).scope_share(run, SCOPE)
    if share or share is None:
        return share
    in_step = any(SCOPE in scope
                  for table in profiler.hlo_scopes(
                      "paged_decoder.step").values()
                  for scope in table.values())
    return 0.0 if in_step else None
