"""Share of the traced slice's device seconds that the two snapshot
copies take: the decoder's `snapshot_save` (a lane's state and tails
into a row of the snapshot pool, scope `state_snapshot_save`) and
`snapshot_restore` (a row back into a lane, `state_snapshot_restore`),
which the server registers at warm-up as `paged_decoder.snapshot_save`
and `paged_decoder.snapshot_restore`.  A device trace names
instructions, not programs: an instruction name that the resident
step's own table holds too is left to the step, so the share errs low,
never above what the copies took.  Nothing where the program registers
no such programs (a program before PR 59, a block without a lane state,
a server without a prefix cache) or the trace has no device plane."""
LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
LABELS = ("paged_decoder.snapshot_save", "paged_decoder.snapshot_restore")


def compute(run):
    from paddle_tpu import profiler

    if not run.trace or not hasattr(profiler, "hlo_scopes"):
        return None
    ops = run.trace["op_seconds"]
    total = sum(profiler.scope_seconds(ops, "paged_decoder.step").values())
    step = {}
    for table in profiler.hlo_scopes("paged_decoder.step").values():
        step.update(table)
    copies = {}
    for label in LABELS:
        for table in profiler.hlo_scopes(label).values():
            copies.update({op: scope for op, scope in table.items()
                           if "state_snapshot_" in scope})
    if not total or not copies:
        return None
    return 100.0 * sum(t for op, t in ops.items()
                       if op in copies and op not in step) / total
