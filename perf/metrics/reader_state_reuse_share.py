"""Share of the window's persistable states that `Executor.run` found in
the scope as its last step had left them, and so took with their
committed values and shape keys from its own record of the compiled step
and not through `device_put` and `_aval_key` again: 100 x (1 - the sum of
`recommitted` over the sum of `states`) on the window's `executor.feed`
spans.  Near 100 in a loop that lets the executor keep its states; it
falls where something replaces them between steps or holds them as NumPy
values.  Nothing where the program sets no such attributes (a program
that commits every state at every step) or keeps no span store under a
listener."""
LAYER = "trainer / core.executor"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
NAME = "executor.feed"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    attrs = [s["attrs"] for s in tracing.finished_spans()
             if s["name"] == NAME and lo <= s["ts"] + s["dur"] <= hi
             and "recommitted" in s["attrs"]]
    states = sum(a["states"] for a in attrs)
    if not states:
        return None
    return 100.0 * (1.0 - sum(a["recommitted"] for a in attrs) / states)
