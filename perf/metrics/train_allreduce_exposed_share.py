"""Share of the traced slice that the cores' serial timelines spent inside
collective operations (synchronous ones and the `-done` half of
asynchronous ones): communication with no compute beside it."""
LAYER = "parallel.executor"
UNIT = "%"
MOVES = "train_throughput"
SOURCE = "device_trace"


def compute(run):
    if not run.trace or not run.trace["window_s"] or not run.trace["chips"]:
        return None
    return 100.0 * run.trace["collective_s"] / run.trace["window_s"]
