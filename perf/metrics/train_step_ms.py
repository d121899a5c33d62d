"""Median distance between the completions of consecutive steps, in
the cells fed whole arrays (`Executor`, `ParallelExecutor`)."""
LAYER = "trainer / core.executor"
UNIT = "ms"
MOVES = "train_throughput"
SOURCE = "host_clock"


def compute(run):
    import common

    return common.step_ms(run)
