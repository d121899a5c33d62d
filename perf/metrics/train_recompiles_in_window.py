"""XLA compile requests inside the window (0, or something compiled in
it), in the cells fed whole arrays (`Executor`, `ParallelExecutor`)."""
LAYER = "trainer / core.executor"
UNIT = "count"
MOVES = "train_throughput"
SOURCE = "program_counter"


def compute(run):
    import common

    return common.recompiles_in_window(run)
