"""XLA compile requests inside the window (0, or something compiled in
it), in the cell fed by a Python reader through `Trainer`."""
LAYER = "trainer / core.executor"
UNIT = "count"
MOVES = "train_reader_throughput"
SOURCE = "program_counter"


def compute(run):
    import common

    return common.recompiles_in_window(run)
