"""Median distance between the completions of consecutive steps, in
the cell fed by a Python reader through `Trainer`."""
LAYER = "trainer / core.executor"
UNIT = "ms"
MOVES = "train_reader_throughput"
SOURCE = "host_clock"


def compute(run):
    import common

    return common.step_ms(run)
