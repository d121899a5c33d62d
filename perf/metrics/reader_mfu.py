"""End-to-end model FLOP/s utilisation (perf/flops.py x measured rate over
chips x peak; not a kernel's roofline share), in
the cell fed by a Python reader through `Trainer`."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "host_clock"


def compute(run):
    import common

    return common.mfu(run, "train_reader_throughput")
