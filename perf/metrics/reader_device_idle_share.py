"""1 - (union of device-op intervals) / (traced slice of the window), chips
averaged, in the cell fed by a Python reader through `Trainer`."""
LAYER = "device"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "device_trace"


def compute(run):
    import common

    return common.device_idle_share(run)
