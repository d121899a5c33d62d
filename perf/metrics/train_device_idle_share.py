"""1 - (union of device-op intervals) / (traced slice of the window), chips
averaged, in the cells fed whole arrays (`Executor`, `ParallelExecutor`)."""
LAYER = "device"
UNIT = "%"
MOVES = "train_throughput"
SOURCE = "device_trace"


def compute(run):
    import common

    return common.device_idle_share(run)
