"""Share of the window's wall time in which the loop had no step
dispatched because it was getting the next batch: inside the reader,
and packing and feeding its rows (from the end of one iteration to the
beginning of the next, by the job's reader wrapper and event handler).
The copy to the device inside `Executor.run` is not in it; the device's
idle share shows that."""
LAYER = "reader / data_feeder"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "host_clock"


def compute(run):
    wait = run.counters.get("input_wait_s")
    window = run.t_window_close - run.t_window_open
    return 100.0 * wait / window if wait is not None and window > 0 else None
