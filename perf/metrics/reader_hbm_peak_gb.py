"""Peak of the fullest chip in 1e9 bytes (`peak_bytes_in_use` plus
`peak_bytes_reserved`), in the cell fed by a Python reader through `Trainer`."""
LAYER = "device"
UNIT = "GB"
MOVES = "train_reader_throughput"
SOURCE = "program_counter"


def compute(run):
    import common

    return common.hbm_peak_gb(run)
