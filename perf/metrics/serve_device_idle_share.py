"""1 - (union of device-op intervals) / (traced slice of the window)."""
LAYER = "device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def compute(run):
    import common

    return common.device_idle_share(run)
