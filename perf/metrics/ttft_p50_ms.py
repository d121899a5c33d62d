"""Median of the samples whose 95th percentile is `ttft_p95_ms`."""
LAYER = "model step"
UNIT = "ms"
MOVES = "ttft_p95_ms"
SOURCE = "host_clock"


def compute(run):
    import common

    v = run.samples.get("ttft_ms")
    return common.percentile(v, 50) if v else None
