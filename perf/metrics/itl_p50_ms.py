"""Median of the samples whose 95th percentile is `itl_p95_ms`."""
LAYER = "model step"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"


def compute(run):
    import common

    v = run.samples.get("itl_ms")
    return common.percentile(v, 50) if v else None
