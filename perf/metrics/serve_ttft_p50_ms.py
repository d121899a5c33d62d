"""Median wait for a first token over the requests whose first token
fell in the window, from the clients' own clocks: the samples behind
`ttft_p95_ms`, for a cell whose window holds too few first tokens for
a 95th percentile to be steady (about 50 at a prompt token a tick) and
so does not report that end-to-end metric.  Here the wait is the
prompt's length in ticks: the scheduler feeds a prompt one token a
tick, in a slot that delivers nothing meanwhile."""
LAYER = "serving.generation scheduler"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def compute(run):
    import common

    v = run.samples.get("ttft_ms")
    return common.percentile(v, 50) if v else None
