"""XLA compile requests of the whole process between the window's opening
and its close (`xla_compile_counts()`): 0, or a compile was paid inside
a request's latency."""
LAYER = "model step"
UNIT = "count"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def compute(run):
    return run.counters.get("compiles_in_window")
