"""Mean duration of the program's `serving.decode_tick` spans inside the
window: one resident decode step and the host sync on its tokens."""
LAYER = "serving.generation scheduler"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    durs = [s["dur"] for s in run.spans if s["name"] == "serving.decode_tick"]
    return 1e3 * sum(durs) / len(durs) if durs else None
