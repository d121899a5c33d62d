"""Share of slot-ticks in the window that teacher-forced a prompt position
and so delivered no token (counted by the job around `_tick`: the cursor
is below prompt_len - 1).  Tokens a second = slot-ticks a second times
(1 - this share), so it is what chunked prefill moves."""
LAYER = "serving.generation scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def compute(run):
    if not run.counters.get("slot_ticks_all"):
        return None
    return 100.0 * run.counters["slot_ticks_prefill"] \
        / run.counters["slot_ticks_all"]
