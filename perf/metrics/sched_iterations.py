"""Not a metric: the walk the nine `sched_*` readers of the scheduler's
iteration share (`sched_host_busy_share`, `sched_admit_ms`,
`sched_build_ms`, `sched_dispatch_ms`, `sched_deliver_ms`,
`sched_lock_wait_ms`, `sched_host_unattributed_share`,
`sched_iteration_max_ms`, `sched_iteration_max_host_ms`).

An iteration of `GenerationServer._loop` runs from the end of one
blocking read to the end of the next: from the end of one
`generation.phase.sample` span to the end of the next.  Inside it the
program puts every piece of host work under one phase span, in the order
`deliver`, `admit`, `build`, `decode` or `prefill`, `sample`
(docs/observability.md).  `iterations(run)` cuts `run.spans` (the tap's
own list of `{name, ts, dur}`, in the order the spans ended, never
dropped) at the ends of the `sample` spans and gives, an iteration, its
period and the seconds under each phase.

Left out: what lies before the window's first read (no period), an
iteration with a speculative tick in it (`draft_verify`: the serial
loop), and one in which the loop went round more than once (an idle
poll or a flush: two `admit`s).  Nothing at all where the program has no
`generation.phase.build` span: its phases do not tile the iteration, and
a duration of one of them would be of something else.
"""
PHASE = "generation.phase."
HOST = ("deliver", "admit", "build", "dispatch")
# the dispatch phase carries one of two names (attribution of the tick)
PART = {"deliver": "deliver", "admit": "admit", "build": "build",
        "decode": "dispatch", "prefill": "dispatch", "sample": "sample"}


def iterations(run):
    """-> [{period, sample, deliver, admit, build, dispatch}] in
    seconds, one an iteration whose end lies in the window."""
    spans = run.spans or []
    if not any(s["name"] == PHASE + "build" for s in spans):
        return []
    out = []
    last_end = acc = None
    for s in spans:
        name = s["name"]
        if not name.startswith(PHASE):
            continue
        name = name[len(PHASE):]
        if acc is None:
            acc = dict.fromkeys(PART.values(), 0.0)
            admits, speculative = 0, False
        if name == "draft_verify":
            speculative = True
        part = PART.get(name)
        if part is None:
            continue        # kv_alloc, kv_release: inside admit, deliver
        acc[part] += s["dur"]
        admits += name == "admit"
        if name != "sample":
            continue
        end = s["ts"] + s["dur"]
        if last_end is not None and admits == 1 and not speculative:
            out.append(dict(acc, period=end - last_end))
        last_end, acc = end, None
    return out


def mean_ms(run, part):
    """Mean seconds an iteration under one host phase, in ms."""
    its = iterations(run)
    return 1e3 * sum(i[part] for i in its) / len(its) if its else None


def host_busy_share(run):
    """100 x (1 - seconds under `sample` over the sum of the periods)."""
    its = iterations(run)
    period = sum(i["period"] for i in its)
    return (100.0 * (1.0 - sum(i["sample"] for i in its) / period)
            if period else None)


def unattributed_share(run):
    """100 x the host's own seconds (period less `sample`) under none of
    the four host phases, over the host's own seconds."""
    its = iterations(run)
    busy = sum(i["period"] - i["sample"] for i in its)
    named = sum(i[p] for i in its for p in HOST)
    return 100.0 * (busy - named) / busy if busy > 0 else None


def longest_ms(run, host_only=False):
    """The longest period in ms, or (`host_only`) that iteration less
    its `sample`."""
    its = iterations(run)
    if not its:
        return None
    it = max(its, key=lambda i: i["period"])
    return 1e3 * (it["period"] - (it["sample"] if host_only else 0.0))
