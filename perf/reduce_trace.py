"""From a profiler trace (.xplane.pb) to numbers: device busy and idle
time, time per device operation, and idle gaps attributed to what the
host was doing.  Every PR computes these the same way from here; the
recorded trace under perf/testdata/ and perf/selfcheck.py pin it.

What a v5e trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` is the core's serial timeline
(one event per executed HLO op, fusions included) and whose line
`XLA Modules` has one event per executed program with a `run_id`; and
the plane `/host:CPU`, whose thread lines hold the runtime's events
(`CompleteCallbacks` with the same `run_id`) and every
`jax.profiler.TraceAnnotation` of the process.  All times are
nanoseconds on the trace's own clock, but the device's clock runs a
millisecond or two behind the host's, so `Trace.shift_ns`
estimates the lag from the runs both sides saw.

Only jax is needed to read the file (`jax.profiler.ProfileData`), and
nothing here touches a device.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-done)?\b")
# a gap shorter than this lies between two ops of one program: the
# core's own scheduling, not something the host could have filled
INTER_OP_GAP_NS = 2_000

Interval = Tuple[float, float]


def op_name(event_name: str) -> str:
    """`%fusion.3 = bf16[...] fusion(...)` -> `fusion.3`."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The complement of a merged, clipped busy list inside [lo, hi]."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


class Trace:
    """One parsed capture.  `device_ops[chip]` is a list of
    (start_ns, end_ns, op name), `host_spans` a list of
    (start_ns, end_ns, name) of every annotation and runtime event on
    the host plane, both on the trace's clock."""

    def __init__(self, device_ops: Dict[int, list], modules: Dict[int, list],
                 host_spans: list, completions: Dict[str, float]):
        self.device_ops = device_ops
        self.modules = modules
        self.host_spans = host_spans
        self.completions = completions
        self.shift_ns = self._device_clock_shift_ns()

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        import jax

        pd = jax.profiler.ProfileData.from_file(path)
        device_ops: Dict[int, list] = {}
        modules: Dict[int, list] = {}
        host_spans: list = []
        completions: Dict[str, float] = {}
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                chip = int(m.group(1))
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        device_ops[chip] = [
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             op_name(ev.name))
                            for ev in line.events if ev.duration_ns > 0]
                    elif line.name == MODULES_LINE:
                        modules[chip] = [
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             str(dict(ev.stats).get("run_id", "")))
                            for ev in line.events]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name == "CompleteCallbacks":
                            rid = str(dict(ev.stats).get("run_id", ""))
                            completions.setdefault(rid, ev.start_ns)
                        if ev.duration_ns > 0:
                            host_spans.append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
        return cls(device_ops, modules, host_spans, completions)

    # -- clocks ------------------------------------------------------------
    def _device_clock_shift_ns(self) -> float:
        """How far the device's clock lags the host's: the smallest
        distance, over the programs both sides recorded, from a
        program's end on the device to the host's completion callback
        for the same run.  The callback cannot come before the end, so
        the smallest distance is the lag plus the quickest callback;
        0 where no run is matched."""
        lags = [self.completions[rid] - end
                for runs in self.modules.values()
                for _, end, rid in runs if rid in self.completions]
        return max(min(lags), 0.0) if lags else 0.0

    def annotation(self, name: str) -> Optional[Interval]:
        """The first host span called `name` (the harness's window
        marker), as (start_ns, end_ns)."""
        hits = [(a, b) for a, b, n in self.host_spans if n == name]
        return min(hits) if hits else None

    # -- device ------------------------------------------------------------
    def chips(self) -> List[int]:
        return sorted(self.device_ops)

    def window(self, marker: Optional[str] = None) -> Interval:
        """The traced window on the host's clock: the span of the
        marker annotation where one is named and found, else from the
        first to the last device operation."""
        if marker:
            hit = self.annotation(marker)
            if hit:
                return hit
        shift = self.shift_ns
        starts = [ops[0][0] for ops in self.device_ops.values() if ops]
        ends = [max(e for _, e, _ in ops)
                for ops in self.device_ops.values() if ops]
        if not starts:
            raise ValueError("the trace holds no device operation")
        return (min(starts) + shift, max(ends) + shift)

    def busy(self, chip: int, window: Interval) -> List[Interval]:
        """Merged intervals, on the host's clock and inside `window`,
        in which an operation ran on `chip`."""
        shift = self.shift_ns
        return clip(union((a + shift, b + shift)
                          for a, b, _ in self.device_ops[chip]),
                    *window)

    def busy_seconds(self, window: Interval) -> float:
        """Device busy time inside the window, averaged over chips."""
        chips = self.chips()
        if not chips:
            return 0.0
        return sum(total(self.busy(c, window)) for c in chips) \
            / len(chips) / 1e9

    def op_seconds(self, window: Interval) -> Dict[str, float]:
        """Seconds per op name inside the window, averaged over chips."""
        shift = self.shift_ns
        out: Dict[str, float] = {}
        for chip in self.chips():
            for a, b, name in self.device_ops[chip]:
                a, b = max(a + shift, window[0]), min(b + shift, window[1])
                if b > a:
                    out[name] = out.get(name, 0.0) + (b - a)
        n = max(len(self.chips()), 1)
        return {k: v / n / 1e9 for k, v in out.items()}

    def collective_seconds(self, window: Interval) -> float:
        """Seconds, averaged over chips, that the core's serial
        timeline spent inside collective operations: a synchronous
        collective, or the `-done` half of an asynchronous one, is time
        in which the core computes nothing, i.e. communication that is
        exposed.  (`-start` halves only launch and are not counted.)"""
        return sum(s for name, s in self.op_seconds(window).items()
                   if COLLECTIVE.search(name)
                   and not name.split(".")[0].endswith("-start"))

    # -- idle gaps ---------------------------------------------------------
    def idle_gaps(self, window: Interval, spans: Sequence[Tuple[float,
                  float, str]]) -> Dict[str, float]:
        """Idle seconds of the device inside the window (chip average),
        by what the host was doing: each gap is divided among the given
        host spans (start_ns, end_ns, name on the trace's clock), the
        innermost (shortest) span winning where they nest; what no span
        covers is `host_other`, and gaps too short for the host to
        matter are `device_inter_op`."""
        out: Dict[str, float] = {}
        chips = self.chips()
        by_len = sorted(spans, key=lambda s: s[1] - s[0])
        for chip in chips:
            for g0, g1 in gaps(self.busy(chip, window), *window):
                if g1 - g0 < INTER_OP_GAP_NS:
                    out["device_inter_op"] = \
                        out.get("device_inter_op", 0.0) + (g1 - g0)
                    continue
                free = [(g0, g1)]
                for s0, s1, name in by_len:
                    if s1 <= g0 or s0 >= g1 or not free:
                        continue
                    rest = []
                    for a, b in free:
                        lo, hi = max(a, s0), min(b, s1)
                        if hi > lo:
                            out[name] = out.get(name, 0.0) + (hi - lo)
                            if lo > a:
                                rest.append((a, lo))
                            if b > hi:
                                rest.append((hi, b))
                        else:
                            rest.append((a, b))
                    free = rest
                left = total(free)
                if left:
                    out["host_other"] = out.get("host_other", 0.0) + left
        n = max(len(chips), 1)
        return {k: v / n / 1e9 for k, v in out.items()}


def top(table: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce(source, marker: Optional[str] = None,
           spans: Optional[Sequence[Tuple[float, float, str]]] = None
           ) -> dict:
    """The whole reduction of one capture: window and busy seconds, the
    per-op table, exposed collective seconds, and the idle gaps by
    host span.  With `spans` None the gaps go to the annotations the
    trace itself holds (dotted names, the marker left out).  `source`
    is a path or a parsed `Trace`."""
    tr = source if isinstance(source, Trace) else Trace.from_file(source)
    window = tr.window(marker)
    if spans is None:
        spans = [s for s in tr.host_spans if "." in s[2] and s[2] != marker
                 and not s[2].startswith("$") and "::" not in s[2]]
    ops = tr.op_seconds(window)
    idle = tr.idle_gaps(window, spans)
    return {
        "chips": len(tr.chips()),
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": tr.busy_seconds(window),
        "device_clock_shift_ms": tr.shift_ns / 1e6,
        "op_seconds": ops,
        "collective_s": tr.collective_seconds(window),
        "idle_by_span": idle,
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(idle)},
    }
