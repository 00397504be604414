"""Reduction of a profiler trace (`.xplane.pb`) to what the metrics read.

Read with `jax.profiler.ProfileData.from_file`, nothing else. A TPU's plane
is `/device:TPU:<n>`; its line `XLA Ops` holds one event per executed HLO
operation, named by the instruction's whole text (`%fusion.7 = bf16[..]
fusion(..)`); a scan's `while` is one event that spans its body's events.
`XLA Modules` has one event per executed program, `Async XLA Ops` the
copy-starts that overlap the rest, `Steps` the profiler's own grouping. The
events carry no category: a fusion that holds a convolution cannot be told
from one that does not unless XLA named it so (`convolution_add_fusion`).
Host threads are lines of `/host:CPU`; the benchmark's `TraceAnnotation`s are
on its line `python` under their names (`bench.fit_call`, `bench.listener`,
`bench.iterator_yield`). All planes share one clock, to within about half a
millisecond (seen in the recorded trace under tests/data): gaps shorter than
that may be given to a neighbouring span.

The steady stretch runs from the start of the first of the driver's marks to
the start of its last: whole periods, so the trace's edges (the profiler
starting and stopping) are left out. Everything is computed inside it:

  busy      union of the intervals in which an operation ran on a device
  idle      the stretch less busy; each gap, cut at the edges of the
            benchmark's host spans, goes piece by piece to the innermost span
            over it, or to `inside_program`
  ops       summed device time by operation name
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
BENCH_PREFIX = "bench."
# an idle gap shorter than this is a seam between two operations, not a gap
MIN_GAP_NS = 2_000

Interval = Tuple[int, int]
# operations that only hold others (a scan is a `while`): they count as busy
# time, and are left out of the sums by operation, which their bodies fill
CONTAINERS = ("while", "conditional", "call")


@functools.lru_cache(maxsize=None)
def parse_op(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an `XLA Ops` event, whose name is the HLO
    instruction's text: `%fusion.7 = bf16[8]{0} fusion(...)`."""
    if " = " not in text:
        return text.lstrip("%"), ""
    name, rest = text.split(" = ", 1)
    depth, i = 0, 0
    while i < len(rest):                       # skip the result's type
        c = rest[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    tail = rest[i:].lstrip()
    return name.lstrip("%"), tail.split("(", 1)[0].strip()


@functools.lru_cache(maxsize=None)
def op_label(text: str) -> str:
    """What the sums by operation are keyed by: `fusion.7`, and for a custom
    call its target too (`jvp__.2 custom-call tpu_custom_call`)."""
    name, opcode = parse_op(text)
    if opcode == "custom-call":
        target = ""
        if 'custom_call_target="' in text:
            target = " " + text.split('custom_call_target="', 1)[1].split('"', 1)[0]
        return f"{name} custom-call{target}"
    if opcode and not name.startswith(opcode) and opcode not in ("fusion",):
        return f"{name} {opcode}"
    return name


@dataclasses.dataclass
class Reduced:
    stretch_s: float
    periods: int                          # marks in the stretch, less one
    busy_s: float                         # averaged over the devices
    busy_by_device: Dict[str, float]
    ops_s: Dict[str, float]               # device 0, by operation name
    op_events: List[Tuple[str, int, int]]  # device 0: (name, start, dur) ns
    gaps_s: Dict[str, float]              # idle by host span, device 0
    n_gaps: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.stretch_s

    def top_ops(self, n: int = 10):
        return [[k, v] for k, v in sorted(self.ops_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10):
        return [[k, v] for k, v in sorted(self.gaps_s.items(),
                                          key=lambda kv: -kv[1])[:n]]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: List[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def gaps_of(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, at = [], lo
    for a, b in busy:
        if a - at >= MIN_GAP_NS:
            out.append((at, a))
        at = max(at, b)
    if hi - at >= MIN_GAP_NS:
        out.append((at, hi))
    return out


def read_planes(path: str):
    """({device plane: [(name, start_ns, dur_ns)] of its XLA Ops},
    [(name, start_ns, dur_ns)] of the benchmark's host spans)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) \
                and plane.name[len(DEVICE_PREFIX):].isdigit():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(BENCH_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
    return devices, sorted(spans, key=lambda s: s[1])


def attribute(gap: Interval, spans) -> str:
    """The innermost of the benchmark's host spans over the gap's middle."""
    mid = (gap[0] + gap[1]) // 2
    best, best_len = "inside_program", None
    for name, start, dur in spans:
        if start <= mid < start + dur and (best_len is None or dur < best_len):
            best, best_len = name, dur
    # time inside a `fit` call that no span of the benchmark's own covers
    # belongs to the program
    return "inside_program" if best == "bench.fit_call" else best


def pieces(gap: Interval, spans) -> List[Interval]:
    """The gap cut where one of the benchmark's host spans starts or ends, so
    that a gap that outlasts a span is shared out and not given whole."""
    cuts = {gap[0], gap[1]}
    for _, start, dur in spans:
        for edge in (start, start + dur):
            if gap[0] < edge < gap[1]:
                cuts.add(edge)
    cuts = sorted(cuts)
    return list(zip(cuts, cuts[1:]))


def reduce(devices, spans, marks: str) -> Optional[Reduced]:
    """None where the trace holds no device operation or fewer than two marks."""
    starts = [s for name, s, _ in spans if name == marks]
    if len(starts) < 2 or not devices:
        return None
    lo, hi = starts[0], starts[-1]
    if hi <= lo:
        return None
    busy_by_device, first = {}, None
    for plane in sorted(devices):
        ivals = [(s, s + d) for _, s, d in devices[plane]]
        merged = clip(union(ivals), lo, hi)
        busy_by_device[plane] = total(merged) / 1e9
        if first is None:
            first = (plane, merged)
    if not any(busy_by_device.values()):
        return None
    plane0, merged0 = first
    ops: Dict[str, float] = collections.defaultdict(float)
    events = []
    for text, s, d in devices[plane0]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a and parse_op(text)[1] not in CONTAINERS:
            label = op_label(text)
            ops[label] += (b - a) / 1e9
            events.append((label, a, b - a))
    gaps: Dict[str, float] = collections.defaultdict(float)
    all_gaps = gaps_of(merged0, lo, hi)
    for gap in all_gaps:
        for piece in pieces(gap, spans):
            gaps[attribute(piece, spans)] += (piece[1] - piece[0]) / 1e9
    return Reduced(
        stretch_s=(hi - lo) / 1e9, periods=len(starts) - 1,
        busy_s=sum(busy_by_device.values()) / len(busy_by_device),
        busy_by_device=busy_by_device, ops_s=dict(ops), op_events=events,
        gaps_s=dict(gaps), n_gaps=len(all_gaps))


def reduce_file(path: str, marks: str) -> Optional[Reduced]:
    devices, spans = read_planes(path)
    return reduce(devices, spans, marks)


def exposed(events, is_collective, is_compute, lo: int, hi: int) -> float:
    """Seconds in [lo, hi) in which a collective ran and no compute did."""
    coll = union([(s, s + d) for n, s, d in events if is_collective(n)])
    comp = union([(s, s + d) for n, s, d in events if is_compute(n)])
    starts = [a for a, _ in comp]
    alone = 0
    for a, b in clip(coll, lo, hi):
        covered = 0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(comp) and comp[i][0] < b:
            covered += max(0, min(b, comp[i][1]) - max(a, comp[i][0]))
            i += 1
        alone += (b - a) - covered
    return alone / 1e9
