"""What the program itself wrote into the traced run's profile, and the table
from a device operation to the program's own name for it.

The program's `telemetry.span`s are `jax.profiler.TraceAnnotation`s: they are
on `/host:CPU`, one line a thread, on the device trace's clock, named
`dl4j.*`, with their arguments (`step`) as stats. `XLA Modules` of a device
plane has one event per executed program, named `jit_<function>(<id>)`; the
program names its train programs `jit_dl4j_*`. The device's `XLA Ops` events
carry the instruction's text and nothing of where it came from, but the
compiled program's own text has an `op_name` with the program's `dl4j.`
scopes for every instruction: `scopes()` builds a net through the cell's
adapter, lowers the window's program at the cell's shapes without running
it, compiles it (the persistent cache has it) and has
`telemetry.profiler.op_scopes` read the table. A fusion goes whole to the
scope of the one instruction whose metadata XLA kept for it.

Everything is computed over the stretch `harness/trace.py` defines (first to
last of the driver's marks), on device 0, and cached on `run`. This costs the
traced run only, after the window, once the program's own net is freed. A
program that has no such spans, scopes or counters (the parent of the PR that
added them) gives `None` everywhere and nothing raises.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

from harness import trace

PROGRAM_PREFIX = "dl4j."
MODULES_LINE = "XLA Modules"
TRAIN_PROGRAM = "jit_dl4j_"
COMPILE_COUNTERS = ("trace_s", "lower_s", "backend_s", "cache_load_s",
                    "programs", "cache_hits", "cache_misses")

Interval = Tuple[int, int]


@dataclasses.dataclass
class Span:
    thread: int               # index of its line of /host:CPU
    name: str
    start: int                # ns, clipped to the stretch
    end: int
    leaf: bool = True         # no other span of the program inside it


@dataclasses.dataclass
class ProgramTrace:
    lo: int                                   # the stretch, ns
    hi: int
    steps: int                                # training steps in it
    spans: List[Span]                         # the program's, every thread
    modules: List[Tuple[str, int, int]]       # device 0: (name, start, end)
    op_events: List[Tuple[str, int, int]]     # device 0: (instruction, start, end)
    busy: List[Interval]                      # device 0, merged

    @property
    def stretch_ns(self) -> int:
        return self.hi - self.lo

    @property
    def busy_ns(self) -> int:
        return trace.total(self.busy)

    def span_ns(self, name: str) -> int:
        return sum(s.end - s.start for s in self.spans if s.name == name)

    def threads_of(self, prefix: str) -> List[int]:
        return sorted({s.thread for s in self.spans
                       if s.name.startswith(prefix)})

    def train_modules(self) -> List[Interval]:
        return [(a, b) for name, a, b in self.modules
                if name.startswith(TRAIN_PROGRAM)]

    def other_programs_ns(self) -> int:
        """Busy device time inside programs that are not the train program."""
        others = trace.union([(a, b) for name, a, b in self.modules
                              if not name.startswith(TRAIN_PROGRAM)])
        return trace.total(intersect(self.busy, others))

    def idle_by_span(self) -> Dict[str, int]:
        """Idle ns of device 0 by the leaf span of the training thread that
        covers it, and `unattributed` for what none covers."""
        training = self.threads_of(PROGRAM_PREFIX + "fit")
        out: Dict[str, int] = collections.defaultdict(int)
        gaps = trace.gaps_of(self.busy, self.lo, self.hi)
        covered = 0
        for s in self.spans:
            if s.leaf and s.thread in training:
                ns = trace.total(intersect(gaps, [(s.start, s.end)]))
                if ns:
                    out[s.name] += ns
                    covered += ns
        out["unattributed"] = trace.total(gaps) - covered
        return dict(out)

    def train_program_ops(self):
        """The operations (instruction, start, end) inside the train program:
        another program's instruction names are its own and are not looked up."""
        inside = self.train_modules()
        starts = [a for a, _ in inside]
        for op in self.op_events:
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[1] < inside[i][1]:
                yield op

    def device_ns_by_scope(self, table: Dict[str, str], scope_phase
                           ) -> Dict[Tuple[Optional[str], str], int]:
        """Device ns of the train program's operations by (scope, phase);
        scope `None` is what the program named nothing for."""
        by_name: Dict[str, int] = collections.defaultdict(int)
        for name, a, b in self.train_program_ops():
            by_name[name] += b - a
        out: Dict[Tuple[Optional[str], str], int] = collections.defaultdict(int)
        for name, ns in by_name.items():
            out[scope_phase(table.get(name, ""))] += ns
        return dict(out)


def intersect(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    """The overlap of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def mark_leaves(spans: List[Span]) -> None:
    """A span is a leaf unless another span of its thread starts inside it."""
    by_thread: Dict[int, List[Span]] = collections.defaultdict(list)
    for s in spans:
        by_thread[s.thread].append(s)
    for line in by_thread.values():
        line.sort(key=lambda s: (s.start, -s.end))
        open_: List[Span] = []
        for s in line:
            while open_ and open_[-1].end <= s.start:
                open_.pop()
            if open_:
                open_[-1].leaf = False
            open_.append(s)


def read(path: str, marks: str, steps_per_mark: int) -> Optional[ProgramTrace]:
    """The program's part of a profile; None where it holds no TPU plane or
    fewer than two of the driver's marks."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    host, device = None, None
    for plane in data.planes:
        if plane.name == trace.HOST_PLANE:
            host = plane
        elif plane.name.startswith(trace.DEVICE_PREFIX) \
                and plane.name[len(trace.DEVICE_PREFIX):].isdigit() \
                and (device is None or plane.name < device.name):
            device = plane
    if host is None or device is None:
        return None
    raw, mark_starts = [], []
    for thread, line in enumerate(host.lines):
        for e in line.events:
            if e.name == marks:
                mark_starts.append(int(e.start_ns))
            elif e.name.startswith(PROGRAM_PREFIX):
                raw.append((thread, e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns)))
    if len(mark_starts) < 2:
        return None
    lo, hi = min(mark_starts), max(mark_starts)
    spans = [Span(t, n, max(a, lo), min(b, hi)) for t, n, a, b in raw
             if min(b, hi) > max(a, lo)]
    mark_leaves(spans)
    modules, ops = [], []
    for line in device.lines:
        if line.name == MODULES_LINE:
            modules = [(e.name, max(int(e.start_ns), lo),
                        min(int(e.start_ns + e.duration_ns), hi))
                       for e in line.events]
        elif line.name == trace.OPS_LINE:
            ops = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
    busy = trace.clip(trace.union([(a, b) for _, a, b in ops]), lo, hi)
    if not busy:
        return None
    op_events = [(trace.parse_op(text)[0], max(a, lo), min(b, hi))
                 for text, a, b in ops
                 if min(b, hi) > max(a, lo)
                 and trace.parse_op(text)[1] not in trace.CONTAINERS]
    return ProgramTrace(
        lo=lo, hi=hi, steps=(len(mark_starts) - 1) * steps_per_mark,
        spans=spans, modules=sorted((m for m in modules if m[2] > m[1]),
                                    key=lambda m: m[1]),
        op_events=op_events, busy=busy)


def compile_counters() -> Optional[Dict[str, float]]:
    """The program's `dl4j.compile.*` counters now; None where the program
    has none."""
    from deeplearning4j_tpu import telemetry
    registry = telemetry.registry()
    if registry.get("dl4j.compile.trace_s") is None:
        return None
    return {name: float(registry.counter("dl4j.compile." + name).value)
            for name in COMPILE_COUNTERS}


def counters(run) -> Optional[Dict[str, float]]:
    """The compile counters as they stood when a reader first asked for
    anything of this module, before `scopes` compiles."""
    if not hasattr(run, "_program_counters"):
        run._program_counters = compile_counters()
    return run._program_counters


def of(run) -> Optional[ProgramTrace]:
    counters(run)
    if not hasattr(run, "_program_trace"):
        path = run.tracer.trace_file() if run.reduced is not None else None
        run._program_trace = None if path is None else read(
            path, run.window.marks, run.window.steps_per_mark)
    return run._program_trace


def lower_window_program(cell):
    """The window's program of `cell`, lowered (not run) at the cell's shapes
    on a net built through the cell's adapter; None where the program cannot
    lower it from shapes."""
    import inspect

    import jax
    from harness import traffic
    key = traffic.key_from_seed(0)
    cfg, t = cell.config, cell.traffic
    net = cell.adapter.build(cfg, cell.reference.init_params(cfg, key), 0)
    x, y = jax.eval_shape(lambda k: traffic.make_batch(cfg, t, k), key)
    if "steps_per_call" in t:
        if "steps" not in inspect.signature(net.lower_train_step).parameters:
            return None
        return net.lower_train_step(x, y, steps=int(t["steps_per_call"]),
                                    vary_batch=bool(t.get("vary_batch", False)))
    if not hasattr(net, "lower_fit_batch"):
        return None
    return net.lower_fit_batch(x, y)


def scope_table(cell) -> Optional[Dict[str, str]]:
    """{instruction name: op_name} of the window's program."""
    from deeplearning4j_tpu.telemetry import profiler
    if not hasattr(profiler, "op_scopes"):
        return None
    lowered = lower_window_program(cell)
    return None if lowered is None else profiler.op_scopes(lowered.compile())


def scopes(run) -> Optional[Dict[str, str]]:
    counters(run)
    if not hasattr(run, "_op_scopes"):
        run._op_scopes = scope_table(run.cell) if of(run) is not None else None
    return run._op_scopes


def by_scope(run) -> Optional[Dict[Tuple[Optional[str], str], int]]:
    """Device ns by (scope, phase) over the stretch, cached on `run`."""
    if not hasattr(run, "_by_scope"):
        p, table = of(run), scopes(run)
        if p is None or table is None:
            run._by_scope = None
        else:
            from deeplearning4j_tpu.telemetry import profiler
            run._by_scope = p.device_ns_by_scope(table, profiler.scope_phase)
    return run._by_scope


def scope_ms_per_step(run, wanted) -> Optional[float]:
    """Device ms a step of the operations whose (scope, phase) `wanted` takes."""
    named = by_scope(run)
    if named is None:
        return None
    ns = sum(v for (scope, phase), v in named.items()
             if scope is not None and wanted(scope, phase))
    return ns / 1e6 / of(run).steps
