"""From a profiler trace to numbers: busy time, time under a named scope,
collectives with no compute beside them, the costliest operations, and the
longest idle gaps named by what the host was doing.

A trace is read with ``jax.profiler.ProfileData``.  Each device is a plane
named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
operation run, named by its HLO instruction, and its ``XLA Modules`` line
one per program run.  Host spans that the benchmark opens with
``jax.profiler.TraceAnnotation`` lie on the host plane, on the same clock.

The events carry no framework names, so the scope an operation belongs
to (``jax.named_scope``) comes from the compiled programs' HLO text: each
instruction's ``op_name``, and for a fusion the names of what it fuses.
An operation is matched to its program by the program run it lies in.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
from collections import defaultdict

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "allreduce", "allgather")
#: ops that only hold other ops (a loop, a call): they are not work
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    start: int          # ns
    end: int            # ns
    name: str           # the HLO instruction's name, or the span's name
    meta: str = ""      # its op_name(s) from the HLO text, once labelled
    module: str = ""    # the program run it lies in


@dataclasses.dataclass
class Device:
    name: str
    ops: list[Op]
    modules: list[Op]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    spans: list[Op]     # host spans opened by the benchmark
    window: tuple[int, int]   # ns, the traced window on the trace's clock

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def _events(line, window=None):
    out = []
    for e in line.events:
        start = int(e.start_ns)
        end = start + int(e.duration_ns)
        if window is not None and (end <= window[0] or start >= window[1]):
            continue
        name = e.name
        if name.startswith("%"):             # "%fusion.3 = bf16[...] fusion(..."
            name = name[1:].split(" ", 1)[0]
        out.append(Op(start, end, name))
    return out


def _assign_modules(ops: list[Op], modules: list[Op]) -> None:
    """Set each op's ``module`` to the program run that holds its start."""
    mods = sorted(modules, key=lambda m: m.start)
    j = 0
    for op in sorted(ops, key=lambda o: o.start):
        while j < len(mods) and mods[j].end < op.start:
            j += 1
        if j < len(mods) and mods[j].start <= op.start:
            op.module = mods[j].name


def read(profile, window_span: str) -> Trace:
    """Reduce a ``ProfileData`` to the device ops inside the host span
    ``window_span`` (the benchmark opens it around the traced window)."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend(_events(line))
    wins = [s for s in spans if s.name == window_span]
    if not wins:
        raise ValueError(f"trace holds no host span {window_span!r}")
    window = (wins[0].start, wins[0].end)
    devices = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = _events(line, window)
            elif line.name == "XLA Modules":
                modules = _events(line, window)
        if ops or modules:
            _assign_modules(ops, modules)
            devices.append(Device(plane.name, ops, modules))
    devices.sort(key=lambda d: _ordinal(d.name))
    spans = [s for s in spans if s.end > window[0] and s.start < window[1]]
    return Trace(devices, spans, window)


def _ordinal(name: str) -> int:
    tail = name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else 0


def load(trace_dir: str, window_span: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return read(ProfileData.from_file(paths[-1]), window_span)


@contextlib.contextmanager
def capture(trace_dir: str, window_span: str, enabled: bool):
    """Trace the body when ``enabled``; the body is one host span either way."""
    import jax
    if not enabled:
        with jax.profiler.TraceAnnotation(window_span):
            yield
        return
    shutil.rmtree(trace_dir, ignore_errors=True)
    # host spans without the Python tracer: it would record every Python
    # call, slow the host, and name the idle gaps by interpreter frames
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(window_span):
            yield
    finally:
        jax.profiler.stop_trace()


# -- scopes from HLO text ---------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPNAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_names(text: str) -> dict[str, str]:
    """``{instruction: op names}`` of a compiled program's HLO text; a
    fusion's entry holds the op names of every instruction it fuses."""
    comps: dict[str, list[str]] = defaultdict(list)
    own: dict[str, str] = {}
    calls: dict[str, list[str]] = {}
    comp = ""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            name = m.group(1)
            comps[comp].append(name)
            op = _OPNAME.search(line)
            own[name] = op.group(1) if op else ""
            if " fusion(" in line:
                calls[name] = _CALLS.findall(line)
            continue
        m = _COMP.match(line)
        if m:
            comp = m.group(1)

    def names(instr: str, depth: int = 0) -> str:
        parts = [own.get(instr, "")]
        if depth < 4:
            for c in calls.get(instr, ()):
                parts.extend(names(i, depth + 1) for i in comps.get(c, ()))
        return "|".join(p for p in parts if p)

    return {i: names(i) for i in own}


def label(trace: "Trace", programs: list[str]) -> None:
    """Give every op the op names of its instruction, from the HLO text of
    the program whose instructions best cover its module's ops."""
    tables = [hlo_op_names(t) for t in programs]
    for dev in trace.devices:
        by_module: dict[str, list[Op]] = defaultdict(list)
        for op in dev.ops:
            by_module[op.module].append(op)
        for ops in by_module.values():
            seen = {o.name for o in ops}
            best = max(tables, key=lambda t: len(seen & t.keys()), default={})
            for o in ops:
                o.meta = best.get(o.name, "")


# -- interval arithmetic ------------------------------------------------------


def union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, window) -> list[tuple[int, int]]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the (merged) intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- reductions -------------------------------------------------------------


def is_container(op: Op) -> bool:
    return op.name.split(".")[0].split("-")[0] in CONTAINERS


def is_collective(op: Op) -> bool:
    low = op.name.lower()
    return any(c in low for c in COLLECTIVES)


def work_ops(dev: Device) -> list[Op]:
    return [o for o in dev.ops if not is_container(o)]


def busy(dev: Device, window) -> list[tuple[int, int]]:
    return clip(union((o.start, o.end) for o in work_ops(dev)), window)


def busy_s(trace: Trace) -> float:
    """Seconds some operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return sum(length(busy(d, trace.window)) for d in trace.devices) / (
        1e9 * len(trace.devices))


def scope_s(dev: Device, scope: str, window) -> float:
    """Seconds in which an operation under the named scope ran."""
    ops = [(o.start, o.end) for o in work_ops(dev) if scope in o.meta]
    return length(clip(union(ops), window)) / 1e9


def collective_exposed_s(dev: Device, window) -> float:
    """Seconds a collective ran with no other operation beside it."""
    ops = work_ops(dev)
    coll = clip(union((o.start, o.end) for o in ops if is_collective(o)), window)
    comp = clip(union((o.start, o.end) for o in ops if not is_collective(o)),
                window)
    return length(subtract(coll, comp)) / 1e9


def module_runs(dev: Device) -> dict[str, list[float]]:
    """Durations (s) of each program's runs, by program name (the trace
    names a program by its jit name and a hash of it)."""
    runs: dict[str, list[float]] = defaultdict(list)
    for m in dev.modules:
        runs[m.name].append((m.end - m.start) / 1e9)
    return runs


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The operations that took most device time, averaged over devices,
    grouped by the framework name of the op where the trace gives one."""
    total: dict[str, float] = defaultdict(float)
    for dev in trace.devices:
        for o in work_ops(dev):
            total[_label(o)] += (min(o.end, trace.window[1])
                                 - max(o.start, trace.window[0])) / 1e9
    k = max(1, len(trace.devices))
    return [[name, t / k] for name, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _label(op: Op) -> str:
    """The op's first op name (its framework path), else its instruction."""
    return (op.meta.split("|", 1)[0] or op.name)[:160]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The longest gaps with no operation on device 0, each named by the
    innermost host span that covers half of it or more (else the span that
    covers most of it)."""
    if not trace.devices:
        return []
    spans = [s for s in trace.spans
             if (s.end - s.start) < (trace.window[1] - trace.window[0])]
    idle = subtract([trace.window], busy(trace.devices[0], trace.window))
    idle.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in idle[:n]:
        cover = {id(sp): min(e, sp.end) - max(s, sp.start) for sp in spans}
        half = [sp for sp in spans if 2 * cover[id(sp)] >= e - s]
        if half:
            best = min(half, key=lambda sp: sp.end - sp.start).name
        else:
            most = max(spans, key=lambda sp: cover[id(sp)], default=None)
            best = (most.name if most is not None and cover[id(most)] > 0
                    else "no host span")
        out.append([best, (e - s) / 1e9])
    return out


def idle_share(trace: Trace | None):
    """Per cent of the window with no operation on the device, averaged
    over the devices; None without a trace."""
    if trace is None or not trace.devices:
        return None
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


@contextlib.contextmanager
def span(name: str):
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield
