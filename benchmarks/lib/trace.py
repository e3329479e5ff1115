"""From an ``.xplane.pb`` to numbers: the one reduction every PR uses.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip named
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per run of a
jitted program, named ``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per HLO
instruction run, named by the instruction's whole text, ``%name = shape
opcode(operands), attributes``).  ``while``/``conditional``/``call`` events span
their bodies, whose instructions are events of their own, so busy time is the
union of the LEAF events.  A Pallas kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``; its name comes from jax's name stack
(``jvp__``, ``transpose_jvp___``), not from the kernel function, so kernels
are told apart by operand shapes.  Host threads are lines of ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` of the benchmark shows there under its name,
on the same clock.

Start and duration are nanoseconds on one timeline for all planes.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"      # the benchmark's own annotation
CONTAINERS = ("while", "conditional", "call")
_OPCODE_RE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_NAME_RE = re.compile(r"^%?([^ ]+)")
_SHAPE_RE = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|pred|f64|s64)"
                       r"\[([0-9,]*)\]")

Event = Tuple[int, int, str]      # start_ns, end_ns, name


class DevicePlane:
    def __init__(self, name: str, ops: List[Event], modules: List[Event]):
        self.name, self.ops, self.modules = name, ops, modules


class Trace:
    """The parts of a profile the metrics read."""

    def __init__(self, devices: List[DevicePlane],
                 host_spans: Dict[str, List[Tuple[int, int]]]):
        self.devices = devices
        self.host_spans = host_spans

    @property
    def window(self) -> Optional[Tuple[int, int]]:
        """[start, end) of the benchmark's own ``bench_window`` annotation,
        or, without one, the extent of the device events."""
        spans = self.host_spans.get(WINDOW_SPAN)
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
        ev = [e for d in self.devices for e in d.ops + d.modules]
        if not ev:
            return None
        return min(e[0] for e in ev), max(e[1] for e in ev)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, host_span_names: Sequence[str] = (WINDOW_SPAN,)
         ) -> Trace:
    """Read an ``.xplane.pb`` (or the profiler's log directory)."""
    import jax
    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):            # a recorded fixture
        import gzip
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], collections.defaultdict(list)
    wanted = set(host_span_names)
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                            e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(int(e.start_ns),
                                int(e.start_ns + e.duration_ns), e.name)
                               for e in line.events]
            if ops or modules:
                devices.append(DevicePlane(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host[e.name].append(
                            (int(e.start_ns),
                             int(e.start_ns + e.duration_ns)))
    return Trace(devices, dict(host))


# -- names ---------------------------------------------------------------------

def opcode(name: str) -> str:
    """The HLO opcode in an op event's text (``fusion``, ``custom-call``,
    ``while``...); layout annotations such as ``T(8,128)`` are upper case and
    never match.  An event that is not instruction text is its own opcode."""
    if " = " not in name:
        return name
    m = _OPCODE_RE.search(name.split(" = ", 1)[1])
    return m.group(1) if m else name


def short_name(name: str) -> str:
    m = _NAME_RE.match(name)
    return m.group(1) if m else name


def is_leaf(name: str) -> bool:
    return opcode(name) not in CONTAINERS


def is_pallas(name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in name


def operand_shapes(name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of every array named in the operand list of an op's
    text, in order."""
    if " = " not in name:
        return []
    rest = name.split(" = ", 1)[1]
    m = _OPCODE_RE.search(rest)
    if not m:
        return []
    args = rest[m.end():]
    depth, end = 1, len(args)
    for i, ch in enumerate(args):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _SHAPE_RE.findall(args[:end])]


def module_name(name: str) -> str:
    """``jit_pstep(1234)`` -> ``jit_pstep``."""
    return name.split("(", 1)[0]


# -- intervals -----------------------------------------------------------------

def clip(events: Iterable[Event], window: Tuple[int, int]) -> List[Event]:
    lo, hi = window
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(plane: DevicePlane, window: Tuple[int, int]) -> int:
    """Nanoseconds of ``window`` in which a leaf operation ran on the chip."""
    leaves = clip((e for e in plane.ops if is_leaf(e[2])), window)
    return sum(e - s for s, e in union((s, e) for s, e, _ in leaves))


def busy_and_window_s(trace: Trace) -> Tuple[float, float]:
    """(busy seconds averaged over the chips, seconds of the window)."""
    win = trace.window
    if win is None or not trace.devices:
        return 0.0, 0.0
    busy = [busy_ns(d, win) for d in trace.devices]
    return sum(busy) / len(busy) / 1e9, (win[1] - win[0]) / 1e9


def op_seconds(plane: DevicePlane, window: Tuple[int, int],
               pick: Callable[[str], bool]) -> Tuple[float, int]:
    """(summed seconds, count) of the leaf events ``pick`` accepts."""
    ev = clip((e for e in plane.ops if is_leaf(e[2]) and pick(e[2])), window)
    return sum(e - s for s, e, _ in ev) / 1e9, len(ev)


def module_runs(plane: DevicePlane, window: Tuple[int, int],
                names: Sequence[str]) -> List[Tuple[int, int]]:
    """[start, end) of every run, wholly inside ``window``, of the programs
    called ``names`` (``jit_<fn>``)."""
    lo, hi = window
    return [(s, e) for s, e, n in plane.modules
            if module_name(n) in names and s >= lo and e <= hi]


def ops_inside(plane: DevicePlane, runs: Sequence[Tuple[int, int]]) -> float:
    """Seconds of leaf operations that ran inside ``runs`` (sorted, disjoint
    program runs): the device time of those programs without the launch gaps
    inside them."""
    if not runs:
        return 0.0
    runs = sorted(runs)
    starts = [s for s, _ in runs]
    total = 0
    for s, e, n in plane.ops:
        if not is_leaf(n):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s >= runs[i][0] and e <= runs[i][1]:
            total += e - s
    return total / 1e9


# -- breakdown -----------------------------------------------------------------

def top_device_ops(trace: Trace, limit: int = 10) -> List[List]:
    """The leaf operations that took most device time in the window, over all
    chips: ``[[name, seconds], ...]``.  A Pallas kernel is marked as one."""
    win = trace.window
    if win is None:
        return []
    total: Dict[str, int] = collections.defaultdict(int)
    for d in trace.devices:
        for s, e, n in clip((x for x in d.ops if is_leaf(x[2])), win):
            label = short_name(n)
            if is_pallas(n):  # one label for a kernel's every call site
                label = "pallas:" + re.sub(r"\.\d+$", "", label)
            total[label] += e - s
    top = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(trace: Trace, limit: int = 10) -> List[List]:
    """The idle time of the first chip inside the window, by where it fell:
    ``within <program>`` (a launch gap inside a running program) or
    ``host between <program> and <program>`` (no program on the chip: the
    host had not sent the next).  ``[[label, seconds], ...]``, largest
    first.  Finer labels need spans inside the program."""
    win = trace.window
    if win is None or not trace.devices:
        return []
    d = trace.devices[0]
    leaves = clip((e for e in d.ops if is_leaf(e[2])), win)
    merged = union((s, e) for s, e, _ in leaves)
    mods = sorted(clip(d.modules, win))
    gaps, prev = [], win[0]
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if win[1] > prev:
        gaps.append((prev, win[1]))
    total: Dict[str, int] = collections.defaultdict(int)
    starts = [m[0] for m in mods]
    for s, e in gaps:
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mods[i][1] > mid:
            label = "within " + module_name(mods[i][2])
        else:
            before = module_name(mods[i][2]) if i >= 0 else "window start"
            after = (module_name(mods[i + 1][2]) if i + 1 < len(mods)
                     else "window end")
            label = f"host between {before} and {after}"
        total[label] += e - s
    top = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / 1e9] for k, v in top]
