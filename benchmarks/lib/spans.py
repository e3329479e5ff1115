"""The program's own spans and scopes, read out of a run's ``.xplane.pb``.

The program (``distkeras_tpu/metrics.py`` lists the names) marks its host
phases with ``jax.profiler.TraceAnnotation``s (``serve.*``, ``train.*``) and
its device phases with ``jax.named_scope``s.  Both land in the profiler's own
trace: a host span is an event of a ``/host:CPU`` line (one line per thread)
with its fields as the event's stats; a scope is part of the HLO ``op_name``,
which a TPU plane holds as the stat ``tf_op`` of each operation's event
METADATA (PERF.md section 6, PR 24, says what was looked at).
``lib/trace.load`` keeps start, end and name of the events it is asked for and
no stats, so this file reads the trace again: through
``jax.profiler.ProfileData`` for every event and its own stats, and, because
``ProfileData`` hands out no metadata stats, through a few lines of protobuf
wire format (``op_names``) for that one.  An operation the compiler made
itself (a convert or a copy behind one of the program's operations) has no
op_name; it counts under that of the operation whose result it reads
(``inherit``).

A metric's ``read(records, trace, env)`` is handed neither the trace's path
nor the cell's name, so ``of_run(trace)`` takes the newest ``.xplane.pb``
under ``benchmarks/out/trace-*/`` and holds it to the ``Trace`` it was given:
the ``bench_window`` annotation of the file has to be ``trace.window``.  It is
read once a process, and that first reading prints two earlier lines,
``idle_by_phase`` and ``compiles_in_trace``.  Against a program without spans
(the parent of the PR that added them) everything here finds nothing and
returns nothing.
"""

from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from . import trace as T

PROGRAM_PREFIXES = ("serve.", "train.")
COMPILE_SPAN = "backend_compile_and_load"
MAIN_SPANS = ("serve.iteration", "train.epoch")   # mark the program's thread
# the stat of an operation's event metadata that holds the HLO op_name: jax's
# name stack with the program's scopes in it
# (``jit(epoch)/.../block_3/attn/attn_core/dot_general:``)
OP_NAME_STAT = "tf_op"
UNATTRIBUTED = "unattributed"
# path elements of an op_name; a scope under a transformation is wrapped
# (``transpose(jvp(lm_head))``), so elements are cut at brackets too
_SCOPE_RE = re.compile(r"[\w.\-]+")
# every program the scope readers measure runs the model's blocks, so an
# op_name that holds this element says the executable was compiled WITH the
# program's scopes (jax's own name stack, ``jit(pstep)/...``, is there always)
_BLOCK_RE = re.compile(r"(?:^|[/(])block_\d+(?:$|[/)])")
# the instructions an instruction's text reads: ``convert(bf16[..] %fusion.31)``
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

Interval = Tuple[int, int]


class Span(NamedTuple):
    start: int          # ns, the trace's one timeline
    end: int
    name: str
    thread: int         # index of the host line the span was recorded on
    fields: Dict[str, object]

    @property
    def ms(self) -> float:
        return (self.end - self.start) / 1e6


class Op(NamedTuple):
    start: int
    end: int
    name: str           # the HLO instruction's text, as ``lib/trace`` has it
    op_name: str        # the HLO op_name: scopes separated by ``/``


class Spans:
    """The host spans of one trace, by name and by thread, and the leaf
    operations of the first chip with their scopes."""

    def __init__(self, spans: Sequence[Span], ops: Sequence[Op] = ()):
        # start order, a parent before the children that start with it
        self.all = sorted(spans, key=lambda s: (s.start, -s.end))
        self.ops = list(ops)
        self._by_name: Dict[str, List[Span]] = collections.defaultdict(list)
        self._by_thread: Dict[int, List[Span]] = collections.defaultdict(list)
        for s in self.all:
            self._by_name[s.name].append(s)
            self._by_thread[s.thread].append(s)
        self._starts = {t: [s.start for s in mine]
                        for t, mine in self._by_thread.items()}

    # -- spans ---------------------------------------------------------------
    def named(self, name: str, window: Optional[Interval] = None
              ) -> List[Span]:
        """Spans called ``name`` in start order; with ``window``, those that
        lie wholly inside it."""
        found = self._by_name.get(name, [])
        if window is None:
            return list(found)
        lo, hi = window
        return [s for s in found if s.start >= lo and s.end <= hi]

    @property
    def window(self) -> Optional[Interval]:
        marks = self._by_name.get(T.WINDOW_SPAN)
        if not marks:
            return None
        return min(s.start for s in marks), max(s.end for s in marks)

    def children(self, parent: Span, name: Optional[str] = None
                 ) -> List[Span]:
        """Spans of ``parent``'s thread that lie inside it (at any depth),
        all of them or those called ``name``."""
        mine = self._by_thread[parent.thread]
        i = bisect.bisect_left(self._starts[parent.thread], parent.start)
        out = []
        for s in mine[i:]:
            if s.start >= parent.end:
                break
            if s is not parent and s.end <= parent.end \
                    and (name is None or s.name == name):
                out.append(s)
        return out

    def self_ns(self, span: Span) -> int:
        """The span's time less what the spans inside it cover."""
        inside = T.union((s.start, s.end) for s in self.children(span))
        return (span.end - span.start) - sum(e - s for s, e in inside)

    def main_threads(self) -> List[int]:
        """The threads the program's loop runs on: those that hold a
        ``serve.iteration`` or a ``train.epoch``."""
        return sorted({s.thread for n in MAIN_SPANS
                       for s in self._by_name.get(n, [])})

    def innermost(self, threads: Sequence[int]) -> List[Tuple[int, int, str]]:
        """The program spans of ``threads`` flattened to disjoint segments
        ``(start, end, name of the innermost span open there)``, sorted."""
        segments: List[Tuple[int, int, str]] = []
        for t in threads:
            # in start order; spans of one thread nest and never cross
            stack: List[Span] = []
            cursor = 0
            for s in self._by_thread[t] + [None]:
                if s is not None and not s.name.startswith(PROGRAM_PREFIXES):
                    continue
                while stack and (s is None or stack[-1].end <= s.start):
                    top = stack.pop()
                    if top.end > cursor:
                        segments.append((cursor, top.end, top.name))
                        cursor = top.end
                if s is None:
                    break
                if stack and s.start > cursor:
                    segments.append((cursor, s.start, stack[-1].name))
                stack.append(s)
                cursor = s.start
        return sorted(segments)

    # -- idle time and compiles ----------------------------------------------
    def idle_by_phase(self, trace: T.Trace) -> Dict[str, float]:
        """Seconds of the first chip's idle time inside the window (the gaps
        of the union of its leaf operations, as ``trace.busy_ns`` has it),
        each gap shared out by overlap among the innermost program spans
        open during it on the program's thread; what no span covers goes to
        a compile open then on any thread, else to ``unattributed``.  (By
        overlap and not by the gap's midpoint: an epoch boundary of 5 ms
        crosses five phases.)"""
        win = trace.window
        if win is None or not trace.devices:
            return {}
        plane = trace.devices[0]
        busy = T.union((s, e) for s, e, _ in T.clip(
            (x for x in plane.ops if T.is_leaf(x[2])), win))
        gaps, prev = [], win[0]
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if win[1] > prev:
            gaps.append((prev, win[1]))
        segments = self.innermost(self.main_threads())
        compiles = [(s, e, COMPILE_SPAN) for s, e in T.union(
            (c.start, c.end) for c in self._by_name.get(COMPILE_SPAN, []))]
        total: Dict[str, int] = collections.defaultdict(int)
        # all gaps in one pass a cover: a busy chip has a gap after every
        # operation, and a pass a gap was quadratic in the span's length
        left = gaps
        for cover in (segments, compiles):
            left = _share_out(left, cover, total)
        total[UNATTRIBUTED] += sum(e - s for s, e in left)
        return {k: v / 1e9 for k, v in total.items() if v}

    def compiles(self, window: Interval) -> List[Tuple[Span, str]]:
        """Every compile that began inside ``window``, with the innermost
        program span of its own thread that it fell in (or
        ``unattributed``)."""
        out = []
        flat: Dict[int, Tuple[list, list]] = {}    # thread -> its segments
        for c in self._by_name.get(COMPILE_SPAN, []):
            if not window[0] <= c.start < window[1]:
                continue
            if c.thread not in flat:
                segments = self.innermost([c.thread])
                flat[c.thread] = (segments, [s for s, _, _ in segments])
            segments, starts = flat[c.thread]
            i = bisect.bisect_right(starts, c.start) - 1
            inside = (segments[i][2] if i >= 0 and segments[i][1] > c.start
                      else UNATTRIBUTED)
            out.append((c, inside))
        return out

    # -- device time by scope --------------------------------------------------
    def scope_seconds(self, runs: Sequence[Interval],
                      scopes: Sequence[str]) -> Optional[Tuple[float, float]]:
        """(seconds of leaf operations whose op_name holds one of ``scopes``
        as a whole path element, bare or wrapped by a transformation
        (``jvp(loss)``), seconds of all leaf operations) inside
        ``runs`` (sorted, disjoint program runs) on the first chip; nothing
        if no operation there names a block of the model, which is how an
        executable compiled without the program's scopes reads."""
        if not runs or not self.ops:
            return None
        runs = sorted(runs)
        starts = [s for s, _ in runs]
        wanted = set(scopes)
        under = total = 0
        scoped = False
        for op in self.ops:
            i = bisect.bisect_right(starts, op.start) - 1
            if i < 0 or op.end > runs[i][1] or not T.is_leaf(op.name):
                continue
            total += op.end - op.start
            scoped = scoped or bool(_BLOCK_RE.search(op.op_name))
            if wanted.intersection(_SCOPE_RE.findall(op.op_name)):
                under += op.end - op.start
        if not scoped or not total:
            return None
        return under / 1e9, total / 1e9


def scope_share_pct(trace: Optional[T.Trace], programs: Sequence[str],
                    scopes: Sequence[str]) -> Optional[float]:
    """What the scope readers report: of the device time of the leaf
    operations inside the runs of ``programs`` in the traced window (first
    chip), the per cent under ``scopes``; nothing without a TPU trace, a run
    of the programs, the program's spans or an operation that names a block
    of the model.  The names are those of the program that COMPILED the
    executable: jax's persistent cache leaves metadata out of its key, so a
    program whose arithmetic a scope-less one compiled first runs that one's
    executable and reads nothing here (PERF.md section 6, PR 24)."""
    if trace is None or not trace.devices:
        return None
    spans = of_run(trace)
    if spans is None:
        return None
    runs = T.module_runs(trace.devices[0], trace.window, programs)
    found = spans.scope_seconds(runs, scopes)
    return None if found is None else 100.0 * found[0] / found[1]


def _share_out(pieces: Sequence[Interval],
               cover: Sequence[Tuple[int, int, str]],
               total: Dict[str, int]) -> List[Interval]:
    """Adds to ``total[name]`` what each of ``cover`` (sorted, disjoint
    ``(start, end, name)``) overlaps of ``pieces``; returns what is left of
    the pieces."""
    starts = [s for s, _, _ in cover]
    left = []
    for s, e in pieces:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        cursor = s
        while i < len(cover) and cover[i][0] < e:
            cs, ce, name = cover[i]
            lo, hi = max(cs, cursor), min(ce, e)
            if hi > lo:
                if lo > cursor:
                    left.append((cursor, lo))
                total[name] += hi - lo
                cursor = hi
            i += 1
        if e > cursor:
            left.append((cursor, e))
    return left


# -- loading -------------------------------------------------------------------

def read(path: str) -> Spans:
    """The spans and scoped operations of an ``.xplane.pb`` (or ``.gz``)."""
    import jax
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    spans: List[Span] = []
    ops: List[Op] = []
    thread = 0
    seen_device = False
    names = None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES) \
                            or e.name in (T.WINDOW_SPAN, COMPILE_SPAN):
                        spans.append(Span(
                            int(e.start_ns), int(e.start_ns + e.duration_ns),
                            e.name, thread, dict(e.stats)))
                thread += 1
        elif (plane.name.startswith("/device:") and "TPU" in plane.name
              and not seen_device):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                seen_device = True
                if names is None:
                    names = inherit(op_names(path, plane.name), list(
                        dict.fromkeys(e.name for e in line.events)))
                for e in line.events:
                    ops.append(Op(int(e.start_ns),
                                  int(e.start_ns + e.duration_ns), e.name,
                                  names.get(e.name, "")))
    return Spans(spans, ops)


# -- the one stat ProfileData does not hand out ---------------------------------
# xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
# .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
# .ref_value = 7 (the id of a stat metadata whose name is the value).

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of every field of one protobuf message: an int
    for a varint, a view of the bytes for anything with a length."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {kind}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _entry(buf):
    """The value message of one map entry."""
    return next(v for k, v in _fields(buf) if k == 2)


def op_names(path: str, plane_name: str) -> Dict[str, str]:
    """Event name (the HLO instruction's text) -> HLO op_name, for the
    operations of the plane called ``plane_name``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    for key, plane in _fields(space):
        if key != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and bytes(v).decode() == plane_name
                   for k, v in fields):
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                meta = dict(_fields(_entry(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
        out = {}
        for k, v in fields:
            if k != 4:
                continue
            name, op_name = "", ""
            for fk, fv in _fields(_entry(v)):
                if fk == 2:
                    name = bytes(fv).decode()
                elif fk == 5:
                    stat = dict(_fields(fv))
                    if stat.get(1) in wanted:
                        op_name = (bytes(stat[5]).decode() if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
            if op_name:
                out[name] = op_name
        return out
    return {}


def inherit(named: Dict[str, str], texts: Sequence[str]) -> Dict[str, str]:
    """``named`` (instruction text -> op_name) with an entry for every one
    of ``texts`` that has none of its own but reads an instruction that has,
    directly or through others as nameless (its first operand with a name,
    found in passes over ``texts`` in their order): the converts and copies
    the compiler puts behind one of the program's operations carry no
    metadata (PR 24: the 48 f32 converts of the gathered rows, a third of
    the decode step), and count under the scope of the operation whose
    result they read."""
    by_short = {T.short_name(t): t for t in texts}
    out = dict(named)
    reads = {t: [by_short[o] for o in _OPERAND_RE.findall(
                 t.partition("=")[2]) if o in by_short and by_short[o] != t]
             for t in texts if t not in out}
    for _ in range(8):                  # a chain of nameless operations
        found = {}
        for t, sources in reads.items():
            name = next((out[x] for x in sources if x in out), None)
            if name is not None:
                found[t] = name
        if not found:
            break
        out.update(found)
        for t in found:
            del reads[t]
    return out


def newest_trace() -> Optional[str]:
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "out")
    found = glob.glob(os.path.join(out, "trace-*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


_OF_RUN: Dict[Interval, Optional[Spans]] = {}


def of_run(trace: Optional[T.Trace]) -> Optional[Spans]:
    """The spans of the run whose ``Trace`` a reader was handed: the newest
    trace file under ``benchmarks/out``, if its window annotation is
    ``trace.window`` and the program put spans into it.  Read once a
    process; the first reading prints ``idle_by_phase`` and
    ``compiles_in_trace``."""
    if trace is None or trace.window is None:
        return None
    if trace.window not in _OF_RUN:
        path = newest_trace()
        found = read(path) if path else None
        if found is not None and found.window != trace.window:
            found = None
        if found is not None and not found.main_threads():
            found = None        # a program without spans: nothing to read
        if found is not None:
            report(found, trace)
        _OF_RUN[trace.window] = found
    return _OF_RUN[trace.window]


def report(spans: Spans, trace: T.Trace) -> None:
    idle = spans.idle_by_phase(trace)
    rest = sorted(((k, v) for k, v in idle.items() if k != UNATTRIBUTED),
                  key=lambda kv: -kv[1])
    print(json.dumps({"idle_by_phase": [[k, v] for k, v in rest],
                      UNATTRIBUTED: idle.get(UNATTRIBUTED, 0.0)}),
          flush=True)
    inside = collections.Counter(
        where for _, where in spans.compiles(trace.window))
    print(json.dumps({"compiles_in_trace": sum(inside.values()),
                      "in": sorted(inside.items())}), flush=True)
