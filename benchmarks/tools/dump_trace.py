#!/usr/bin/env python3
"""One-off: look at a trace by hand before writing code against it, and cut a
small slice of it WITH its stats for ``tests/fixtures``.

    python3 benchmarks/tools/dump_trace.py [xplane or trace dir] --out chiprun_out/x.json
        [--slice NAME START_MS SPAN_MS]... [--host-names PREFIX,PREFIX...]
        [--planes PREFIX,PREFIX...]

Prints (and writes to ``--out``) everything ``jax.profiler.ProfileData`` gives
for the trace: planes and their stats, lines and event counts, and for a
sample of the ``XLA Ops`` events every stat of the event — where the HLO
``op_name`` (jax's name stack, the program's ``named_scope``s) is, if anywhere.
Where tensorflow's ``xplane_pb2`` can be imported the raw proto is read too:
the event metadata of the sampled ops (name, display name, stats) and what the
metadata planes hold.  ``--slice`` cuts ``[start, start + span)`` milliseconds
from the start of the ``bench_window`` annotation out of every line, drops
event metadata nothing refers to and every stat over 4 KiB (HLO protos), and
writes ``<out dir>/<NAME>.xplane.pb.gz``; with ``--host-names`` a host plane
keeps only the events whose names start with one of the prefixes (the
program's spans, say, without the runtime's own), and with ``--planes`` only
the planes whose names start with one of those (one chip of four, say).  The
default trace is the newest under ``benchmarks/out``.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

SAMPLE = 48           # op events shown in full
BIG_STAT = 4096       # bytes: a stat over this is an HLO proto, not a label


def newest_xplane() -> str:
    found = glob.glob(os.path.join(BENCH_DIR, "out", "trace-*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not found:
        raise SystemExit("no trace under benchmarks/out")
    return max(found, key=os.path.getmtime)


def short(v, n=300):
    if isinstance(v, bytes):
        return f"<{len(v)} bytes>"
    s = str(v)
    return s if len(s) <= n else s[:n] + f"...<{len(s)} chars>"


def with_profile_data(path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = {"planes": []}
    for plane in data.planes:
        p = {"name": plane.name,
             "stats": {k: short(v) for k, v in plane.stats}, "lines": []}
        for line in plane.lines:
            events = list(line.events)
            entry = {"name": line.name, "events": len(events)}
            if events:
                keys = {}
                step = max(len(events) // SAMPLE, 1)
                shown = []
                for e in events[::step][:SAMPLE]:
                    stats = {k: short(v) for k, v in e.stats}
                    for k, v in stats.items():
                        keys.setdefault(k, v)
                    shown.append({"name": short(e.name), "stats": stats,
                                  "duration_ns": e.duration_ns})
                entry["stat_keys"] = keys
                if line.name in ("XLA Ops", "XLA Modules", "Steps") \
                        or plane.name.startswith("/host:CPU"):
                    entry["sample"] = shown[:SAMPLE if line.name == "XLA Ops"
                                            else 6]
            p["lines"].append(entry)
        out["planes"].append(p)
    return out


def load_proto(path: str):
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:  # the look through ProfileData stands alone
        print(f"dump_trace: no xplane_pb2 ({e}); raw proto not read",
              file=sys.stderr)
        return None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_value(st, names):
    kind = st.WhichOneof("value")
    v = getattr(st, kind) if kind else None
    if kind == "ref_value":
        v = names.get(v, v)
    return v


def with_proto(space) -> dict:
    out = {"planes": []}
    for plane in space.planes:
        names = {i: m.name for i, m in plane.stat_metadata.items()}
        p = {"name": plane.name, "event_metadata": len(plane.event_metadata),
             "stat_metadata": sorted(names.values()),
             "plane_stats": {names.get(s.metadata_id, s.metadata_id):
                             short(stat_value(s, names)) for s in plane.stats},
             "sample_event_metadata": []}
        items = list(plane.event_metadata.values())
        for m in items[::max(len(items) // 24, 1)][:24]:
            p["sample_event_metadata"].append({
                "name": short(m.name), "display_name": short(m.display_name),
                "metadata_bytes": len(m.metadata),
                "stats": {names.get(s.metadata_id, s.metadata_id):
                          short(stat_value(s, names)) for s in m.stats}})
        out["planes"].append(p)
    return out


def cut(space, name: str, start_ms: float, span_ms: float, out_dir: str,
        host_names=(), planes=()):
    """``[start, start + span)`` ms after the window annotation opens, from
    every line; big stats and unused metadata dropped; of a host plane only
    the events named ``host_names...``, and only the planes named
    ``planes...``, if any are given."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    from benchmarks.lib.trace import WINDOW_SPAN
    t0 = None
    for plane in space.planes:
        ids = {i for i, m in plane.event_metadata.items()
               if m.name == WINDOW_SPAN}
        for line in plane.lines:
            for e in line.events:
                if e.metadata_id in ids:
                    t0 = line.timestamp_ns * 1000 + e.offset_ps
    if t0 is None:
        raise SystemExit(f"no {WINDOW_SPAN} annotation in the trace")
    lo = t0 + int(start_ms * 1e9)
    hi = lo + int(span_ms * 1e9)
    new = xplane_pb2.XSpace()
    for plane in space.planes:
        if planes and not plane.name.startswith(planes):
            continue
        keep_plane = new.planes.add()
        keep_plane.id, keep_plane.name = plane.id, plane.name
        used = set()
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            kept = [e for e in line.events
                    if base + e.offset_ps < hi
                    and base + e.offset_ps + e.duration_ps > lo]
            if host_names and plane.name.startswith("/host:"):
                kept = [e for e in kept
                        if plane.event_metadata[e.metadata_id].name
                        .startswith(host_names)]
            # the window annotation itself always stays: it anchors the cut
            kept += [e for e in line.events
                     if plane.event_metadata[e.metadata_id].name
                     == WINDOW_SPAN and e not in kept]
            if not kept:
                continue
            nl = keep_plane.lines.add()
            nl.id, nl.name, nl.display_name = (line.id, line.name,
                                               line.display_name)
            nl.timestamp_ns = line.timestamp_ns
            for e in kept:
                nl.events.add().CopyFrom(e)
                used.add(e.metadata_id)
        for i in used:
            m = keep_plane.event_metadata[i]
            m.CopyFrom(plane.event_metadata[i])
            m.metadata = b""
            for s in list(m.stats):
                if len(s.SerializeToString()) > BIG_STAT:
                    m.stats.remove(s)
        for i, m in plane.stat_metadata.items():
            keep_plane.stat_metadata[i].CopyFrom(m)
        if not keep_plane.lines:
            del new.planes[-1]
    path = os.path.join(out_dir, name + ".xplane.pb.gz")
    with gzip.open(path, "wb") as f:
        f.write(new.SerializeToString())
    print(f"dump_trace: slice {name}: {os.path.getsize(path)} bytes, "
          f"{sum(len(l.events) for p in new.planes for l in p.lines)} events")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", nargs="?")
    ap.add_argument("--out", required=True)
    ap.add_argument("--slice", nargs=3, action="append", default=[],
                    metavar=("NAME", "START_MS", "SPAN_MS"))
    ap.add_argument("--host-names", default="")
    ap.add_argument("--planes", default="")
    args = ap.parse_args(argv)
    path = args.path or newest_xplane()
    if os.path.isdir(path):
        from benchmarks.lib.trace import find_xplane
        path = find_xplane(path)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = {"path": path, "bytes": os.path.getsize(path),
           "profile_data": with_profile_data(path)}
    space = load_proto(path)
    if space is not None:
        out["proto"] = with_proto(space)
        for name, start, span in args.slice:
            cut(space, name, float(start), float(span),
                os.path.dirname(os.path.abspath(args.out)),
                tuple(n for n in args.host_names.split(",") if n),
                tuple(n for n in args.planes.split(",") if n))
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(f"dump_trace: {path} ({out['bytes']} bytes) -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
