"""The median device time of one prefill unit in the traced span: of every
run of a prefill program (``prefill_programs`` of the traffic file: buckets,
chunks and final chunks) that the trace holds whole on the first chip, the
seconds of its leaf operations.  A decode step that shares its iteration with
a unit waits for it: this is what the long inter-token gaps of the cell are
made of."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if (records.get("kind") != "serve" or trace is None or not trace.devices
            or trace.window is None):
        return None
    programs = env["traffic"].get("prefill_programs")
    if not programs:
        return None
    import bisect
    from benchmarks.lib import trace as T
    from benchmarks.lib.stats import median
    plane = trace.devices[0]
    runs = T.module_runs(plane, trace.window, programs)
    if not runs:
        return None
    # ``T.ops_inside`` a run, for all runs in one pass over the operations
    # (a pass a run was quadratic in the span's length); the runs of one
    # chip's programs are disjoint
    runs = sorted(runs)
    starts = [s for s, _ in runs]
    inside = [0] * len(runs)
    for s, e, n in plane.ops:
        if T.is_leaf(n):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= runs[i][1]:
                inside[i] += e - s
    return 1000.0 * median([ns / 1e9 for ns in inside])
