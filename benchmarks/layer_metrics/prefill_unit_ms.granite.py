"""The median device time of one prefill unit in the traced span of the
Granite-4.0-H serving cell (units of at most 512 tokens): of every
run of a prefill program (``prefill_programs`` of the traffic file: buckets,
chunks and final chunks) that the trace holds whole on the first chip, the
seconds of its leaf operations.  A decode step that shares its iteration with
a unit waits for it: this is what the long inter-token gaps of the cell are
made of.

A BURST-SPAN reading: the cell's traced span (``trace`` of the traffic file:
2.5 s from second 13) lies inside the schedule's largest burst, where about
twice the window's mean of rows are live and half the device time is prefill;
``itl_p95_ms`` and ``serve_tokens_per_s`` are taken over the whole window.
The run's log prints ``traced_rows_live`` beside ``window_rows_live``
(``drivers/serve_granite.py``): compare two runs' readings at like rows."""

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
