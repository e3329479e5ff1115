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
    from benchmarks.lib import trace as T
    from benchmarks.lib.stats import median
    plane = trace.devices[0]
    runs = T.module_runs(plane, trace.window, programs)
    if not runs:
        return None
    return 1000.0 * median([T.ops_inside(plane, [r]) for r in runs])
