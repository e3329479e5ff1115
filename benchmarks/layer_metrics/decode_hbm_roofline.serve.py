"""The decode steps' share of their HBM roofline in the traced span.

Least time: every matmul weight once a step at the configuration's stated
compute precision, plus the keys and values of every context position of every
token decoded in the span (from the generator's own records of which token
came out when), at the HBM peak (``counts.decode_least_seconds``).  Time: the
device time of the decode program's operations in the span.  Decode is bound by
bytes; the FLOP bound is far below."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if (records.get("kind") != "serve" or trace is None or not trace.devices
            or records.get("traced_context_positions") is None):
        return None
    from benchmarks.lib import counts
    from benchmarks.lib import trace as T
    plane = trace.devices[0]
    runs = T.module_runs(plane, trace.window, records["decode_programs"])
    seconds = T.ops_inside(plane, runs)
    if not runs or seconds <= 0:
        return None
    least = counts.decode_least_seconds(
        env["cfg"], len(runs), records["traced_context_positions"],
        env["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
