"""The decode steps' share of their HBM roofline in the traced span, for the
Granite-4.0-H serving cell.  Least bytes (``counts_granite.
decode_least_bytes``): every block's weights and the tied table ONCE a step,
every live row's recurrent state read and written (the engine's own counter,
``recurrent_state_bytes_moved``), and the keys and values of every attended
position, at the HBM peak.  Time: the device time of the decode program's
operations in the span.  The engine's counters are taken at the span's edges
on the host; they are scaled to the decode runs the trace holds whole.
Nothing where the engine has no such counter.

A BURST-SPAN reading: the cell's traced span (``trace`` of the traffic file:
2.5 s from second 13) lies inside the schedule's largest burst, where about
twice the window's mean of rows are live and half the device time is prefill;
``itl_p95_ms`` and ``serve_tokens_per_s`` are taken over the whole window.
The run's log prints ``traced_rows_live`` beside ``window_rows_live``
(``drivers/serve_granite.py``): compare two runs' readings at like rows."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    c = records.get("traced_counters")
    if (records.get("kind") != "serve" or trace is None or not trace.devices
            or not c or not c.get("decode_steps")
            or not c.get("recurrent_state_bytes_moved")
            or records.get("traced_context_positions") is None):
        return None
    from benchmarks.lib import counts_granite as C
    from benchmarks.lib import trace as T
    plane = trace.devices[0]
    runs = T.module_runs(plane, trace.window, records["decode_programs"])
    seconds = T.ops_inside(plane, runs)
    if not runs or seconds <= 0:
        return None
    part = len(runs) / c["decode_steps"]
    least = C.decode_least_bytes(
        env["cfg"], len(runs), c["recurrent_state_bytes_moved"] * part,
        records["traced_context_positions"] * part)
    return 100.0 * least / env["peaks"]["hbm_bytes_per_s"] / seconds
