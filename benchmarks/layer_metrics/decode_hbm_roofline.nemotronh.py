"""The decode steps' share of their HBM roofline in the traced span, for the
Nemotron-H serving cell.  Least bytes (``counts_nemotronh.
decode_least_bytes``): the weights outside the experts once a step, every
expert that got a token once (the engine's ``moe_experts_touched``), every live
row's recurrent state read and written, and the keys and values of every
attended position, at the HBM peak.  Time: the device time of the decode
program's operations in the span.  The engine's counters are taken at the
span's edges on the host; they are scaled to the decode runs the trace holds
whole."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    c = records.get("traced_counters")
    if (records.get("kind") != "serve" or trace is None or not trace.devices
            or not c or not c.get("decode_steps")
            or records.get("traced_context_positions") is None):
        return None
    from benchmarks.lib import counts_nemotronh as C
    from benchmarks.lib import trace as T
    plane = trace.devices[0]
    runs = T.module_runs(plane, trace.window, records["decode_programs"])
    seconds = T.ops_inside(plane, runs)
    if not runs or seconds <= 0:
        return None
    part = len(runs) / c["decode_steps"]
    least = C.decode_least_bytes(
        env["cfg"], len(runs), c["active_slot_steps"] * part,
        c["moe_experts_touched"] * part,
        records["traced_context_positions"] * part)
    return 100.0 * least / env["peaks"]["hbm_bytes_per_s"] / seconds
