"""The experts' grouped matmuls' share of their roofline in the traced span,
over the decode steps AND the prefill units.  Time: the device time of the
leaf operations under scope ``moe_experts`` (up, the activation, down) inside
the runs of the decode and the prefill programs.  Least time
(``counts_nemotronh.experts_least_seconds``, once a family and added: a decode
step is bound by the touched experts' weights, a prefill unit by whichever of
its assignments' FLOPs over the bf16 peak and its touched experts' weights over
the HBM peak is larger): from the engine's two families of counters
(``moe_*`` over decode steps, ``moe_prefill_*`` over prefill units), taken at
the span's edges and scaled to the runs the trace holds whole."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    c = records.get("traced_counters")
    if (records.get("kind") != "serve" or trace is None or not trace.devices
            or not c or not c.get("decode_steps")
            or "moe_prefill_layer_units" not in c):
        return None
    from benchmarks.lib import counts_nemotronh as C
    from benchmarks.lib import spans as S
    from benchmarks.lib import trace as T
    spans = S.of_run(trace)
    if spans is None:
        return None
    plane = trace.devices[0]
    decode = T.module_runs(plane, trace.window, records["decode_programs"])
    prefill = T.module_runs(plane, trace.window,
                            env["traffic"]["prefill_programs"])
    found = spans.scope_seconds(sorted(decode + prefill), ("moe_experts",))
    if found is None or found[0] <= 0:
        return None
    units = c["prefill_chunks"] + c["prefill_batches"]
    peaks = env["peaks"]
    least = 0.0
    for runs, of, held, touched in (
            (decode, c["decode_steps"], "moe_assignments_held",
             "moe_experts_touched"),
            (prefill, units, "moe_prefill_assignments_held",
             "moe_prefill_experts_touched")):
        if runs and of:
            part = len(runs) / of
            least += C.experts_least_seconds(
                env["cfg"], c[held] * part, c[touched] * part,
                peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return 100.0 * least / found[0]
