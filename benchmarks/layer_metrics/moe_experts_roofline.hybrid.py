"""The experts' grouped matmuls' share of their roofline in the decode steps of
the traced span.  Time: the device time of the leaf operations under scope
``moe_experts`` (gate/up, the activation between, down) inside the decode
program's runs.  Least time (``counts_solar.experts_least_seconds``): the
larger of the FLOPs of the (token, held expert) assignments the engine counted
over the bf16 peak and the weights of the experts it counted as touched over
the HBM peak; the counters, taken at the span's edges, are scaled to the runs
the trace holds whole."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    c = records.get("traced_counters")
    if (records.get("kind") != "serve" or trace is None or not trace.devices
            or not c or not c.get("decode_steps")):
        return None
    from benchmarks.lib import counts_solar as C
    from benchmarks.lib import spans as S
    from benchmarks.lib import trace as T
    spans = S.of_run(trace)
    if spans is None:
        return None
    runs = T.module_runs(trace.devices[0], trace.window,
                         records["decode_programs"])
    found = spans.scope_seconds(runs, ("moe_experts",))
    if found is None or found[0] <= 0:
        return None
    part = len(runs) / c["decode_steps"]
    least = C.experts_least_seconds(
        env["cfg"], c["moe_assignments_held"] * part,
        c["moe_experts_touched"] * part, env["peaks"]["bf16_flops_per_s"],
        env["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / found[0]
