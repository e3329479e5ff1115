"""The ``ssd_decode`` kernel's share of its roofline in the traced span of
the Granite-4.0-H serving cell (36 Mamba-2 layers, one group).
Time: the device time of the Pallas kernels (``tpu_custom_call``) under scope
``ssm_core`` inside the decode program's runs: the fused read-modify-write of
the state-space state, one a Mamba-2 layer a step.  Least time
(``counts_granite.ssd_decode_least_seconds``): every live row's ``S`` read
once and written once at the HBM peak (the kernel is bound by bytes), for the
live rows the engine counted, scaled to the runs the trace holds whole.

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
            or not c or not c.get("decode_steps")):
        return None
    import bisect
    from benchmarks.lib import counts_granite as C
    from benchmarks.lib import spans as S
    from benchmarks.lib import trace as T
    spans = S.of_run(trace)
    if spans is None:
        return None
    runs = sorted(T.module_runs(trace.devices[0], trace.window,
                                records["decode_programs"]))
    starts = [s for s, _ in runs]
    ns = 0
    for op in spans.ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if (i >= 0 and op.end <= runs[i][1] and T.is_pallas(op.name)
                and "ssm_core" in op.op_name):
            ns += op.end - op.start
    if not runs or ns <= 0:
        return None
    least = C.ssd_decode_least_seconds(
        env["cfg"], c["active_slot_steps"] * len(runs) / c["decode_steps"],
        env["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
