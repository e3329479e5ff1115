"""Model FLOP/s utilization of the traced span of the Granite-4.0-H serving
cell: the benchmark's own count of the forward of every prompt token prefilled
and every token decoded between the span's edges
(``counts_granite.flops_per_token``: 2 x the matmul weights of every block,
the recurrence of the Mamba-2 layers, the head (the tied table) for a decoded
token, and the attention of the decoded tokens over their contexts; a
prefilled token's attention over its context and a final unit's head are left
out, so the share reads low), over the span's seconds and the chip's bf16
peak.  The share of the whole step: host gaps and idle time count against
it.

A BURST-SPAN reading: the cell's traced span (``trace`` of the traffic file:
2.5 s from second 13) lies inside the schedule's largest burst, where about
twice the window's mean of rows are live and half the device time is prefill;
``itl_p95_ms`` and ``serve_tokens_per_s`` are taken over the whole window.
The run's log prints ``traced_rows_live`` beside ``window_rows_live``
(``drivers/serve_granite.py``): compare two runs' readings at like rows."""

LAYER = "model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    c = records.get("traced_counters")
    if (records.get("kind") != "serve" or trace is None or not trace.devices
            or not c or trace.window is None or not c.get("seconds")):
        return None
    from benchmarks.lib import counts_granite as C
    cfg = env["cfg"]
    d = C.dims(cfg)
    flops = (c["prefill_tokens"] * C.flops_per_token(cfg, 0.0, False)
             + c["active_slot_steps"] * C.flops_per_token(cfg, 0.0, True)
             + 4.0 * d["heads"] * d["head_dim"] * C.count(cfg, "attn")
             * (records.get("traced_context_positions") or 0))
    peak = env["chips"] * env["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / c["seconds"] / peak
