"""The share of the serving programs' device time under scope ``ssm``, in
the Granite-4.0-H serving cell: of the leaf operations inside the runs of the
decode and prefill programs (``serve_programs`` of the traffic file) in the
traced span, first chip, the part whose HLO op_name holds the scope of the
Mamba-2 state-space mixers (the input projection, the convolution, the
recurrence, the gated norm and the output projection; the block's MLP is
under ``mlp``).  Nothing where the trace names no scope.

A BURST-SPAN reading: the cell's traced span (``trace`` of the traffic file:
2.5 s from second 13) lies inside the schedule's largest burst, where about
twice the window's mean of rows are live and half the device time is prefill;
``itl_p95_ms`` and ``serve_tokens_per_s`` are taken over the whole window.
The run's log prints ``traced_rows_live`` beside ``window_rows_live``
(``drivers/serve_granite.py``): compare two runs' readings at like rows."""

LAYER = "model step"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve" or "serve_programs" not in records:
        return None
    from benchmarks.lib import spans as S
    return S.scope_share_pct(trace, records["serve_programs"], ("ssm",))
