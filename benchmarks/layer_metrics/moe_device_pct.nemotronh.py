"""The share of the serving programs' device time under scope ``moe``, in
the Nemotron-H serving cell: of the leaf operations inside the runs of the
decode and prefill programs (``serve_programs`` of the traffic file) in the
traced span, first chip, the part whose HLO op_name holds the scope of
the sparse expert layers (routing, dispatch, the grouped matmuls over the held experts, the shared expert, the combine).
Nothing where the trace names no scope."""

LAYER = "model step"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    # the scope share is the same arithmetic over the same records as the accepted
    # reader's: one copy of it
    from benchmarks.lib import manifest as mf
    return mf.load_layer_metric("moe_device_pct.hybrid").read(records, trace, env)
