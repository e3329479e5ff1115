"""The share of the serving programs' device time under scope ``ssm``, in
the Nemotron-H serving cell: of the leaf operations inside the runs of the
decode and prefill programs (``serve_programs`` of the traffic file) in the
traced span, first chip, the part whose HLO op_name holds the scope of
the Mamba-2 state-space layers (the input projection, the convolution, the recurrence, the gated norm and the output projection).
Nothing where the trace names no scope."""

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
