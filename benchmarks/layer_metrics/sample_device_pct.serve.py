"""The sampler's share of the decode step on the chip: of the device time of
the leaf operations inside the runs of the decode program
(``decode_programs`` of the traffic file) in the traced span, first chip, the
part whose HLO op_name holds the program's scope ``sample`` (the choice of
each slot's next token from its logits: the argmax, and where a live row asks
for them the temperature divide, the top-k/top-p filter's sort and the
categorical draw).  Nothing where the trace names no scope."""

LAYER = "model step"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve":
        return None
    from benchmarks.lib import spans as S
    return S.scope_share_pct(trace, records["decode_programs"], ("sample",))
