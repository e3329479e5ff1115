"""The paged gather's share of the decode step on the chip: of the device time
of the leaf operations inside the runs of the decode program
(``decode_programs`` of the traffic file) in the traced span, first chip, the
part whose HLO op_name holds the program's scope ``kv_gather`` (the
block-table gather of every slot's keys and values and the read of the
gathered rows).  An operation the compiler makes without a name of its own
(PR 24 found the f32 converts of the gathered rows to be such) is in the
whole and not in the part.  Nothing where the trace names no scope."""

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve":
        return None
    from benchmarks.lib import spans as S
    return S.scope_share_pct(trace, records["decode_programs"], ("kv_gather",))
