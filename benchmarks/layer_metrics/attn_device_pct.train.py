"""Attention's share of the train step on the chip: of the device time of the
leaf operations inside the whole epoch programs of the traced window (first
chip), the part whose HLO op_name holds the program's scope ``attn`` (layer
norm, q/k/v/o projections and ``attn_core``, the flash kernels; forward and
backward, a transposed operation keeps its scope).  Nothing where the trace
names no scope (a program without ``jax.named_scope``s)."""

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(records, trace, env):
    if records.get("kind") != "train":
        return None
    from benchmarks.lib import spans as S
    return S.scope_share_pct(trace, records["epoch_programs"], ("attn",))
