"""The LM head and the loss as a share of the train step on the chip: of the
device time of the leaf operations inside the whole epoch programs of the
traced window (first chip), the part whose HLO op_name holds the program's
scope ``lm_head`` or ``loss`` (the vocabulary projection, the softmax
cross-entropy and its mask; forward and backward, a transposed operation
keeps its scope).  Loop machinery the compiler names after the enclosing
``while`` alone (PR 24 found the loss's scatter loop over the logits to be
such) is in the whole and not in the part.  Nothing where the trace names no
scope (a program without ``jax.named_scope``s)."""

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(records, trace, env):
    if records.get("kind") != "train":
        return None
    from benchmarks.lib import spans as S
    return S.scope_share_pct(trace, records["epoch_programs"], ("lm_head", "loss"))
