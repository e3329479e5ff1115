"""The flash-attention kernels' share of their roofline in the traced epochs.

Time: the summed device time of every Pallas kernel (``tpu_custom_call``)
whose operands are shaped (batch x heads, seq, head_dim), forward and backward,
inside the whole epoch programs of the traced window, on the first chip.
Least time: for every layer of every step of those epochs, the larger of
FLOPs over the bf16 peak and bytes over the HBM peak of one causal
forward-and-backward (``counts.flash_attention_call``).  The count of layer-steps
comes from the job (epochs x steps x layers), not from the number of kernel
events, so fusing or splitting kernels does not move the yardstick."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(records, trace, env):
    if records.get("kind") != "train" or trace is None or not trace.devices:
        return None
    from benchmarks.lib import counts
    from benchmarks.lib import trace as T
    w = counts.widths(env["cfg"])
    batch, seq = records["batch"], records["seq_len"]
    shape = (batch * w["heads"], seq, w["head_dim"])
    plane = trace.devices[0]
    runs = T.module_runs(plane, trace.window, records["epoch_programs"])
    if not runs:
        return None
    lo, hi = min(s for s, _ in runs), max(e for _, e in runs)

    def flash(name):
        if not T.is_pallas(name):
            return False
        ops = T.operand_shapes(name)
        return bool(ops) and ops[0][1] == shape

    seconds, n = T.op_seconds(plane, (lo, hi), flash)
    if n == 0:
        return None
    call = counts.flash_attention_call(batch, w["heads"], seq, w["head_dim"])
    least, _ = counts.roofline_seconds(
        call["fwd_flops"] + call["bwd_flops"],
        call["fwd_bytes"] + call["bwd_bytes"],
        env["peaks"]["bf16_flops_per_s"], env["peaks"]["hbm_bytes_per_s"])
    layer_steps = len(runs) * records["steps_per_epoch"] * w["layers"]
    return 100.0 * layer_steps * least / seconds
