"""Operations and bytes, computed from a configuration's widths.

The yardstick of ``mfu.train``, ``flash_attn_roofline.train`` and
``decode_hbm_roofline.serve``.  Everything counts the LEAST work the algorithm
needs (no recomputation, causal attention as half of S x S, weights at the
precision the configuration states), so a share of a peak can only read low,
never above 100 %.  Checked on hand-computed shapes in ``tests/``.
"""

from __future__ import annotations

from typing import Dict

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def widths(cfg: Dict) -> Dict[str, int]:
    d = int(cfg["n_embd"])
    return dict(d=d, heads=int(cfg["n_head"]), layers=int(cfg["n_layer"]),
                inner=int(cfg.get("n_inner") or 4 * d),
                vocab=int(cfg["vocab_size"]),
                positions=int(cfg["n_positions"]),
                head_dim=d // int(cfg["n_head"]))


def matmul_params(cfg: Dict) -> int:
    """Weights that a token is multiplied through: q, k, v, o and the two MLP
    matrices of every block, and the (untied) LM head.  Embedding tables are
    looked up, not multiplied; biases and norms add, and are left out."""
    w = widths(cfg)
    per_block = 4 * w["d"] * w["d"] + 2 * w["d"] * w["inner"]
    return w["layers"] * per_block + w["d"] * w["vocab"]


def total_params(cfg: Dict) -> int:
    """Every parameter of the repo's model (untied head with bias)."""
    w = widths(cfg)
    d, i = w["d"], w["inner"]
    per_block = (4 * d * d + 4 * d) + (2 * d * i + i + d) + 4 * d
    return (w["vocab"] * d + w["positions"] * d + w["layers"] * per_block
            + 2 * d + d * w["vocab"] + w["vocab"])


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward and backward of one token in a causal LM at ``seq_len``:
    6 x matmul parameters (2 forward, 4 backward) plus causal attention,
    6 x layers x seq_len x d (QK^T and PV are 2 x 2 x S x d forward over the
    full square, half of it under the causal mask; backward is twice the
    forward).  No recomputation is counted."""
    w = widths(cfg)
    return 6.0 * matmul_params(cfg) + 6.0 * w["layers"] * seq_len * w["d"]


def flash_attention_call(batch: int, heads: int, seq: int, head_dim: int,
                         itemsize: int = 2) -> Dict[str, float]:
    """One layer's causal flash attention, forward and backward, as the least
    an algorithm needs.  FLOPs: forward 2 matmuls (QK^T, PV), backward 4
    (dV, dP, dQ, dK; the recomputed QK^T is not counted), each 2 x S x S x Dh
    per head, halved by the causal mask.  Bytes: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    tensor = batch * heads * seq * head_dim
    mm = 2.0 * batch * heads * seq * seq * head_dim / 2.0
    return dict(fwd_flops=2 * mm, bwd_flops=4 * mm,
                fwd_bytes=4.0 * tensor * itemsize,
                bwd_bytes=8.0 * tensor * itemsize)


def kv_bytes_per_token(cfg: Dict) -> int:
    """Keys and values of one context position, all layers, at the stated
    cache precision."""
    w = widths(cfg)
    item = DTYPE_BYTES[cfg["precision"]["kv_cache"]]
    return 2 * w["layers"] * w["d"] * item


def weight_bytes(cfg: Dict) -> int:
    """Every matmul weight once, at the stated compute precision: what a
    decode step has to read however it is written."""
    return matmul_params(cfg) * DTYPE_BYTES[cfg["precision"]["compute"]]


def decode_least_seconds(cfg: Dict, decode_steps: int, context_positions: int,
                         hbm_bytes_per_s: float) -> float:
    """Least time of ``decode_steps`` batched decode steps that together
    attend over ``context_positions`` cached positions (summed over every
    token decoded): the weights once a step plus each position's keys and
    values once, at the HBM peak.  Decode is bound by bytes, not FLOPs."""
    total = (decode_steps * weight_bytes(cfg)
             + context_positions * kv_bytes_per_token(cfg))
    return total / hbm_bytes_per_s


def roofline_seconds(flops: float, bytes_: float, peak_flops: float,
                     peak_bytes: float):
    """(least seconds, which bound)."""
    tf, tb = flops / peak_flops, bytes_ / peak_bytes
    return (tf, "flops") if tf >= tb else (tb, "bytes")
