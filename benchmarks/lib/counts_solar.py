"""Sizes, operations and least bytes of the Solar-Open2 configuration, from
its own keys.  The yardstick of ``mfu.hybrid``, ``decode_hbm_roofline.hybrid``,
``moe_experts_roofline.hybrid`` and ``kda_decode_roofline.hybrid``: everything
is the LEAST the algorithm needs on THIS chip's share of the deployment (the
experts held here, the slice of the vocabulary), so a share of a peak can only
read low.  Checked on hand-computed shapes in ``tests/``."""

from __future__ import annotations

from typing import Dict

from .counts import DTYPE_BYTES


def dims(cfg: Dict) -> Dict:
    lin = cfg["linear_attn_config"]
    layers = int(cfg["num_hidden_layers"])
    gqa = {int(i) for i in cfg["gqa_layers"]}
    moe = int(cfg["moe_intermediate_size"])
    return dict(
        hidden=int(cfg["hidden_size"]), layers=layers,
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        lin_heads=int(lin["num_heads"]), lin_dim=int(lin["head_dim"]),
        conv=int(lin["short_conv_kernel_size"]),
        rank=int(lin["head_dim"]),   # the low-rank gates go through head_dim
        experts=int(cfg["published"]["n_routed_experts"]),
        held=int(cfg["n_routed_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        expert_dim=moe,
        shared_dim=int(cfg["n_shared_experts"]) * moe,
        vocab=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
        kinds=["gqa" if i in gqa else "kda" for i in range(layers)])


def expert_params(cfg: Dict) -> int:
    """One routed expert: gate, up and down."""
    d = dims(cfg)
    return 3 * d["hidden"] * d["expert_dim"]


def mixer_params(cfg: Dict, kind: str) -> int:
    d = dims(cfg)
    h = d["hidden"]
    if kind == "gqa":
        inner, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
        return 3 * h * inner + 2 * h * kv               # q, gate, o; k, v
    inner = d["lin_heads"] * d["lin_dim"]
    low_rank = h * d["rank"] + d["rank"] * inner
    return 4 * h * inner + 2 * low_rank + h * d["lin_heads"]


def dense_params_per_layer(cfg: Dict, kind: str) -> int:
    """Matmul weights every token of a layer goes through whatever the
    router says: the mixer, the router and the shared expert."""
    d = dims(cfg)
    return (mixer_params(cfg, kind) + d["hidden"] * d["experts"]
            + 3 * d["hidden"] * d["shared_dim"])


def dense_params(cfg: Dict) -> int:
    """All of them, and the head (the embedding is looked up)."""
    d = dims(cfg)
    return (sum(dense_params_per_layer(cfg, k) for k in d["kinds"])
            + d["hidden"] * d["vocab"])


def total_params(cfg: Dict) -> int:
    """Every parameter held on this chip."""
    d = dims(cfg)
    inner = d["lin_heads"] * d["lin_dim"]
    small = sum(2 * d["hidden"] if k == "gqa" else
                2 * d["hidden"] + 3 * d["conv"] * inner + d["lin_heads"]
                + inner + d["lin_dim"] for k in d["kinds"])
    return (dense_params(cfg) + d["hidden"] * d["vocab"] + d["hidden"] + small
            + d["layers"] * d["held"] * expert_params(cfg))


def held_share(cfg: Dict) -> float:
    """Of a token's ``top_k`` assignments, how many land on experts held
    here when the router is even: ``top_k * held / experts``."""
    d = dims(cfg)
    return d["top_k"] * d["held"] / d["experts"]


def kda_state_flops_per_token(cfg: Dict) -> float:
    """One layer's recurrence for one token: decay (1), the read ``S^T k``
    (2), the rank-one write (2) and the read ``S^T q`` (2) per state
    element."""
    d = dims(cfg)
    return 7.0 * d["lin_heads"] * d["lin_dim"] * d["lin_dim"]


def flops_per_token(cfg: Dict, context: float, with_head: bool) -> float:
    """Forward of one token that attends ``context`` positions: 2 x the
    matmul weights it goes through (its routed experts at the even router's
    share of those held here), the attention over its context in the GQA
    layers, the recurrence in the KDA layers, and the head if its logits
    are needed."""
    d = dims(cfg)
    per = 0.0
    for kind in d["kinds"]:
        per += 2.0 * (dense_params_per_layer(cfg, kind)
                      + held_share(cfg) * expert_params(cfg))
        if kind == "gqa":
            per += 4.0 * d["heads"] * d["head_dim"] * context
        else:
            per += kda_state_flops_per_token(cfg)
    return per + (2.0 * d["hidden"] * d["vocab"] if with_head else 0.0)


def kv_bytes_per_token(cfg: Dict) -> int:
    d = dims(cfg)
    item = DTYPE_BYTES[cfg["precision"]["kv_cache"]]
    return (2 * d["kv_heads"] * d["head_dim"] * item
            * sum(k == "gqa" for k in d["kinds"]))


def recurrent_state_bytes(cfg: Dict) -> int:
    """One slot's recurrent state over all KDA layers: ``S`` at the stated
    ``recurrent_state`` precision and the convolution's history."""
    d = dims(cfg)
    s = (d["lin_heads"] * d["lin_dim"] * d["lin_dim"]
         * DTYPE_BYTES[cfg["precision"]["recurrent_state"]])
    conv = ((d["conv"] - 1) * 3 * d["lin_heads"] * d["lin_dim"]
            * DTYPE_BYTES[cfg["precision"]["compute"]])
    return (s + conv) * sum(k == "kda" for k in d["kinds"])


def decode_least_bytes(cfg: Dict, steps: int, live_row_steps: int,
                       experts_touched: int, context_positions: int) -> float:
    """Least HBM traffic of ``steps`` decode steps: the weights outside the
    experts once a step, every expert that got a token once (``experts_
    touched``: summed over layers and steps, from the engine's counter), each
    live row's recurrent state read and written, and the keys and values of
    every attended position."""
    item = DTYPE_BYTES[cfg["precision"]["compute"]]
    return (steps * dense_params(cfg) * item
            + experts_touched * expert_params(cfg) * item
            + live_row_steps * 2 * recurrent_state_bytes(cfg)
            + context_positions * kv_bytes_per_token(cfg))


def experts_least_seconds(cfg: Dict, assignments: int, experts_touched: int,
                          peak_flops: float, peak_bytes: float) -> float:
    """Least time of the grouped matmuls (gate/up and down) over
    ``assignments`` (token, held expert) pairs that touched
    ``experts_touched`` experts: the larger of their FLOPs over the bf16 peak
    and the touched experts' weights over the HBM peak."""
    flops = 2.0 * assignments * expert_params(cfg)
    bytes_ = (experts_touched * expert_params(cfg)
              * DTYPE_BYTES[cfg["precision"]["compute"]])
    return max(flops / peak_flops, bytes_ / peak_bytes)


def kda_decode_least_seconds(cfg: Dict, live_row_steps: int,
                             peak_bytes: float) -> float:
    """Least time of the fused decode step over ``live_row_steps`` (live row,
    step) pairs, all KDA layers: each state read once and written once."""
    d = dims(cfg)
    s = (d["lin_heads"] * d["lin_dim"] * d["lin_dim"]
         * DTYPE_BYTES[cfg["precision"]["recurrent_state"]])
    layers = sum(k == "kda" for k in d["kinds"])
    return live_row_steps * layers * 2 * s / peak_bytes
