"""Sizes, operations and least bytes of the Nemotron-3-Nano configuration
(``model_type`` ``nemotron_h``), from its own keys.  The yardstick of the
``.nemotronh`` readers: everything is the LEAST the algorithm needs on THIS
chip's share of the deployment (the experts held here, the slice of the
vocabulary), so a share of a peak can only read low.  Checked on hand-computed
shapes in ``tests/``."""

from __future__ import annotations

from typing import Dict

from .counts import DTYPE_BYTES

KINDS = {"M": "mamba", "*": "attn", "E": "experts"}


def dims(cfg: Dict) -> Dict:
    layers = int(cfg["num_hidden_layers"])
    heads, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    groups, state = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    inner = heads * p           # NOT expand x hidden (``assumed``)
    return dict(
        hidden=int(cfg["hidden_size"]), layers=layers,
        kinds=[KINDS[ch] for ch in cfg["hybrid_override_pattern"][:layers]],
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        m_heads=heads, m_dim=p, groups=groups, state=state,
        conv=int(cfg["conv_kernel"]), inner=inner,
        conv_dim=inner + 2 * groups * state,
        experts=int(cfg["published"]["n_routed_experts"]),
        held=int(cfg["n_routed_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        expert_dim=int(cfg["moe_intermediate_size"]),
        shared_dim=(int(cfg["n_shared_experts"])
                    * int(cfg["moe_shared_expert_intermediate_size"])),
        scale=float(cfg["routed_scaling_factor"]),
        vocab=int(cfg["vocab_size"]), eps=float(cfg["layer_norm_epsilon"]))


def count(cfg: Dict, kind: str) -> int:
    return sum(k == kind for k in dims(cfg)["kinds"])


def expert_params(cfg: Dict) -> int:
    """One routed expert: up and down (no gate)."""
    d = dims(cfg)
    return 2 * d["hidden"] * d["expert_dim"]


def mixer_params(cfg: Dict, kind: str) -> int:
    """Matmul weights of a Mamba-2 or attention part."""
    d = dims(cfg)
    h = d["hidden"]
    if kind == "attn":
        inner, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
        return 2 * h * inner + 2 * h * kv               # q, o; k, v
    return (h * (d["inner"] + d["conv_dim"] + d["m_heads"])
            + d["inner"] * h)                           # in; out


def dense_params_per_layer(cfg: Dict, kind: str) -> int:
    """Matmul weights every token of a layer goes through whatever the
    router says: the mixer, or the router and the shared expert."""
    d = dims(cfg)
    if kind == "experts":
        return d["hidden"] * d["experts"] + 2 * d["hidden"] * d["shared_dim"]
    return mixer_params(cfg, kind)


def dense_params(cfg: Dict) -> int:
    """All of them, and the head (the embedding is looked up)."""
    d = dims(cfg)
    return (sum(dense_params_per_layer(cfg, k) for k in d["kinds"])
            + d["hidden"] * d["vocab"])


def small_params(cfg: Dict, kind: str) -> int:
    """What a layer holds beside its matmul weights: its norm; a Mamba-2
    part's convolution and bias, dt_bias, A_log, D and the gated norm's
    scale; the router's selection bias."""
    d = dims(cfg)
    if kind == "mamba":
        return (d["hidden"] + (d["conv"] + 1) * d["conv_dim"]
                + 3 * d["m_heads"] + d["inner"])
    return d["hidden"] + (d["experts"] if kind == "experts" else 0)


def total_params(cfg: Dict) -> int:
    """Every parameter held on this chip."""
    d = dims(cfg)
    return (dense_params(cfg) + d["hidden"] * d["vocab"] + d["hidden"]
            + sum(small_params(cfg, k) for k in d["kinds"])
            + count(cfg, "experts") * d["held"] * expert_params(cfg))


def held_share(cfg: Dict) -> float:
    """Of a token's ``top_k`` assignments, how many land on experts held
    here when the router is even: ``top_k * held / experts``."""
    d = dims(cfg)
    return d["top_k"] * d["held"] / d["experts"]


def ssm_state_flops_per_token(cfg: Dict) -> float:
    """One layer's recurrence for one token: decay (1), the rank-one write
    (2) and the read ``S C`` (2) per state element."""
    d = dims(cfg)
    return 5.0 * d["m_heads"] * d["m_dim"] * d["state"]


def flops_per_token(cfg: Dict, context: float, with_head: bool) -> float:
    """Forward of one token that attends ``context`` positions: 2 x the
    matmul weights it goes through (its routed experts at the even router's
    share of those held here), the attention over its context in the
    attention layers, the recurrence in the Mamba-2 layers, and the head if
    its logits are needed."""
    d = dims(cfg)
    per = 0.0
    for kind in d["kinds"]:
        per += 2.0 * dense_params_per_layer(cfg, kind)
        if kind == "experts":
            per += 2.0 * held_share(cfg) * expert_params(cfg)
        elif kind == "attn":
            per += 4.0 * d["heads"] * d["head_dim"] * context
        else:
            per += ssm_state_flops_per_token(cfg)
    return per + (2.0 * d["hidden"] * d["vocab"] if with_head else 0.0)


def kv_bytes_per_token(cfg: Dict) -> int:
    d = dims(cfg)
    item = DTYPE_BYTES[cfg["precision"]["kv_cache"]]
    return 2 * d["kv_heads"] * d["head_dim"] * item * count(cfg, "attn")


def ssm_state_bytes(cfg: Dict) -> int:
    """One slot's ``S`` in ONE Mamba-2 layer at the stated precision."""
    d = dims(cfg)
    return (d["m_heads"] * d["m_dim"] * d["state"]
            * DTYPE_BYTES[cfg["precision"]["recurrent_state"]])


def recurrent_state_bytes(cfg: Dict) -> int:
    """One slot's recurrent state over all Mamba-2 layers: ``S`` and the
    convolution's history."""
    d = dims(cfg)
    conv = ((d["conv"] - 1) * d["conv_dim"]
            * DTYPE_BYTES[cfg["precision"]["compute"]])
    return (ssm_state_bytes(cfg) + conv) * count(cfg, "mamba")


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
    """Least time of the grouped matmuls (up and down) over ``assignments``
    (token, held expert) pairs that touched ``experts_touched`` experts: the
    larger of their FLOPs over the bf16 peak and the touched experts' weights
    over the HBM peak.  Call it once a family of programs (decode steps,
    prefill units) and add: which bound holds differs between them."""
    flops = 2.0 * assignments * expert_params(cfg)
    bytes_ = (experts_touched * expert_params(cfg)
              * DTYPE_BYTES[cfg["precision"]["compute"]])
    return max(flops / peak_flops, bytes_ / peak_bytes)


def ssd_decode_least_seconds(cfg: Dict, live_row_steps: int,
                             peak_bytes: float) -> float:
    """Least time of the fused decode step over ``live_row_steps`` (live row,
    step) pairs, all Mamba-2 layers: each ``S`` read once and written once."""
    return (live_row_steps * count(cfg, "mamba") * 2 * ssm_state_bytes(cfg)
            / peak_bytes)
