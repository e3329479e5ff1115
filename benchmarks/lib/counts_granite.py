"""Sizes, operations and least bytes of the Granite-4.0-H configuration
(``model_type`` ``granitemoehybrid``), from its own keys.  The yardstick of
the ``.granite`` readers: everything is the LEAST the algorithm needs (every
weight once a step, the tied table once, a live row's state read once and
written once), so a share of a peak can only read low.  Checked against hand
arithmetic in ``tests/``."""

from __future__ import annotations

from typing import Dict

from .counts import DTYPE_BYTES

KINDS = {"mamba": "mamba", "attention": "attn"}


def dims(cfg: Dict) -> Dict:
    layers = int(cfg["num_hidden_layers"])
    hidden, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    m_heads, p = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    groups, state = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    inner = int(cfg["mamba_expand"]) * hidden
    assert inner == m_heads * p, (inner, m_heads, p)
    return dict(
        hidden=hidden, layers=layers,
        kinds=[KINDS[k] for k in cfg["layer_types"][:layers]],
        heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or hidden // heads),  # ``assumed``
        m_heads=m_heads, m_dim=p, groups=groups, state=state,
        conv=int(cfg["mamba_d_conv"]), inner=inner,
        conv_dim=inner + 2 * groups * state,
        mlp_dim=int(cfg["shared_intermediate_size"]),
        vocab=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
        embed_mult=float(cfg["embedding_multiplier"]),
        resid_mult=float(cfg["residual_multiplier"]),
        attn_mult=float(cfg["attention_multiplier"]),
        logits_div=float(cfg["logits_scaling"]))


def count(cfg: Dict, kind: str) -> int:
    return sum(k == kind for k in dims(cfg)["kinds"])


def mixer_params(cfg: Dict, kind: str) -> int:
    """Matmul weights of a Mamba-2 or attention mixer."""
    d = dims(cfg)
    h = d["hidden"]
    if kind == "attn":
        inner, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
        return 2 * h * inner + 2 * h * kv               # q, o; k, v
    return (h * (d["inner"] + d["conv_dim"] + d["m_heads"])
            + d["inner"] * h)                           # in; out


def mlp_params(cfg: Dict) -> int:
    """A block's gated MLP: gate and up side by side, and down."""
    d = dims(cfg)
    return 3 * d["hidden"] * d["mlp_dim"]


def matmul_params_per_layer(cfg: Dict, kind: str) -> int:
    return mixer_params(cfg, kind) + mlp_params(cfg)


def matmul_params(cfg: Dict) -> int:
    """Every weight a token is multiplied through: the blocks, and the tied
    table ONCE (as the head; as the embedding it is looked up)."""
    d = dims(cfg)
    return (sum(matmul_params_per_layer(cfg, k) for k in d["kinds"])
            + d["hidden"] * d["vocab"])


def small_params(cfg: Dict, kind: str) -> int:
    """What a block holds beside its matmul weights: two norms; a Mamba-2
    mixer's convolution and bias, dt_bias, A_log, D and the gated norm's
    scale."""
    d = dims(cfg)
    extra = ((d["conv"] + 1) * d["conv_dim"] + 3 * d["m_heads"] + d["inner"]
             if kind == "mamba" else 0)
    return 2 * d["hidden"] + extra


def total_params(cfg: Dict) -> int:
    """Every parameter of the model: the table counts once (tied)."""
    d = dims(cfg)
    return (matmul_params(cfg) + d["hidden"]
            + sum(small_params(cfg, k) for k in d["kinds"]))


def ssm_state_flops_per_token(cfg: Dict) -> float:
    """One layer's recurrence for one token: decay (1), the rank-one write
    (2) and the read ``S C`` (2) per state element."""
    d = dims(cfg)
    return 5.0 * d["m_heads"] * d["m_dim"] * d["state"]


def flops_per_token(cfg: Dict, context: float, with_head: bool) -> float:
    """Forward of one token that attends ``context`` positions: 2 x the
    matmul weights of every block, the attention over its context in the
    attention layers, the recurrence in the Mamba-2 layers, and the head if
    its logits are needed."""
    d = dims(cfg)
    per = 0.0
    for kind in d["kinds"]:
        per += 2.0 * matmul_params_per_layer(cfg, kind)
        if kind == "attn":
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


def decode_least_bytes(cfg: Dict, steps: int, state_bytes_moved: float,
                       context_positions: float) -> float:
    """Least HBM traffic of ``steps`` decode steps: every block's weights
    and the table once a step, the live rows' recurrent state read and
    written (``state_bytes_moved``: the engine's own counter, or live rows x
    2 x :func:`recurrent_state_bytes`), and the keys and values of every
    attended position."""
    item = DTYPE_BYTES[cfg["precision"]["compute"]]
    return (steps * matmul_params(cfg) * item + state_bytes_moved
            + context_positions * kv_bytes_per_token(cfg))


def ssd_decode_least_seconds(cfg: Dict, live_row_steps: float,
                             peak_bytes: float) -> float:
    """Least time of the fused decode step over ``live_row_steps`` (live row,
    step) pairs, all Mamba-2 layers: each ``S`` read once and written once."""
    return (live_row_steps * count(cfg, "mamba") * 2 * ssm_state_bytes(cfg)
            / peak_bytes)
