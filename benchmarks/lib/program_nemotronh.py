"""The one place that knows how the program builds and lays out the
Nemotron-3-Nano configuration: ``models.hybrid_lm`` from the configuration's
own keys (``hybrid_override_pattern``), and ``weights_nemotronh``'s layout
renamed into ``Sequential``'s parameter list.  The driver goes through here;
the reference never does."""

from __future__ import annotations

from typing import Any, Dict, List

from .program import import_program
from .weights_nemotronh import make_weights


def import_layers():
    """The layer classes this configuration needs of the program.  A
    program without them (the parent of the PR that added them) fails here,
    at once, before anything is built."""
    import_program()
    from distkeras_tpu.core.layers import (HybridBlock, Mamba2Mixer,  # noqa: F401
                                           MultiHeadAttention, RMSNorm,
                                           SparseMoE)
    from distkeras_tpu.models import hybrid_lm
    return hybrid_lm


def build_model(cfg: Dict):
    """The model as this chip holds it: the router scores the PUBLISHED
    number of experts, ``n_routed_experts`` of them (the first) are held."""
    hybrid_lm = import_layers()
    published = dict(cfg, n_routed_experts=int(
        cfg["published"]["n_routed_experts"]))
    return hybrid_lm(published, compute_dtype=cfg["precision"]["compute"],
                     held=(0, int(cfg["n_routed_experts"])))


#: the program's names of a part's arrays, by the reference layout's
_PART_KEYS = {
    "attn": ("mixer", "norm1", {k: k for k in ("wq", "wk", "wv", "wo")}),
    "mamba": ("mixer", "norm1", dict(
        {k: k for k in ("w_in", "conv_w", "conv_b", "dt_bias", "a_log",
                        "d_skip", "w_out")}, norm="gnorm")),
    "experts": ("ffn", "norm2", {k: k for k in (
        "router", "router_bias", "w_in", "w_out", "shared_in",
        "shared_out")}),
}


def to_program_layout(w: Dict) -> List[Any]:
    """``Sequential``'s list: Embedding, the HybridBlocks (one part each),
    RMSNorm, Dense.  The same arrays under the program's names: nothing is
    copied."""
    out: List[Any] = [{"embedding": w["embed"]}]
    for layer in w["layers"]:
        part, norm, names = _PART_KEYS[layer["kind"]]
        out.append({norm: {"scale": layer["norm"]},
                    part: {mine: layer[theirs]
                           for mine, theirs in names.items()}})
    out.append({"scale": w["final_norm"]})
    out.append({"kernel": w["head"]})
    return out


def program_params(cfg: Dict, seed: int) -> List[Any]:
    return to_program_layout(
        make_weights(cfg, seed, cfg["precision"]["params"]))


def build_engine(cfg: Dict, seed: int):
    import_layers()
    from distkeras_tpu.core.model import FittedModel
    from distkeras_tpu.serving import ServingEngine
    fitted = FittedModel(build_model(cfg), program_params(cfg, seed))
    return ServingEngine(fitted, **dict(cfg["deployment"]["engine"]))
