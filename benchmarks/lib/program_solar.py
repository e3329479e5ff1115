"""The one place that knows how the program builds and lays out the
Solar-Open2 configuration: ``models.hybrid_lm`` from the configuration's own
keys, and ``weights_solar``'s layout renamed into ``Sequential``'s parameter
list.  The driver goes through here; the reference never does."""

from __future__ import annotations

from typing import Any, Dict, List

from .program import import_program
from .weights_solar import make_weights


def import_layers():
    """The layer classes this configuration needs of the program.  A
    program without them (the parent of the PR that added them) fails here,
    at once, before anything is built."""
    import_program()
    from distkeras_tpu.core.layers import (GatedAttention, HybridBlock,  # noqa: F401
                                           KimiDeltaAttention, RMSNorm,
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


_MIXER_KEYS = {
    "gqa": ("wq", "wk", "wv", "wg", "wo"),
    "kda": ("wq", "wk", "wv", "wo", "wb", "wf_down", "wf_up", "wg_down",
            "wg_up", "a_log", "dt_bias", "o_norm", "conv_q", "conv_k",
            "conv_v"),
}


def to_program_layout(w: Dict) -> List[Any]:
    """``Sequential``'s list: Embedding, the HybridBlocks, RMSNorm, Dense.
    The same arrays under the program's names: nothing is copied."""
    out: List[Any] = [{"embedding": w["embed"]}]
    for layer in w["layers"]:
        out.append({
            "norm1": {"scale": layer["norm1"]},
            "mixer": {k: layer[k] for k in _MIXER_KEYS[layer["kind"]]},
            "norm2": {"scale": layer["norm2"]},
            "ffn": {k: layer[k] for k in ("router", "w_in", "w_out",
                                          "shared_in", "shared_out")},
        })
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
