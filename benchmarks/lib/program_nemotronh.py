"""The one place that knows how the program builds and lays out the
Nemotron-3-Nano configuration: ``models.hybrid_lm`` from the configuration's
own keys (``hybrid_override_pattern``), and ``weights_nemotronh``'s layout
renamed into ``Sequential``'s parameter list.  The driver goes through here;
the reference never does."""

from __future__ import annotations

from typing import Any, Dict, List

from .program import import_program
from .weights_nemotronh import make_weights, weight_parts


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


def _block(layer: Dict) -> Dict:
    """One HybridBlock's parameters: a layer's arrays under the program's
    names."""
    part, norm, names = _PART_KEYS[layer["kind"]]
    return {norm: {"scale": layer["norm"]},
            part: {mine: layer[theirs] for mine, theirs in names.items()}}


def _sequence(ends: Dict, blocks: List[Dict]) -> List[Any]:
    """``Sequential``'s list: Embedding, the HybridBlocks (one part each),
    RMSNorm, Dense."""
    return ([{"embedding": ends["embed"]}] + blocks
            + [{"scale": ends["final_norm"]}, {"kernel": ends["head"]}])


def to_program_layout(w: Dict) -> List[Any]:
    """The same arrays under the program's names: nothing is copied."""
    return _sequence(w, [_block(layer) for layer in w["layers"]])


def program_params(cfg: Dict, seed: int) -> List[Any]:
    return to_program_layout(
        make_weights(cfg, seed, cfg["precision"]["params"]))


def serving_params(model, cfg: Dict, seed: int) -> List[Any]:
    """``program_params`` in the form the engine holds them
    (``store_for_serving``: an expert layer's up-projection transposed where
    the layer says so), each layer laid out as it is drawn: one original at a
    time is alive beside its transpose, never the four of them beside the
    engine's own and its state (2.1 GB of the 13.39 GB peak that PR 35
    read).  The engine's ``params`` setter takes this form as it is."""
    parts = weight_parts(cfg, seed, cfg["precision"]["params"])
    ends = next(parts)
    return _sequence(ends, [layer.store_for_serving(_block(part))[0]
                            for layer, part in zip(model.layers[1:], parts)])


def build_engine(cfg: Dict, seed: int):
    import_layers()
    from distkeras_tpu.serving import ServingEngine
    model = build_model(cfg)
    return ServingEngine((model, serving_params(model, cfg, seed)),
                         **dict(cfg["deployment"]["engine"]))
