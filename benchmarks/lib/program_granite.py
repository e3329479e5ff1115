"""The one place that knows how the program builds and lays out the
Granite-4.0-H configuration: ``models.hybrid_lm`` from the configuration's
own keys (``layer_types``), and ``weights_granite``'s layout renamed into
``Sequential``'s parameter list.  The driver goes through here; the reference
never does."""

from __future__ import annotations

from typing import Any, Dict, List

from .program import import_program
from .weights_granite import weight_parts


def import_layers():
    """The layer classes this configuration needs of the program.  A
    program without them (the parent of the PR that added them) fails here,
    at once, before anything is built."""
    import_program()
    from distkeras_tpu.core.layers import (GatedMLP, HybridBlock,  # noqa: F401
                                           Mamba2Mixer, MultiHeadAttention,
                                           RMSNorm, TiedHead)
    from distkeras_tpu.models import hybrid_lm
    return hybrid_lm


def build_model(cfg: Dict):
    """The model as published: every key of the configuration as it reads."""
    return import_layers()(cfg, compute_dtype=cfg["precision"]["compute"])


#: the program's names of a mixer's arrays, by the reference layout's
_MIXER_KEYS = {
    "attn": {k: k for k in ("wq", "wk", "wv", "wo")},
    "mamba": dict({k: k for k in ("w_in", "conv_w", "conv_b", "dt_bias",
                                  "a_log", "d_skip", "w_out")},
                  norm="gnorm"),
}


def _block(layer: Dict) -> Dict:
    """One HybridBlock's parameters: a layer's arrays under the program's
    names."""
    names = _MIXER_KEYS[layer["kind"]]
    return {"norm1": {"scale": layer["norm"]},
            "mixer": {mine: layer[theirs] for mine, theirs in names.items()},
            "norm2": {"scale": layer["norm2"]},
            "ffn": {"w_in": layer["mlp_in"], "w_out": layer["mlp_out"]}}


def program_params(cfg: Dict, seed: int) -> List[Any]:
    """``Sequential``'s list: Embedding, the HybridBlocks, RMSNorm, and the
    tied head's EMPTY entry (the table is the embedding's: one array).
    Nothing is copied."""
    parts = weight_parts(cfg, seed, cfg["precision"]["params"])
    ends = next(parts)
    return ([{"embedding": ends["embed"]}] + [_block(p) for p in parts]
            + [{"scale": ends["final_norm"]}, {}])


def build_engine(cfg: Dict, seed: int):
    import_layers()
    from distkeras_tpu.serving import ServingEngine
    return ServingEngine((build_model(cfg), program_params(cfg, seed)),
                         **dict(cfg["deployment"]["engine"]))
