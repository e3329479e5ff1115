"""The one place that knows how the system under test is built and laid out:
``transformer_lm`` from a configuration's widths, and the stacked weights of
``weights.py`` rearranged into its parameter list.  Both drivers go through
here; the reference never does."""

from __future__ import annotations

import sys
from typing import Any, Dict, List

import jax

from .counts import widths
from .manifest import ROOT
from .weights import make_weights


def import_program():
    """The package under test, from the checkout this benchmark sits in."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import distkeras_tpu  # noqa: F401
    return distkeras_tpu


def build_model(cfg: Dict):
    import_program()
    from distkeras_tpu.models import transformer_lm
    w = widths(cfg)
    return transformer_lm(
        vocab_size=w["vocab"], seq_len=w["positions"], d_model=w["d"],
        num_heads=w["heads"], num_layers=w["layers"], mlp_dim=w["inner"],
        compute_dtype=cfg["precision"]["compute"])


def to_program_layout(w: Dict) -> List[Any]:
    """Stacked weights (or anything shaped like them: gradients, moments) as
    ``Sequential``'s list: Embedding, PositionalEmbedding, the blocks,
    LayerNormalization, Dense."""
    layers = w["wq"].shape[0]
    out: List[Any] = [{"embedding": w["wte"]}, {"embedding": w["wpe"]}]
    for i in range(layers):
        out.append({
            "ln1": {"scale": w["ln1_g"][i], "offset": w["ln1_b"][i]},
            "attn": {"wq": w["wq"][i], "wk": w["wk"][i], "wv": w["wv"][i],
                     "wo": w["wo"][i], "bq": w["bq"][i], "bk": w["bk"][i],
                     "bv": w["bv"][i], "bo": w["bo"][i]},
            "ln2": {"scale": w["ln2_g"][i], "offset": w["ln2_b"][i]},
            "mlp_w1": w["w1"][i], "mlp_b1": w["b1"][i],
            "mlp_w2": w["w2"][i], "mlp_b2": w["b2"][i],
        })
    out.append({"scale": w["lnf_g"], "offset": w["lnf_b"]})
    out.append({"kernel": w["head_w"], "bias": w["head_b"]})
    return out


_to_program_layout_jit = jax.jit(to_program_layout)


def program_params(cfg: Dict, seed: int) -> List[Any]:
    """The program's parameter list from ``seed``, on the device, in the type
    the configuration states for parameters."""
    return _to_program_layout_jit(
        make_weights(cfg, seed, cfg["precision"]["params"]))


def use_compile_cache() -> str:
    """jax's persistent cache at the program's fixed place inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program
    however quick its compile."""
    import_program()
    from distkeras_tpu.utils import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
