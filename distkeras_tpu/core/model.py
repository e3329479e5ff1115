"""Sequential model: a list of layer specs + a params pytree.

Replaces the reference's Keras-model handling (reference:
``distkeras/utils.py :: serialize_keras_model / deserialize_keras_model``,
which pickle ``model.to_json()`` + ``model.get_weights()``).  Here the model
*spec* is JSON-able layer configs and the *weights* are a pytree, so the whole
forward/backward is a pure jittable function — the shape XLA wants.
"""

from __future__ import annotations

import json
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import Layer, params_of, scope_names

Params = Any


class Sequential:
    """A stack of layer specs with a functional (init/apply) interface.

    Unlike Keras, the model object holds no weights: ``init`` returns the
    params pytree and ``apply`` consumes it.  ``compute_dtype`` defaults to
    bfloat16 — matmuls/convs run on the MXU in bf16 with f32 accumulation.
    """

    def __init__(self, layers: Optional[Sequence[Layer]] = None,
                 input_shape: Optional[Sequence[int]] = None,
                 compute_dtype: str = "bfloat16", name: str = "sequential"):
        self.layers: List[Layer] = list(layers) if layers else []
        self.input_shape = tuple(input_shape) if input_shape else None
        self.compute_dtype = compute_dtype
        self.name = name

    # -- construction -------------------------------------------------------
    def add(self, layer: Layer) -> "Sequential":
        self.layers.append(layer)
        return self

    @property
    def _cdtype(self):
        return jnp.dtype(self.compute_dtype)

    # -- functional core ----------------------------------------------------
    def init(self, rng, input_shape: Optional[Sequence[int]] = None) -> Params:
        """Initialize params. ``input_shape`` excludes the batch dim."""
        shape = tuple(input_shape) if input_shape else self.input_shape
        if shape is None:
            raise ValueError("input_shape required (constructor or init())")
        self.input_shape = shape
        params = []
        for layer in self.layers:
            rng, sub = jax.random.split(rng)
            p, shape = layer.init(sub, shape)
            params.append(p)
        self.output_shape = shape
        return params

    def apply(self, params: Params, x, *, train: bool = False, rng=None,
              stats_out: Optional[dict] = None, segment_ids=None):
        """Pure forward pass. Safe to jit / grad / vmap / shard_map.

        ``stats_out``: optional dict filled (at trace time) with
        ``{layer_index: new_stats}`` for stat-carrying layers (BatchNorm) when
        ``train=True`` — the train step merges these back into params via
        ``merge_stats`` after the optimizer update.

        ``segment_ids`` (B, S): sequence-packing isolation — forwarded to
        every attention-bearing layer (``takes_segment_ids``) so packed
        documents attend only within themselves (``data/packing.py``).
        Requires relative positions: an absolute additive table
        (``PositionalEmbedding``) would hand a mid-row document shifted
        position vectors — silently different training than unpacked —
        so that combination is refused.
        """
        if segment_ids is not None:
            from .layers import PositionalEmbedding
            if any(isinstance(l, PositionalEmbedding) for l in self.layers):
                raise ValueError(
                    "sequence packing (segment_ids) requires relative "
                    "positions: this model has an absolute "
                    "PositionalEmbedding table, which would give packed "
                    "documents position-shifted embeddings — build the "
                    "model with positional='rope'")
        cdtype = self._cdtype
        scopes = scope_names(self.layers)
        for i, layer in enumerate(self.layers):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            kw = ({"segment_ids": segment_ids}
                  if segment_ids is not None
                  and getattr(layer, "takes_segment_ids", False) else {})
            # a layer tied to another's parameters (``TiedHead``) has none of
            # its own and is handed that layer's
            mine = params_of(self.layers, params, i)
            with jax.named_scope(scopes[i]):
                if (train and stats_out is not None
                        and hasattr(layer, "apply_with_stats")):
                    x, new_stats = layer.apply_with_stats(
                        params[i], x, compute_dtype=cdtype, rng=sub)
                    stats_out[i] = new_stats
                else:
                    x = layer.apply(mine, x, compute_dtype=cdtype,
                                    train=train, rng=sub, **kw)
        return x

    @staticmethod
    def merge_stats(params: Params, stats: dict) -> Params:
        """Write ``{layer_index: new_stats}`` (from ``apply(stats_out=...)``)
        into a params pytree, leaving trained leaves untouched."""
        if not stats:
            return params
        out = list(params)
        for i, s in stats.items():
            out[i] = {**out[i], "stats": s}
        return out

    def has_stats(self) -> bool:
        return any(hasattr(layer, "apply_with_stats")
                   for layer in self.layers)

    def __call__(self, params, x, **kw):
        return self.apply(params, x, **kw)

    # -- keras-parity conveniences ------------------------------------------
    def predict(self, params, x, batch_size: int = 512):
        """Batched host-side inference (used by predictors.ModelPredictor)."""
        fn = jax.jit(lambda p, b: self.apply(p, b, train=False))
        outs = []
        x = np.asarray(x)
        for i in range(0, len(x), batch_size):
            outs.append(np.asarray(fn(params, x[i:i + batch_size])))
        return np.concatenate(outs, axis=0)

    def count_params(self, params) -> int:
        from .quant import QuantizedTensor
        # QuantizedTensor is one logical weight: count its .shape, not its
        # (codes + scale) component leaves
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(
                       params,
                       is_leaf=lambda x: isinstance(x, QuantizedTensor)))

    # -- (de)serialization ---------------------------------------------------
    def to_json(self) -> str:
        return json.dumps({
            "name": self.name,
            "compute_dtype": self.compute_dtype,
            "input_shape": list(self.input_shape) if self.input_shape else None,
            "layers": [layer.get_config() for layer in self.layers],
        })

    @staticmethod
    def from_json(spec: str) -> "Sequential":
        cfg = json.loads(spec)
        model = Sequential(
            [Layer.from_config(c) for c in cfg["layers"]],
            input_shape=cfg.get("input_shape"),
            compute_dtype=cfg.get("compute_dtype", "bfloat16"),
            name=cfg.get("name", "sequential"),
        )
        return model

    def get_weights(self, params) -> List[np.ndarray]:
        """Flat list of np arrays in deterministic (pytree) order —
        the wire/storage format, mirroring Keras ``model.get_weights()``."""
        return [np.asarray(w) for w in jax.tree_util.tree_leaves(params)]

    def set_weights(self, params: Params, weights: Sequence[np.ndarray]):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if len(leaves) != len(weights):
            raise ValueError(
                f"weight count mismatch: {len(leaves)} vs {len(weights)}")
        new = [jnp.asarray(w, dtype=l.dtype) for l, w in zip(leaves, weights)]
        return jax.tree_util.tree_unflatten(treedef, new)


class FittedModel:
    """A (spec, params) pair — what ``Trainer.train`` returns.

    Plays the role of the trained ``keras.Model`` the reference hands back
    (reference: ``trainers.py :: DistributedTrainer.train`` returns the PS
    center model).  Carries enough surface (predict / get_weights / save) for
    the predictor+evaluator pipeline.
    """

    def __init__(self, model: Sequential, params: Params):
        self.model = model
        self.params = params

    def predict(self, x, batch_size: int = 512):
        return self.model.predict(self.params, x, batch_size=batch_size)

    def get_weights(self):
        return self.model.get_weights(self.params)

    def set_weights(self, weights):
        self.params = self.model.set_weights(self.params, weights)
        return self

    def count_params(self):
        return self.model.count_params(self.params)

    def quantize(self) -> "FittedModel":
        """Weight-only int8 post-training quantization for serving: matmul
        kernels become (int8, per-channel scale) leaves that dequantize
        inside the existing forward/decode code (``core.quant``); predict
        and generate work unchanged at ~half the bf16 weight traffic."""
        from .quant import quantize_params
        return FittedModel(self.model, quantize_params(self.params))

    def generate(self, prompt, num_steps: int, temperature: float = 0.0,
                 rng=None, max_len=None, rolling: bool = False, **kw):
        """KV-cache autoregressive continuation (causal LMs only) — see
        ``core.decode.generate`` (``**kw`` passes through its sampling/
        stopping surface: ``top_k``, ``top_p``, ``eos_id``, ``pad_id``)."""
        from .decode import generate
        return generate(self.model, self.params, prompt, num_steps,
                        temperature=temperature, rng=rng, max_len=max_len,
                        rolling=rolling, **kw)

    def speculative_generate(self, draft: "FittedModel", prompt,
                             num_steps: int, draft_len: int = 4, **kw):
        """Decoding accelerated by a cheaper ``draft`` model — greedy by
        default; with ``temperature``/``top_k``/``top_p``/``rng`` it is
        distribution-exact speculative SAMPLING (see
        ``core.decode.speculative_generate``; ``**kw`` also takes
        ``max_len``, ``return_stats``)."""
        from .decode import speculative_generate
        return speculative_generate(self.model, self.params, draft.model,
                                    draft.params, prompt, num_steps,
                                    draft_len=draft_len, **kw)

    def beam_search(self, prompt, num_steps: int, num_beams: int = 4, **kw):
        """Deterministic top-``num_beams`` continuation search (causal LMs)
        — see ``core.decode.beam_search`` (``**kw``: ``length_penalty``,
        ``eos_id``, ``pad_id``).  Returns (tokens (B, beams, P+steps),
        scores), best beam first."""
        from .decode import beam_search
        return beam_search(self.model, self.params, prompt, num_steps,
                           num_beams=num_beams, **kw)

    def serialize(self) -> dict:
        return serialize_model(self.model, self.params)

    @staticmethod
    def deserialize(blob: dict) -> "FittedModel":
        model, params = deserialize_model(blob)
        return FittedModel(model, params)

    def save(self, path: str):
        """Persist spec+weights as .npz (final-model persistence; the
        reference's only persistence was ``model.save`` on the returned
        Keras model)."""
        write_npz_blob(path, self.serialize())

    @staticmethod
    def load(path: str) -> "FittedModel":
        return FittedModel.deserialize(read_npz_blob(path))


def write_npz_blob(path: str, blob: dict) -> None:
    """The framework's ONE npz model layout (``spec`` json bytes + ``w{i}``
    weight arrays) — shared by ``FittedModel.save`` and the process-worker
    shipping path, which writes straight from a blob without re-tracing."""
    weights = {f"w{i}": np.asarray(w) for i, w in enumerate(blob["weights"])}
    np.savez(path, spec=np.frombuffer(blob["model"].encode(),
                                      dtype=np.uint8), **weights)


def read_npz_blob(path: str) -> dict:
    with np.load(path) as z:
        spec = bytes(z["spec"]).decode()
        weights = [z[f"w{i}"] for i in range(len(z.files) - 1)]
    return {"model": spec, "weights": weights}


def serialize_model(model: Sequential, params: Params) -> dict:
    """Parity with reference ``serialize_keras_model`` (utils.py):
    returns a picklable dict {'model': json_spec, 'weights': [ndarray...]}."""
    from .quant import QuantizedTensor
    if any(isinstance(l, QuantizedTensor) for l in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor))):
        raise ValueError(
            "cannot serialize int8-quantized params (the npz/wire layout is "
            "a flat full-precision weight list): save the unquantized model "
            "and call .quantize() after load")
    return {"model": model.to_json(), "weights": model.get_weights(params)}


def deserialize_model(blob: dict) -> Tuple[Sequential, Params]:
    """Parity with reference ``deserialize_keras_model`` (utils.py)."""
    model = Sequential.from_json(blob["model"])
    if model.input_shape is None:
        raise ValueError("serialized model missing input_shape")
    params = model.init(jax.random.PRNGKey(0), model.input_shape)
    params = model.set_weights(params, blob["weights"])
    return model, params
