"""Layer system for distkeras_tpu.

TPU-first design notes
----------------------
Layers are *declarative specs*: lightweight Python objects holding only static
configuration (shapes, strides, activation names).  Parameters live outside the
layer in a pytree, so the whole forward pass is a pure function
``apply(params, x)`` that JAX can trace once and XLA can fuse aggressively.

This replaces the reference's reliance on Keras layer objects with mutable
weights (reference: ``distkeras/utils.py :: serialize_keras_model`` pickles a
Keras model's config + weights; here the spec *is* the config and the params
pytree *is* the weights).

All matmuls/convs run in a configurable ``compute_dtype`` (default bfloat16 on
TPU) with float32 parameters and float32 accumulation via
``preferred_element_type`` — this keeps the MXU fed without fp32 conversion
costs on the HBM side.  (Convs route through ``_conv_f32_acc``: jax 0.9's
conv transpose rule can't differentiate the upcast, so the f32-accumulating
conv carries a custom VJP — don't add ``preferred_element_type`` to a conv
call directly.)
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # per-layer params: dict of arrays (possibly empty)

_ACTIVATIONS = {
    "linear": lambda x: x,
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "softmax": lambda x: jax.nn.softmax(x, axis=-1),
    "log_softmax": lambda x: jax.nn.log_softmax(x, axis=-1),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "elu": jax.nn.elu,
    "leaky_relu": jax.nn.leaky_relu,
    "softplus": jax.nn.softplus,
}


def get_activation(name: Optional[str]):
    if name is None:
        return _ACTIVATIONS["linear"]
    if callable(name):
        return name
    try:
        return _ACTIVATIONS[name]
    except KeyError:
        raise ValueError(
            f"Unknown activation {name!r}; known: {sorted(_ACTIVATIONS)}"
        ) from None


def _apply_activation(name, x):
    # softmax-family must run in f32 for numerical stability under bf16 compute.
    if name in ("softmax", "log_softmax", "sigmoid"):
        return get_activation(name)(x.astype(jnp.float32))
    return get_activation(name)(x)


# ---------------------------------------------------------------------------
# initializers (Keras-compatible names so serialized configs round-trip)
# ---------------------------------------------------------------------------

def _fans(shape: Sequence[int]) -> Tuple[int, int]:
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv kernels: (kh, kw, cin, cout)
    receptive = int(np.prod(shape[:-2]))
    return shape[-2] * receptive, shape[-1] * receptive


def init_weight(rng, shape, scheme: str = "glorot_uniform", dtype=jnp.float32):
    fan_in, fan_out = _fans(shape)
    if scheme == "glorot_uniform":
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(rng, shape, dtype, -limit, limit)
    if scheme == "glorot_normal":
        std = math.sqrt(2.0 / (fan_in + fan_out))
        return std * jax.random.normal(rng, shape, dtype)
    if scheme == "he_uniform":
        limit = math.sqrt(6.0 / fan_in)
        return jax.random.uniform(rng, shape, dtype, -limit, limit)
    if scheme == "he_normal":
        std = math.sqrt(2.0 / fan_in)
        return std * jax.random.normal(rng, shape, dtype)
    if scheme == "zeros":
        return jnp.zeros(shape, dtype)
    if scheme == "ones":
        return jnp.ones(shape, dtype)
    raise ValueError(f"Unknown initializer {scheme!r}")


# ---------------------------------------------------------------------------
# Layer base
# ---------------------------------------------------------------------------

class Layer:
    """Base layer spec.

    Subclasses implement:
      - ``init(rng, in_shape) -> (params, out_shape)`` where shapes exclude the
        leading batch dim;
      - ``apply(params, x, *, compute_dtype, train, rng) -> y``.
    """

    #: class-level registry name (set via __init_subclass__)
    kind: str = "Layer"

    _REGISTRY: Dict[str, type] = {}

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        cls.kind = cls.__name__
        Layer._REGISTRY[cls.__name__] = cls

    # -- config (serialization) --------------------------------------------
    def get_config(self) -> Dict[str, Any]:
        cfg = {k: v for k, v in self.__dict__.items() if not k.startswith("_")}
        cfg["kind"] = self.kind
        return cfg

    @staticmethod
    def from_config(cfg: Dict[str, Any]) -> "Layer":
        cfg = dict(cfg)
        kind = cfg.pop("kind")
        cls = Layer._REGISTRY[kind]
        obj = cls.__new__(cls)
        # JSON round-trips tuples (kernel_size, strides, target_shape, ...)
        # to lists; shape fields must come back as tuples.
        obj.__dict__.update({k: tuple(v) if isinstance(v, list) else v
                             for k, v in cfg.items()})
        return obj

    # -- shape/params -------------------------------------------------------
    def init(self, rng, in_shape):  # pragma: no cover - abstract
        raise NotImplementedError

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):  # pragma: no cover - abstract
        raise NotImplementedError

    def store_for_serving(self, params):
        """``(params, n)``: this layer's parameters as a ``ServingEngine``
        should hold them, and how many weights of them are held in another
        form than ``init`` makes.  Idempotent; what comes back is read by
        the layer's own forward and by nothing else (``get_weights``,
        checkpoints and training keep ``init``'s form).  Most layers are
        served as they are."""
        return params, 0

    def __repr__(self):
        cfg = {k: v for k, v in self.get_config().items() if k != "kind"}
        args = ", ".join(f"{k}={v!r}" for k, v in cfg.items())
        return f"{self.kind}({args})"


# ---------------------------------------------------------------------------
# Core layers
# ---------------------------------------------------------------------------

class Dense(Layer):
    """Fully connected layer (reference models are MLP-heavy:
    SURVEY.md §2.1 row 23 — MNIST MLP, ATLAS Higgs tabular)."""

    def __init__(self, units: int, activation: Optional[str] = None,
                 use_bias: bool = True, kernel_init: str = "glorot_uniform"):
        self.units = int(units)
        self.activation = activation
        self.use_bias = use_bias
        self.kernel_init = kernel_init

    def init(self, rng, in_shape):
        (d,) = in_shape[-1:]
        params = {"kernel": init_weight(rng, (d, self.units), self.kernel_init)}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.units,), jnp.float32)
        return params, tuple(in_shape[:-1]) + (self.units,)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        k = params["kernel"].astype(compute_dtype)
        # rows flattened up to the result, so that it is born (rows, units)
        # row-major: a (batch, seq, units) dot_general comes out of the
        # TPU compiler sequence-minor, and a consumer that needs rows of
        # units (the fused cross-entropy over an LM head's logits) then
        # pays a relayout copy of the whole result.  The bias joins before
        # the reshape: after it XLA folds the reshape back into the product
        y = jax.lax.dot_general(
            x.astype(compute_dtype).reshape(-1, x.shape[-1]), k,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if self.use_bias:
            y = y + params["bias"]
        return _apply_activation(self.activation, y).reshape(
            x.shape[:-1] + (self.units,))


def _conv_f32_acc(x, k, strides, padding):
    """Convolution with low-precision operands and a float32-accumulated
    *forward* output.

    jax 0.9's conv transpose rule rejects ``preferred_element_type``
    upcasting under grad, so the f32-accumulating forward gets a custom VJP
    that differentiates the plain same-dtype conv.  Gradient contract: the
    backward convs therefore run entirely in ``compute_dtype`` (the
    cotangent is rounded once to ``compute_dtype``; on TPU the MXU still
    accumulates partial products in f32 internally, with bf16 rounding at
    conv boundaries) — standard mixed-precision training behavior, but
    note it is *less* precise than Dense's grads, which keep
    ``preferred_element_type=f32`` end to end.
    """
    dn = ("NHWC", "HWIO", "NHWC")

    @jax.custom_vjp
    def conv(x, k):
        return jax.lax.conv_general_dilated(
            x, k, strides, padding, dimension_numbers=dn,
            preferred_element_type=jnp.float32)

    def fwd(x, k):
        return conv(x, k), (x, k)

    def bwd(res, g):
        x, k = res
        _, vjp = jax.vjp(
            lambda a, b: jax.lax.conv_general_dilated(
                a, b, strides, padding, dimension_numbers=dn), x, k)
        return vjp(g.astype(x.dtype))

    conv.defvjp(fwd, bwd)
    return conv(x, k)


class Conv2D(Layer):
    """2-D convolution, NHWC layout (TPU-native; XLA tiles it onto the MXU)."""

    def __init__(self, filters: int, kernel_size=3, strides=1,
                 padding: str = "SAME", activation: Optional[str] = None,
                 use_bias: bool = True, kernel_init: str = "he_normal"):
        self.filters = int(filters)
        self.kernel_size = tuple(np.broadcast_to(kernel_size, (2,)).tolist())
        self.strides = tuple(np.broadcast_to(strides, (2,)).tolist())
        self.padding = padding.upper()
        self.activation = activation
        self.use_bias = use_bias
        self.kernel_init = kernel_init

    def init(self, rng, in_shape):
        h, w, cin = in_shape
        kh, kw = self.kernel_size
        params = {
            "kernel": init_weight(rng, (kh, kw, cin, self.filters),
                                  self.kernel_init)
        }
        if self.use_bias:
            params["bias"] = jnp.zeros((self.filters,), jnp.float32)
        out = jax.eval_shape(
            lambda x, k: jax.lax.conv_general_dilated(
                x, k, self.strides, self.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC")),
            jax.ShapeDtypeStruct((1, h, w, cin), jnp.float32),
            jax.ShapeDtypeStruct((kh, kw, cin, self.filters), jnp.float32),
        )
        return params, tuple(out.shape[1:])

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        y = _conv_f32_acc(x.astype(compute_dtype),
                          params["kernel"].astype(compute_dtype),
                          self.strides, self.padding)
        if self.use_bias:
            y = y + params["bias"]
        return _apply_activation(self.activation, y)


class MaxPooling2D(Layer):
    def __init__(self, pool_size=2, strides=None, padding: str = "VALID"):
        self.pool_size = tuple(np.broadcast_to(pool_size, (2,)).tolist())
        self.strides = (tuple(np.broadcast_to(strides, (2,)).tolist())
                        if strides is not None else self.pool_size)
        self.padding = padding.upper()

    def init(self, rng, in_shape):
        h, w, c = in_shape
        out = jax.eval_shape(
            lambda x: self.apply({}, x, compute_dtype=jnp.float32),
            jax.ShapeDtypeStruct((1, h, w, c), jnp.float32))
        return {}, tuple(out.shape[1:])

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        dims = (1,) + self.pool_size + (1,)
        strides = (1,) + self.strides + (1,)
        return jax.lax.reduce_window(
            x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else
            jnp.iinfo(x.dtype).min,
            jax.lax.max, dims, strides, self.padding)


class AveragePooling2D(Layer):
    def __init__(self, pool_size=2, strides=None, padding: str = "VALID"):
        self.pool_size = tuple(np.broadcast_to(pool_size, (2,)).tolist())
        self.strides = (tuple(np.broadcast_to(strides, (2,)).tolist())
                        if strides is not None else self.pool_size)
        self.padding = padding.upper()

    def init(self, rng, in_shape):
        h, w, c = in_shape
        out = jax.eval_shape(
            lambda x: self.apply({}, x, compute_dtype=jnp.float32),
            jax.ShapeDtypeStruct((1, h, w, c), jnp.float32))
        return {}, tuple(out.shape[1:])

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        dims = (1,) + self.pool_size + (1,)
        strides = (1,) + self.strides + (1,)
        summed = jax.lax.reduce_window(
            x, jnp.zeros((), x.dtype), jax.lax.add, dims, strides,
            self.padding)
        return summed / float(np.prod(self.pool_size))


class GlobalAveragePooling2D(Layer):
    def __init__(self):
        pass

    def init(self, rng, in_shape):
        return {}, (in_shape[-1],)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        return jnp.mean(x, axis=(1, 2))


class Flatten(Layer):
    def __init__(self):
        pass

    def init(self, rng, in_shape):
        return {}, (int(np.prod(in_shape)),)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        return x.reshape(x.shape[0], -1)


class Reshape(Layer):
    def __init__(self, target_shape: Sequence[int]):
        self.target_shape = tuple(int(d) for d in target_shape)

    def init(self, rng, in_shape):
        if int(np.prod(in_shape)) != int(np.prod(self.target_shape)):
            raise ValueError(
                f"Cannot reshape {in_shape} to {self.target_shape}")
        return {}, self.target_shape

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        return x.reshape((x.shape[0],) + self.target_shape)


class Activation(Layer):
    def __init__(self, activation: str):
        self.activation = activation

    def init(self, rng, in_shape):
        return {}, tuple(in_shape)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        return _apply_activation(self.activation, x)


def _dropout(rng, rate: float, x, train: bool):
    """Inverted dropout; identity at inference (shared by Dropout and
    TransformerBlock so the semantics live in one place)."""
    if not train or rate <= 0.0:
        return x
    if rng is None:
        raise ValueError("Dropout in train mode requires an rng")
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


class Dropout(Layer):
    """Inverted dropout; identity at inference. Uses the functional rng threaded
    through ``Model.apply`` (no global RNG state — jit/scan friendly)."""

    def __init__(self, rate: float):
        self.rate = float(rate)

    def init(self, rng, in_shape):
        return {}, tuple(in_shape)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        return _dropout(rng, self.rate, x, train)


class BatchNormalization(Layer):
    """Batch norm with functional running stats.

    The running (mean, var) live in the params pytree under ``"stats"``.
    Apply stays pure: in train mode the layer normalizes with *batch*
    statistics and, through ``apply_with_stats``, returns the EMA-updated
    running stats as aux; the train step merges them back into the params
    pytree after the optimizer update (``Sequential.apply(..., stats_out=)``
    collects them, ``model.merge_stats`` writes them).  The optimizer masks
    the ``"stats"`` subtree out, so stats are carried, never trained.
    """

    def __init__(self, momentum: float = 0.99, epsilon: float = 1e-3):
        self.momentum = float(momentum)
        self.epsilon = float(epsilon)

    def init(self, rng, in_shape):
        c = in_shape[-1]
        params = {
            "scale": jnp.ones((c,), jnp.float32),
            "offset": jnp.zeros((c,), jnp.float32),
            # stats are non-trained; optimizer masks them out (see Model)
            "stats": {
                "mean": jnp.zeros((c,), jnp.float32),
                "var": jnp.ones((c,), jnp.float32),
            },
        }
        return params, tuple(in_shape)

    def _norm(self, params, x, train: bool):
        """Returns (y, new_stats); new_stats is None in eval mode."""
        x32 = x.astype(jnp.float32)
        new_stats = None
        if train:
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(x32, axis=axes)
            var = jnp.var(x32, axis=axes)
            m = self.momentum
            new_stats = jax.lax.stop_gradient({
                "mean": m * params["stats"]["mean"] + (1.0 - m) * mean,
                "var": m * params["stats"]["var"] + (1.0 - m) * var,
            })
        else:
            mean = params["stats"]["mean"]
            var = params["stats"]["var"]
        y = (x32 - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y * params["scale"] + params["offset"]
        return y.astype(x.dtype), new_stats

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        return self._norm(params, x, train)[0]

    def apply_with_stats(self, params, x, *, compute_dtype=jnp.bfloat16,
                         rng=None):
        """Train-mode forward that also returns the EMA-updated running
        stats (keras semantics: moving = momentum·moving + (1−momentum)·batch,
        biased batch variance)."""
        return self._norm(params, x, True)


class LayerNormalization(Layer):
    """Layer norm over the trailing dim, f32 arithmetic (bf16-safe)."""

    def __init__(self, epsilon: float = 1e-5):
        self.epsilon = float(epsilon)

    def init(self, rng, in_shape):
        c = in_shape[-1]
        return {"scale": jnp.ones((c,), jnp.float32),
                "offset": jnp.zeros((c,), jnp.float32)}, tuple(in_shape)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.epsilon)
        return (y * params["scale"] + params["offset"]).astype(x.dtype)


class PositionalEmbedding(Layer):
    """Learned additive positional embedding for (B, S, D) inputs."""

    def __init__(self, max_len: int):
        self.max_len = int(max_len)

    def init(self, rng, in_shape):
        s, d = in_shape
        if s > self.max_len:
            raise ValueError(f"sequence {s} exceeds max_len {self.max_len}")
        params = {"embedding": 0.02 * jax.random.normal(
            rng, (self.max_len, d), jnp.float32)}
        return params, tuple(in_shape)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        s = x.shape[1]
        return x + params["embedding"][:s].astype(x.dtype)


def _project(x, kernel, bias, compute_dtype):
    y = jax.lax.dot_general(
        x.astype(compute_dtype), kernel.astype(compute_dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias
    return y


def _validate_window(window: int, causal: bool) -> int:
    """Eager attention_window validation for the layers — delegates to the
    one shared rule in ``ops.attention.validate_window`` (which the ops
    re-apply at trace time)."""
    from ..ops.attention import validate_window
    return validate_window(window, causal)


class MultiHeadAttention(Layer):
    """Multi-head self-attention on (B, S, D) inputs.

    The score/softmax path runs through ``ops.attention`` (XLA fusion or the
    Pallas flash kernel on TPU).  No reference counterpart — part of the
    long-context layer (SURVEY.md §2.3 marks SP/attention absent upstream).

    ``num_kv_heads`` < ``num_heads`` gives grouped-query attention (GQA;
    ``num_kv_heads=1`` is multi-query): the k/v projections shrink to
    ``num_kv_heads * key_dim`` columns, cutting KV projection FLOPs/params
    and the decode-time KV cache by ``num_heads / num_kv_heads``.
    """

    #: class-level defaults so older serialized configs (which lack these
    #: fields; from_config bypasses __init__) deserialize as classic MHA
    num_kv_heads: Optional[int] = None  # None = same as num_heads
    attention_window: Optional[int] = None  # None = full causal context
    rope: bool = False  # rotary position embeddings on q/k
    rope_theta: float = 10000.0  # RoPE base (raise via ntk_theta to extend)
    rope_scale: float = 1.0      # linear position-interpolation factor
    #: ``out * sigmoid(x @ wg)`` before the output projection
    #: (``GatedAttention`` sets it)
    output_gate: bool = False
    #: what the scores are multiplied by; None = ``key_dim ** -0.5``
    score_scale: Optional[float] = None
    #: what a cached step keeps for this mixer (``core/decode.py``)
    state_kind = "kv"
    #: the ``jax.named_scope`` a block runs this mixer under
    scope = "attn"

    def __init__(self, num_heads: int, key_dim: int, causal: bool = False,
                 use_bias: bool = True, attention_impl: Optional[str] = None,
                 num_kv_heads: Optional[int] = None,
                 attention_window: Optional[int] = None,
                 rope: bool = False, rope_theta: float = 10000.0,
                 rope_scale: float = 1.0,
                 score_scale: Optional[float] = None):
        self.num_heads = int(num_heads)
        self.key_dim = int(key_dim)  # per-head dim
        self.causal = bool(causal)
        self.use_bias = bool(use_bias)
        self.attention_impl = attention_impl
        if score_scale is not None:
            self.score_scale = float(score_scale)
        if num_kv_heads is not None:
            self.num_kv_heads = int(num_kv_heads)
            if self.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"num_heads={self.num_heads} not divisible by "
                    f"num_kv_heads={self.num_kv_heads}")
        if attention_window is not None:
            self.attention_window = _validate_window(attention_window,
                                                     causal)
        if rope:
            from ..ops.rope import validate_rope_dim
            validate_rope_dim(self.key_dim)
            self.rope = True
        if rope_theta != 10000.0 or rope_scale != 1.0:
            if not rope:
                # the knobs only feed apply_rope; silently ignoring them
                # would hide a config mistake
                raise ValueError(
                    f"rope_theta={rope_theta}/rope_scale={rope_scale} set "
                    "but rope=False — pass rope=True to enable rotary "
                    "embeddings, or drop the knobs")
            from ..ops.rope import validate_rope_scaling
            self.rope_theta, self.rope_scale = validate_rope_scaling(
                rope_theta, rope_scale)

    def _kv_heads(self) -> int:
        return (self.num_kv_heads if self.num_kv_heads is not None
                else self.num_heads)

    def init(self, rng, in_shape):
        s, d = in_shape
        inner = self.num_heads * self.key_dim
        inner_kv = self._kv_heads() * self.key_dim
        ks = jax.random.split(rng, 4)
        params = {
            "wq": init_weight(ks[0], (d, inner)),
            "wk": init_weight(ks[1], (d, inner_kv)),
            "wv": init_weight(ks[2], (d, inner_kv)),
            "wo": init_weight(ks[3], (inner, d)),
        }
        if self.output_gate:
            params["wg"] = init_weight(jax.random.fold_in(rng, 4),
                                       (d, inner))
        if self.use_bias:
            params.update(bq=jnp.zeros((inner,), jnp.float32),
                          bk=jnp.zeros((inner_kv,), jnp.float32),
                          bv=jnp.zeros((inner_kv,), jnp.float32),
                          bo=jnp.zeros((d,), jnp.float32))
        return params, tuple(in_shape)

    #: Sequential.apply threads a packed batch's segment ids to layers
    #: that declare this (see data/packing.py)
    takes_segment_ids = True

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None, segment_ids=None):
        from ..ops.attention import attention
        b, s, _ = x.shape
        dh = self.key_dim

        def proj(name, heads):
            bias = params.get("b" + name[1]) if self.use_bias else None
            y = _project(x, params[name], bias, compute_dtype)
            return y.astype(compute_dtype).reshape(b, s, heads, dh)

        q = proj("wq", self.num_heads)
        k = proj("wk", self._kv_heads())
        v = proj("wv", self._kv_heads())
        if self.rope:
            from ..ops.rope import apply_rope
            pos = jnp.arange(s)
            q = apply_rope(q, pos, self.rope_theta, self.rope_scale)
            k = apply_rope(k, pos, self.rope_theta, self.rope_scale)
        with jax.named_scope("attn_core"):
            out = attention(q, k, v, scale=self.score_scale,
                            causal=self.causal, impl=self.attention_impl,
                            window=self.attention_window,
                            segment_ids=segment_ids)
        out = out.reshape(b, s, self.num_heads * dh)
        out = self.gate(params, x, out, compute_dtype)
        bias_o = params.get("bo") if self.use_bias else None
        return _project(out, params["wo"], bias_o, compute_dtype)

    def gate(self, params, x, out, compute_dtype):
        """The output gate, where the layer has one: the heads' outputs
        (B, S, H * Dh) times ``sigmoid(x @ wg)``, elementwise, in float32."""
        if not self.output_gate:
            return out
        with jax.named_scope("attn_gate"):
            g = jax.nn.sigmoid(_project(x, params["wg"], None, compute_dtype))
            return (out.astype(jnp.float32) * g).astype(out.dtype)


class TransformerBlock(Layer):
    """Pre-LN transformer block: LN → MHA → residual, LN → MLP → residual.

    Self-contained params (no nested Layer objects) so the spec stays
    JSON-serializable like every other layer.
    """

    #: class-level defaults mirror MultiHeadAttention (older configs)
    num_kv_heads: Optional[int] = None
    attention_window: Optional[int] = None
    rope: bool = False
    rope_theta: float = 10000.0
    rope_scale: float = 1.0

    def __init__(self, num_heads: int, key_dim: int, mlp_dim: int,
                 dropout: float = 0.0, causal: bool = False,
                 activation: str = "gelu",
                 attention_impl: Optional[str] = None,
                 num_kv_heads: Optional[int] = None,
                 attention_window: Optional[int] = None,
                 rope: bool = False, rope_theta: float = 10000.0,
                 rope_scale: float = 1.0):
        self.num_heads = int(num_heads)
        self.key_dim = int(key_dim)
        self.mlp_dim = int(mlp_dim)
        self.dropout = float(dropout)
        self.causal = bool(causal)
        self.activation = activation
        self.attention_impl = attention_impl
        if num_kv_heads is not None:
            self.num_kv_heads = int(num_kv_heads)
        if attention_window is not None:
            self.attention_window = _validate_window(attention_window,
                                                     causal)
        if rope:
            from ..ops.rope import validate_rope_dim
            validate_rope_dim(self.key_dim)  # eager, like MultiHeadAttention
            self.rope = True
        if rope_theta != 10000.0 or rope_scale != 1.0:
            if not rope:
                raise ValueError(
                    f"rope_theta={rope_theta}/rope_scale={rope_scale} set "
                    "but rope=False — pass rope=True to enable rotary "
                    "embeddings, or drop the knobs")
            from ..ops.rope import validate_rope_scaling
            self.rope_theta, self.rope_scale = validate_rope_scaling(
                rope_theta, rope_scale)

    def _mha(self) -> MultiHeadAttention:
        return MultiHeadAttention(self.num_heads, self.key_dim,
                                  causal=self.causal,
                                  attention_impl=self.attention_impl,
                                  num_kv_heads=self.num_kv_heads,
                                  attention_window=self.attention_window,
                                  rope=self.rope,
                                  rope_theta=self.rope_theta,
                                  rope_scale=self.rope_scale)

    def init(self, rng, in_shape):
        s, d = in_shape
        k_ln1, k_attn, k_ln2, k_m1, k_m2 = jax.random.split(rng, 5)
        ln = LayerNormalization()
        attn_params, _ = self._mha().init(k_attn, in_shape)
        params = {
            "ln1": ln.init(k_ln1, in_shape)[0],
            "attn": attn_params,
            "ln2": ln.init(k_ln2, in_shape)[0],
            "mlp_w1": init_weight(k_m1, (d, self.mlp_dim)),
            "mlp_b1": jnp.zeros((self.mlp_dim,), jnp.float32),
            "mlp_w2": init_weight(k_m2, (self.mlp_dim, d)),
            "mlp_b2": jnp.zeros((d,), jnp.float32),
        }
        return params, tuple(in_shape)

    takes_segment_ids = True
    state_kind = "kv"
    #: what the cached step and the serving engine ask of a block
    #: (``HybridBlock`` answers from its parts)
    routes_tokens = False
    wants_token_mask = False
    int8_weights = True

    def mixer(self) -> MultiHeadAttention:
        return self._mha()

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None, segment_ids=None):
        def full(mha, p, h):
            return mha.apply(p, h, compute_dtype=compute_dtype, train=train,
                             rng=None, segment_ids=segment_ids)
        return self.run(params, x, full, compute_dtype=compute_dtype,
                        train=train, rng=rng)[0]

    def run(self, params, x, mix, *, compute_dtype=jnp.bfloat16,
            train=False, rng=None, token_mask=None):
        """The block around its mixer: ``mix(mixer, mixer params, normed
        input) -> mixed`` is the full-sequence attention (``apply``) or the
        cached one (``core/decode.py``); norms, residuals and the MLP are
        the same lines either way.  Returns ``(y, None)``: this block has
        no counters."""
        ln = LayerNormalization()
        drop_rngs = (jax.random.split(rng, 2) if rng is not None else
                     (None, None))

        with jax.named_scope("attn"):
            h = ln.apply(params["ln1"], x, compute_dtype=compute_dtype)
            h = mix(self._mha(), params["attn"], h)
            x = x + _dropout(drop_rngs[0], self.dropout, h.astype(x.dtype),
                             train)
        with jax.named_scope("mlp"):
            h = ln.apply(params["ln2"], x, compute_dtype=compute_dtype)
            h = _project(h, params["mlp_w1"], params["mlp_b1"],
                         compute_dtype)
            h = _apply_activation(self.activation, h).astype(compute_dtype)
            h = _project(h, params["mlp_w2"], params["mlp_b2"],
                         compute_dtype)
            return x + _dropout(drop_rngs[1], self.dropout,
                                h.astype(x.dtype), train), None


class Embedding(Layer):
    #: what a looked-up row is multiplied by (1: nothing is multiplied)
    output_scale: float = 1.0

    def __init__(self, input_dim: int, output_dim: int,
                 output_scale: float = 1.0):
        self.input_dim = int(input_dim)
        self.output_dim = int(output_dim)
        if output_scale != 1.0:
            self.output_scale = float(output_scale)

    def init(self, rng, in_shape):
        params = {"embedding": 0.02 * jax.random.normal(
            rng, (self.input_dim, self.output_dim), jnp.float32)}
        return params, tuple(in_shape) + (self.output_dim,)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        # jnp.asarray: trained params may live as host numpy arrays
        # (FittedModel), which tracer-indexing rejects
        rows = jnp.asarray(params["embedding"]).astype(compute_dtype)[x]
        if self.output_scale == 1.0:
            return rows
        return (rows.astype(jnp.float32) * self.output_scale).astype(
            compute_dtype)


class TiedHead(Layer):
    """The LM head of a model whose head IS its embedding table: ``logits =
    x E^T / divisor`` with ``E`` the ``(V, D)`` table of layer ``tied_to``,
    read as it lies (contracted over ``D``: no transposed copy is made or
    kept).  The layer has NO parameters of its own (``init`` gives ``{}``),
    so ``get_weights`` / ``set_weights``, checkpoints and a ``ServingEngine``
    hold one table; ``Sequential.apply`` and the cached step hand it the
    parameters of the layer it is tied to (``params_of``, which refuses an
    index that is not an ``Embedding``)."""

    def __init__(self, units: int, tied_to: int = 0, divisor: float = 1.0):
        self.units = int(units)        # the table's rows
        self.tied_to = int(tied_to)
        self.divisor = float(divisor)

    def init(self, rng, in_shape):
        return {}, tuple(in_shape[:-1]) + (self.units,)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        table = jnp.asarray(params["embedding"]).astype(compute_dtype)
        y = jax.lax.dot_general(
            x.astype(compute_dtype).reshape(-1, x.shape[-1]), table,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if self.divisor != 1.0:
            y = y / self.divisor
        return y.reshape(x.shape[:-1] + (table.shape[0],))


# ---------------------------------------------------------------------------
# Hybrid blocks: a mixer (attention or a linear recurrence), a feed-forward
# part (sparse experts), or both, under RMSNorm
# ---------------------------------------------------------------------------

class RMSNorm(Layer):
    """Root-mean-square norm over the trailing dim with a learned scale, no
    offset; float32 arithmetic."""

    def __init__(self, epsilon: float = 1e-5):
        self.epsilon = float(epsilon)

    def init(self, rng, in_shape):
        return {"scale": jnp.ones((in_shape[-1],), jnp.float32)}, \
            tuple(in_shape)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        return (y * params["scale"]).astype(x.dtype)


class GatedAttention(MultiHeadAttention):
    """Causal grouped-query attention with NO position signal (NoPE), no
    biases, and an output gate: ``o = attn * sigmoid(x @ wg)`` elementwise
    over the ``num_heads * key_dim`` features, then ``wo``.  The cached step
    is ``MultiHeadAttention``'s (state kind ``kv``)."""

    output_gate = True

    def __init__(self, num_heads: int, key_dim: int,
                 num_kv_heads: Optional[int] = None):
        super().__init__(num_heads, key_dim, causal=True, use_bias=False,
                         num_kv_heads=num_kv_heads)


def _short_conv(history, x, taps, n_live, bias=None):
    """The short causal depthwise convolution of a recurrent mixer, then
    SiLU, continuing ``history`` (B, c - 1, F), the inputs of the last
    ``c - 1`` positions: ``x`` (B, L, F) in the compute type, ``taps``
    (c, F) float32, ``n_live`` (B,) how many positions of each row count.
    Returns ``(SiLU(conv) (B, L, F) float32, the history after each row's
    last live position)``."""
    f32 = jnp.float32
    size, length = taps.shape[0], x.shape[1]
    hist = jnp.concatenate([history.astype(x.dtype), x], axis=1)
    conv = sum(hist[:, i:i + length].astype(f32) * taps[i]
               for i in range(size))
    if bias is not None:
        conv = conv + bias
    # the inputs of the last c-1 live positions: position t sits at
    # hist[t + c-1], so they are hist[n .. n + c-2]
    idx = n_live[:, None] + jnp.arange(size - 1)[None, :]
    return jax.nn.silu(conv), jnp.take_along_axis(hist, idx[:, :, None],
                                                  axis=1)


def _l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


class KimiDeltaAttention(Layer):
    """Kimi Delta Attention (arXiv:2510.26692): gated delta-rule linear
    attention with a per-channel decay, a short causal depthwise convolution
    on q, k and v, and a gated, per-head-normalised output.  The arithmetic
    of the recurrence is ``ops/kda.py``'s; here are the projections around
    it.  Per request the layer keeps a FIXED-SIZE state (state kind
    ``recurrent``): ``S`` (H, Dk, Dv) float32 and the last ``conv_size - 1``
    inputs of the convolution.

    ``neg_eigval``: ``beta = 2 * sigmoid(.)`` (the state transition may have
    negative eigenvalues) instead of ``sigmoid(.)``.  ``gate_rank``: the
    decay and output gates are low-rank, ``d -> gate_rank -> H * Dh``."""

    state_kind = "recurrent"
    scope = "kda"

    def __init__(self, num_heads: int, head_dim: int, conv_size: int = 4,
                 gate_rank: int = 128, neg_eigval: bool = True,
                 norm_eps: float = 1e-5):
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.conv_size = int(conv_size)
        self.gate_rank = int(gate_rank)
        self.neg_eigval = bool(neg_eigval)
        self.norm_eps = float(norm_eps)

    def init(self, rng, in_shape):
        s, d = in_shape
        h, dh, r = self.num_heads, self.head_dim, self.gate_rank
        inner = h * dh
        ks = iter(jax.random.split(rng, 16))
        params = {
            "wq": init_weight(next(ks), (d, inner)),
            "wk": init_weight(next(ks), (d, inner)),
            "wv": init_weight(next(ks), (d, inner)),
            "wo": init_weight(next(ks), (inner, d)),
            "wb": init_weight(next(ks), (d, h)),
            "wf_down": init_weight(next(ks), (d, r)),
            "wf_up": init_weight(next(ks), (r, inner)),
            "wg_down": init_weight(next(ks), (d, r)),
            "wg_up": init_weight(next(ks), (r, inner)),
            # decays spread over (0, 1): a_log in [log 1/16, log 4)
            "a_log": jnp.log(jax.random.uniform(next(ks), (h,), jnp.float32,
                                                1.0 / 16.0, 4.0)),
            "dt_bias": jnp.zeros((inner,), jnp.float32),
            "o_norm": jnp.ones((dh,), jnp.float32),
        }
        for name in ("conv_q", "conv_k", "conv_v"):
            params[name] = init_weight(next(ks), (self.conv_size, inner),
                                       "glorot_normal")
        return params, tuple(in_shape)

    def init_state(self, batch: int, dtype):
        """A zero state for ``batch`` rows: what a request starts from."""
        h, dh = self.num_heads, self.head_dim
        return {"S": jnp.zeros((batch, h, dh, dh), jnp.float32),
                "conv": jnp.zeros((batch, self.conv_size - 1, 3 * h * dh),
                                  dtype)}

    def kernel_tiles(self, state) -> bool:
        """Can the fused decode kernel (``kda_decode``) take ``state``?"""
        from ..ops import kda
        return kda.kernel_tiles(state["S"].shape, state["S"].dtype)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        state = self.init_state(x.shape[0], compute_dtype)
        return self.mix(params, x, state, compute_dtype=compute_dtype)[0]

    def mix(self, params, x, state, *, compute_dtype=jnp.bfloat16,
            token_mask=None, fused_step: bool = False):
        """(B, L, D) inputs continuing ``state`` -> ``(y (B, L, D) float32,
        the state after each row's last live token)``.  ``token_mask``
        (B, L) bool: the positions that count, a PREFIX of each row
        (right-padding and dead rows are False: they leave the state
        alone).  ``fused_step``: take the single-token step through the
        ``kda_decode`` kernel, which skips the rows the mask marks dead."""
        from ..ops import kda
        f32 = jnp.float32
        b, length, _ = x.shape
        h, dh = self.num_heads, self.head_dim
        inner = h * dh
        if token_mask is None:
            token_mask = jnp.ones((b, length), bool)
        n_live = jnp.sum(token_mask, axis=1).astype(jnp.int32)     # (B,)

        with jax.named_scope("kda_conv"):
            qkv = jnp.concatenate(
                [_project(x, params[w], None, compute_dtype)
                 for w in ("wq", "wk", "wv")], axis=-1).astype(compute_dtype)
            taps = jnp.concatenate(
                [params[w] for w in ("conv_q", "conv_k", "conv_v")],
                axis=-1).astype(f32)                       # (c, 3I)
            conv, new_conv = _short_conv(state["conv"], qkv, taps, n_live)
            q, k, v = (conv[..., i * inner:(i + 1) * inner]
                       .reshape(b, length, h, dh) for i in range(3))
            q = _l2_normalise(q) * (dh ** -0.5)
            k = _l2_normalise(k)

        def low_rank(down, up):
            mid = _project(x, params[down], None, compute_dtype)
            return _project(mid.astype(compute_dtype), params[up], None,
                            compute_dtype)

        with jax.named_scope("kda_gates"):
            decay = jax.nn.softplus(low_rank("wf_down", "wf_up")
                                    + params["dt_bias"].astype(f32))
            g = -jnp.exp(params["a_log"].astype(f32))[:, None] \
                * decay.reshape(b, length, h, dh)
            beta = jax.nn.sigmoid(_project(x, params["wb"], None,
                                           compute_dtype))
            if self.neg_eigval:
                beta = 2.0 * beta
            g = jnp.where(token_mask[:, :, None, None], g, 0.0)
            beta = jnp.where(token_mask[:, :, None], beta, 0.0)

        with jax.named_scope("kda_core"):
            if length == 1 and fused_step:
                o, new_s = kda.kda_decode(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    state["S"], token_mask[:, 0])
                o = o[:, None]
            elif length == 1:
                o, new_s = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                        beta[:, 0], state["S"])
                o = o[:, None]
            else:
                o, new_s = kda.kda_chunk(q, k, v, g, beta, state["S"])

        with jax.named_scope("kda_gate_out"):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + self.norm_eps)
            o = o * params["o_norm"].astype(f32)
            gate = jax.nn.sigmoid(low_rank("wg_down", "wg_up"))
            o = (o.reshape(b, length, inner) * gate).astype(compute_dtype)
            y = _project(o, params["wo"], None, compute_dtype)
        return y, {"S": new_s, "conv": new_conv.astype(state["conv"].dtype)}


class Mamba2Mixer(Layer):
    """A Mamba-2 state-space mixer (arXiv:2405.21060): one input projection
    to ``[z | x B C | dt]``, a short causal depthwise convolution with bias
    and SiLU on ``x B C``, the recurrence of ``ops/ssd.py`` (a scalar decay
    a head, ``B`` and ``C`` shared by the heads of a group), the skip ``D x``,
    an output gate ``silu(z)`` and an RMSNorm taken inside each group's
    channels, then the output projection.  No bias but the convolution's.
    Per request the layer keeps a FIXED-SIZE state (state kind
    ``recurrent``): ``S`` (H, P, N) float32 and the last ``conv_size - 1``
    inputs of the convolution."""

    state_kind = "recurrent"
    scope = "ssm"

    def __init__(self, num_heads: int, head_dim: int, state_size: int,
                 num_groups: int = 1, conv_size: int = 4,
                 chunk_size: int = 128, norm_eps: float = 1e-5):
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.state_size = int(state_size)
        self.num_groups = int(num_groups)
        self.conv_size = int(conv_size)
        self.chunk_size = int(chunk_size)
        self.norm_eps = float(norm_eps)
        if self.num_heads % self.num_groups:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"num_groups={self.num_groups}")

    def _sizes(self):
        inner = self.num_heads * self.head_dim
        return inner, inner + 2 * self.num_groups * self.state_size

    def init(self, rng, in_shape):
        s, d = in_shape
        h = self.num_heads
        inner, conv_dim = self._sizes()
        ks = iter(jax.random.split(rng, 8))
        # steps spread log-uniformly over (0.001, 0.1): dt_bias is the
        # inverse softplus of the step a zero projection gives
        dt = jnp.exp(jax.random.uniform(next(ks), (h,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        params = {
            "w_in": init_weight(next(ks), (d, inner + conv_dim + h)),
            "conv_w": init_weight(next(ks), (self.conv_size, conv_dim),
                                  "glorot_normal"),
            "conv_b": jnp.zeros((conv_dim,), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(next(ks), (h,), jnp.float32,
                                                1.0, 16.0)),
            "d_skip": jnp.ones((h,), jnp.float32),
            "norm": jnp.ones((inner,), jnp.float32),
            "w_out": init_weight(next(ks), (inner, d)),
        }
        return params, tuple(in_shape)

    def init_state(self, batch: int, dtype):
        """A zero state for ``batch`` rows: what a request starts from."""
        return {"S": jnp.zeros((batch, self.num_heads, self.head_dim,
                                self.state_size), jnp.float32),
                "conv": jnp.zeros((batch, self.conv_size - 1,
                                   self._sizes()[1]), dtype)}

    def kernel_tiles(self, state) -> bool:
        """Can the fused decode kernel (``ssd_decode``) take ``state``?"""
        from ..ops import ssd
        return ssd.kernel_tiles(state["S"].shape, state["S"].dtype)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        state = self.init_state(x.shape[0], compute_dtype)
        return self.mix(params, x, state, compute_dtype=compute_dtype)[0]

    def mix(self, params, x, state, *, compute_dtype=jnp.bfloat16,
            token_mask=None, fused_step: bool = False):
        """As ``KimiDeltaAttention.mix``: (B, L, D) inputs continuing
        ``state`` -> ``(y (B, L, D) float32, the state after each row's last
        live token)``; ``token_mask`` (B, L) bool, a PREFIX of each row;
        ``fused_step``: the single-token step through the ``ssd_decode``
        kernel, which skips the rows the mask marks dead."""
        from ..ops import ssd
        f32 = jnp.float32
        b, length, _ = x.shape
        h, p, n, g = (self.num_heads, self.head_dim, self.state_size,
                      self.num_groups)
        inner, conv_dim = self._sizes()
        if token_mask is None:
            token_mask = jnp.ones((b, length), bool)
        n_live = jnp.sum(token_mask, axis=1).astype(jnp.int32)     # (B,)

        with jax.named_scope("ssm_proj"):
            zxd = _project(x, params["w_in"], None, compute_dtype)
            z = zxd[..., :inner]
            xbc = zxd[..., inner:inner + conv_dim].astype(compute_dtype)
            dt = jax.nn.softplus(zxd[..., inner + conv_dim:].astype(f32)
                                 + params["dt_bias"].astype(f32))
            dt = jnp.where(token_mask[:, :, None], dt, 0.0)     # (B, L, H)

        with jax.named_scope("ssm_conv"):
            conv, new_conv = _short_conv(
                state["conv"], xbc, params["conv_w"].astype(f32), n_live,
                bias=params["conv_b"].astype(f32))
            xs = conv[..., :inner].reshape(b, length, h, p)
            bm = conv[..., inner:inner + g * n].reshape(b, length, g, n)
            cm = conv[..., inner + g * n:].reshape(b, length, g, n)

        a = -jnp.exp(params["a_log"].astype(f32))
        with jax.named_scope("ssm_core"):
            if length == 1 and fused_step:
                y, new_s = ssd.ssd_decode(xs[:, 0], dt[:, 0], a, bm[:, 0],
                                          cm[:, 0], state["S"],
                                          token_mask[:, 0])
                y = y[:, None]
            elif length == 1:
                y, new_s = ssd.ssd_step(xs[:, 0], dt[:, 0], a, bm[:, 0],
                                        cm[:, 0], state["S"])
                y = y[:, None]
            else:
                y, new_s = ssd.ssd_chunk(xs, dt, a, bm, cm, state["S"],
                                         chunk=self.chunk_size)

        with jax.named_scope("ssm_out"):
            y = y + params["d_skip"].astype(f32)[:, None] * xs
            y = y.reshape(b, length, inner) * jax.nn.silu(z.astype(f32))
            # gate first, then the norm inside each group's channels
            yg = y.reshape(b, length, g, inner // g)
            yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1,
                                             keepdims=True) + self.norm_eps)
            y = (yg.reshape(b, length, inner)
                 * params["norm"].astype(f32)).astype(compute_dtype)
            out = _project(y, params["w_out"], None, compute_dtype)
        return out, {"S": new_s,
                     "conv": new_conv.astype(state["conv"].dtype)}


def _swiglu(h):
    """``silu(gate) * up`` with gate and up side by side on the last axis."""
    f = h.shape[-1] // 2
    return jax.nn.silu(h[..., :f]) * h[..., f:]


#: an expert's form: (what stands between its two matmuls, columns of its
#: input weight per unit of width)
_EXPERT_FORMS = {"gated_silu": (_swiglu, 2),
                 "relu2": (lambda h: jnp.square(jax.nn.relu(h)), 1)}


def _expert_mlp(x, w_in, w_out, form: str, compute_dtype):
    """One expert over every row: ``act(x @ w_in) @ w_out``."""
    h = _EXPERT_FORMS[form][0](_project(x, w_in, None, compute_dtype))
    return _project(h.astype(compute_dtype), w_out, None, compute_dtype)


class GatedMLP(Layer):
    """A dense gated-SiLU MLP as a ``HybridBlock``'s feed-forward part:
    ``(silu(g) * u) W_out`` with ``[g | u] = x W_in`` (``w_in`` ``(D, 2 F)``,
    gate first; ``w_out`` ``(F, D)``), no biases.  Every token goes through
    it: it routes nothing and has no counters."""

    routes_tokens = False

    def __init__(self, mlp_dim: int):
        self.mlp_dim = int(mlp_dim)

    def init(self, rng, in_shape):
        d = in_shape[-1]
        k_i, k_o = jax.random.split(rng)
        return {"w_in": init_weight(k_i, (d, 2 * self.mlp_dim)),
                "w_out": init_weight(k_o, (self.mlp_dim, d))}, \
            tuple(in_shape)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        return self.mix(params, x, compute_dtype=compute_dtype)[0]

    def mix(self, params, x, *, compute_dtype=jnp.bfloat16, token_mask=None):
        """``(y float32, None)``: a feed-forward part's contract
        (``SparseMoE.mix``), without counters."""
        with jax.named_scope("mlp"):
            with jax.named_scope("mlp_in"):
                h = _swiglu(_project(x, params["w_in"], None, compute_dtype))
            with jax.named_scope("mlp_out"):
                return _project(h.astype(compute_dtype), params["w_out"],
                                None, compute_dtype), None


class SparseMoE(Layer):
    """Sparse experts WITHOUT drops, told which experts it holds.

    The router scores all ``num_experts`` in float32 (softmax, the ``top_k``
    largest, their weights renormalised to 1); each expert is a gated MLP of
    width ``expert_dim``; one shared expert of width ``shared_dim`` (0: none)
    is added for every token.  ``held`` = (first, count) names the experts
    whose weights live HERE — one chip's share of an expert-parallel
    deployment; default all.  The layer computes the terms of its own experts
    for the tokens routed to them (``ops/experts.py``: assignments sorted by
    expert, one grouped matmul in and one out, no capacity and no dropped
    token under any skew) plus the shared expert; what experts held
    elsewhere would add is left out, and that partial result goes on.

    ``router`` ``"sigmoid_bias"``: the scores are ``sigmoid``s, the ``top_k``
    are chosen by score PLUS a learned per-expert bias (``router_bias``,
    selection only), and the chosen experts' UNBIASED scores, renormalised
    to 1, times ``router_scale`` are the weights.  ``expert_form``
    ``"relu2"``: every expert (the shared one too) is ``relu(x W_up)^2
    W_down``, no gate.

    Parameters: the held experts' up-projections are ``w_in`` of shape
    ``(E, D, cols * F)`` (``cols`` 2 for a gated form, gate then up; 1 for
    ``relu2``) and their down-projections ``w_out`` ``(E, F, D)``.  That is
    what ``init`` makes and what ``get_weights`` / ``set_weights``,
    checkpoints and training see.  ``store_for_serving`` alone replaces
    ``w_in`` by its transpose ``w_in_t`` ``(E, cols * F, D)`` where the
    chip would otherwise relay the weight before every grouped matmul
    (``serves_transposed``); ``mix`` reads whichever it is given."""

    routes_tokens = True
    #: class-level defaults: configs written before these fields existed
    #: deserialize as the softmax router over gated-SiLU experts
    router = "softmax"
    router_scale = 1.0
    expert_form = "gated_silu"

    def __init__(self, num_experts: int, top_k: int, expert_dim: int,
                 held: Optional[Tuple[int, int]] = None, shared_dim: int = 0,
                 router: str = "softmax", router_scale: float = 1.0,
                 expert_form: str = "gated_silu"):
        self.num_experts = int(num_experts)
        self.top_k = int(top_k)
        self.expert_dim = int(expert_dim)
        self.held = (0, self.num_experts) if held is None else \
            (int(held[0]), int(held[1]))
        self.shared_dim = int(shared_dim)
        if router not in ("softmax", "sigmoid_bias"):
            raise ValueError(f"router must be 'softmax' or 'sigmoid_bias', "
                             f"got {router!r}")
        if expert_form not in _EXPERT_FORMS:
            raise ValueError(f"expert_form must be one of "
                             f"{sorted(_EXPERT_FORMS)}, got {expert_form!r}")
        if router != "softmax":
            self.router, self.router_scale = router, float(router_scale)
        elif router_scale != 1.0:
            raise ValueError("router_scale belongs to router='sigmoid_bias'")
        if expert_form != "gated_silu":
            self.expert_form = expert_form
        if not (0 <= self.held[0]
                and self.held[0] + self.held[1] <= self.num_experts):
            raise ValueError(f"held={self.held} outside the "
                             f"{self.num_experts} experts")

    def init(self, rng, in_shape):
        d = in_shape[-1]
        n, f = self.held[1], self.expert_dim
        cols = _EXPERT_FORMS[self.expert_form][1]
        k_r, k_i, k_o, k_si, k_so = jax.random.split(rng, 5)
        std_in, std_out = (2.0 / (d + f)) ** 0.5, (2.0 / (f + d)) ** 0.5
        params = {
            "router": init_weight(k_r, (d, self.num_experts)),
            "w_in": std_in * jax.random.normal(k_i, (n, d, cols * f)),
            "w_out": std_out * jax.random.normal(k_o, (n, f, d)),
        }
        if self.router == "sigmoid_bias":
            params["router_bias"] = jnp.zeros((self.num_experts,),
                                              jnp.float32)
        if self.shared_dim:
            params["shared_in"] = init_weight(
                k_si, (d, cols * self.shared_dim))
            params["shared_out"] = init_weight(k_so, (self.shared_dim, d))
        return params, tuple(in_shape)

    @staticmethod
    def serves_transposed(shape) -> bool:
        """Whether an up-projection of ``shape`` ``(E, D, cols * F)`` is
        served transposed.  A TPU keeps an array's minor-most axis in whole
        tiles of 128 lanes and chooses which axis that is: where ``cols * F``
        is not a whole number of them and ``D`` is, it keeps ``D`` minor-most
        (Nemotron-3-Nano's ``(64, 2688, 1856)``), the grouped matmul reads
        its weight row-major, and XLA copies the whole weight into that
        layout before every call.  Stored ``(E, cols * F, D)`` the chip's
        choice IS row-major (as ``w_out`` shows) and the kernel reads it in
        place.  Any other shape (Solar-Open2's ``(40, 4096, 2560)``) is
        row-major as it is."""
        _, d, f = shape
        return d % 128 == 0 and f % 128 != 0

    def store_for_serving(self, params):
        if "w_in_t" in params:
            return params, 1
        if not self.serves_transposed(params["w_in"].shape):
            return params, 0
        held = {k: v for k, v in params.items() if k != "w_in"}
        held["w_in_t"] = jnp.swapaxes(params["w_in"], 1, 2)
        return held, 1

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        return self.mix(params, x, compute_dtype=compute_dtype)[0]

    def mix(self, params, x, *, compute_dtype=jnp.bfloat16, token_mask=None):
        """``(y, counters)``: the layer's output and ``[assignments to held
        experts, held experts that got a token, the fullest held expert's
        rows]`` (int32) over the tokens ``token_mask`` counts."""
        from ..ops import experts as ops
        f32 = jnp.float32
        lead, d = x.shape[:-1], x.shape[-1]
        flat = x.reshape(-1, d)
        live = None if token_mask is None else token_mask.reshape(-1)
        with jax.named_scope("moe"):
            with jax.named_scope("moe_route"):
                logits = jnp.matmul(flat.astype(f32),
                                    params["router"].astype(f32),
                                    precision=jax.lax.Precision.HIGHEST)
                if self.router == "softmax":
                    chosen, weights = ops.route(logits, self.top_k)
                else:
                    chosen, weights = ops.route(
                        logits, self.top_k, kind=self.router,
                        bias=params["router_bias"], scale=self.router_scale)
            with jax.named_scope("moe_dispatch"):
                token, weight, sizes, total = ops.dispatch(
                    chosen, weights, self.held, live)
                rows = flat.astype(compute_dtype)[token]
            with jax.named_scope("moe_experts"):
                stored = "w_in_t" in params     # store_for_serving's form
                h = ops.grouped_matmul(
                    rows, params["w_in_t" if stored else "w_in"].astype(
                        compute_dtype), sizes, transpose_rhs=stored)
                h = _EXPERT_FORMS[self.expert_form][0](h).astype(
                    compute_dtype)
                out = ops.grouped_matmul(
                    h, params["w_out"].astype(compute_dtype), sizes)
            with jax.named_scope("moe_combine"):
                y = jnp.zeros((flat.shape[0], d), f32).at[token].add(
                    out * weight[:, None])
            if self.shared_dim:
                with jax.named_scope("moe_shared"):
                    y = y + _expert_mlp(flat, params["shared_in"],
                                        params["shared_out"],
                                        self.expert_form, compute_dtype)
        counters = jnp.stack([total, jnp.sum(sizes > 0),
                              jnp.max(sizes)]).astype(jnp.int32)
        return y.reshape(lead + (d,)), counters


class HybridBlock(Layer):
    """Pre-norm residual block that TAKES its parts, no biases: a mixer
    (``h = x + mixer(RMSNorm(x))``), a feed-forward part (``y = h +
    ffn(RMSNorm(h))``), or both in that order.  ``mixer`` is an attention
    layer (``GatedAttention``, a plain ``MultiHeadAttention``) or a linear
    recurrence (``KimiDeltaAttention``, ``Mamba2Mixer``); ``ffn`` a
    ``SparseMoE`` or a dense ``GatedMLP``; ``residual_multiplier`` scales
    each part's output before it joins the stream.  A block of ONE part is
    a layer of a stack whose layers are a mixer OR a feed-forward part
    alone; without a mixer the block keeps no per-request state (state kind
    ``none``).  The parts are kept
    as their configs (the spec stays JSON-serialisable) and rebuilt on use.
    What the cached step and the serving engine need to know of a block they
    ask the block (``state_kind``, ``routes_tokens``, ``wants_token_mask``,
    ``int8_weights``, ``store_for_serving``), never its class."""

    #: ``core.quant.quantize_params`` finds matmul weights by
    #: ``TransformerBlock``'s names and would leave these as they are
    int8_weights = False
    #: what each part's output is multiplied by before it joins the residual
    #: stream (1: nothing is multiplied)
    residual_multiplier: float = 1.0

    def __init__(self, mixer=None, ffn=None, epsilon: float = 1e-5,
                 residual_multiplier: float = 1.0):
        def config(part):
            if part is None:
                return None
            return part.get_config() if isinstance(part, Layer) else \
                dict(part)
        if mixer is None and ffn is None:
            raise ValueError("HybridBlock needs a mixer, a feed-forward "
                             "part, or both")
        self.mixer_config = config(mixer)
        self.ffn_config = config(ffn)
        self.epsilon = float(epsilon)
        if residual_multiplier != 1.0:
            self.residual_multiplier = float(residual_multiplier)

    def mixer(self) -> Optional[Layer]:
        return self.mixer_config and Layer.from_config(self.mixer_config)

    def ffn(self) -> Optional[Layer]:
        return self.ffn_config and Layer.from_config(self.ffn_config)

    @property
    def state_kind(self) -> str:
        return self.mixer().state_kind if self.mixer_config else "none"

    @property
    def routes_tokens(self) -> bool:
        """The feed-forward part routes tokens to experts and returns
        counters of it (``SparseMoE``; a ``GatedMLP`` answers False)."""
        return bool(self.ffn_config) and self.ffn().routes_tokens

    @property
    def wants_token_mask(self) -> bool:
        """Padding and dead rows must be told apart from live tokens: they
        would advance a recurrent state, or be routed and counted."""
        return self.state_kind == "recurrent" or self.routes_tokens

    @property
    def causal(self) -> bool:
        return True

    def store_for_serving(self, params):
        if not self.ffn_config:
            return params, 0
        ffn, n = self.ffn().store_for_serving(params["ffn"])
        return {**params, "ffn": ffn}, n

    def init(self, rng, in_shape):
        k_m, k_f = jax.random.split(rng)
        norm = RMSNorm(self.epsilon)
        params = {}
        if self.mixer_config:
            params.update(norm1=norm.init(None, in_shape)[0],
                          mixer=self.mixer().init(k_m, in_shape)[0])
        if self.ffn_config:
            params.update(norm2=norm.init(None, in_shape)[0],
                          ffn=self.ffn().init(k_f, in_shape)[0])
        return params, tuple(in_shape)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        def full(mixer, p, h):
            return mixer.apply(p, h, compute_dtype=compute_dtype)
        return self.run(params, x, full, compute_dtype=compute_dtype)[0]

    def run(self, params, x, mix, *, compute_dtype=jnp.bfloat16,
            train=False, rng=None, token_mask=None):
        """As ``TransformerBlock.run``: the block around ``mix``, which is
        not called where the block has no mixer.  Returns ``(y, counters)``,
        the feed-forward part's counters (``SparseMoE``) or None.
        ``token_mask`` (B, L) bool keeps padding and dead rows out of the
        experts' routing."""
        norm = RMSNorm(self.epsilon)
        mixer, ffn = self.mixer(), self.ffn()
        counters = None

        def joins(x, h):
            if self.residual_multiplier != 1.0:
                h = h.astype(jnp.float32) * self.residual_multiplier
            return x + h.astype(x.dtype)

        if mixer is not None:
            with jax.named_scope(mixer.scope):
                h = norm.apply(params["norm1"], x,
                               compute_dtype=compute_dtype)
                x = joins(x, mix(mixer, params["mixer"], h))
        if ffn is not None:
            h = norm.apply(params["norm2"], x, compute_dtype=compute_dtype)
            h, counters = ffn.mix(params["ffn"], h,
                                  compute_dtype=compute_dtype,
                                  token_mask=token_mask)
            x = joins(x, h)
        return x, counters


def params_of(layers: Sequence[Layer], params, i: int):
    """The parameters layer ``i`` of a stack reads, for the training forward
    and the cached step alike: its own, or, for a layer tied to another's
    (``TiedHead.tied_to``), that layer's, which has to be an ``Embedding``:
    an index that points elsewhere (a stack walked as a slice) is refused,
    never read."""
    j = getattr(layers[i], "tied_to", None)
    if j is None:
        return params[i]
    if not (0 <= j < len(layers) and isinstance(layers[j], Embedding)):
        raise ValueError(
            f"layer {i} ({type(layers[i]).__name__}) is tied to layer {j}, "
            f"which is not an Embedding of this stack")
    return params[j]


def scope_names(layers: Sequence[Layer]) -> List[str]:
    """The ``jax.named_scope`` each layer of a stack runs under, in the
    training forward and the decode forward alike (``metrics.py`` lists
    them): ``embed`` for the token and position tables, ``block_<i>`` for
    the i-th ``TransformerBlock`` or ``HybridBlock``, and — in a stack that has blocks —
    ``final_norm`` for the normalization after the last one and ``lm_head``
    for a closing ``Dense`` or ``TiedHead``.  Any other layer runs under its
    class name in lower case."""
    blocks = [i for i, l in enumerate(layers)
              if isinstance(l, (TransformerBlock, HybridBlock))]
    names = []
    for i, layer in enumerate(layers):
        if isinstance(layer, (Embedding, PositionalEmbedding)):
            name = "embed"
        elif isinstance(layer, (TransformerBlock, HybridBlock)):
            name = f"block_{blocks.index(i)}"
        elif (blocks and i > blocks[-1]
              and isinstance(layer, (LayerNormalization, RMSNorm))):
            name = "final_norm"
        elif (blocks and i == len(layers) - 1
              and isinstance(layer, (Dense, TiedHead))):
            name = "lm_head"
        else:
            name = type(layer).__name__.lower()
        names.append(name)
    return names
