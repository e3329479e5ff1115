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
        y = jax.lax.dot_general(
            x.astype(compute_dtype), k,
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if self.use_bias:
            y = y + params["bias"]
        return _apply_activation(self.activation, y)


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

    def __init__(self, num_heads: int, key_dim: int, causal: bool = False,
                 use_bias: bool = True, attention_impl: Optional[str] = None,
                 num_kv_heads: Optional[int] = None,
                 attention_window: Optional[int] = None,
                 rope: bool = False, rope_theta: float = 10000.0,
                 rope_scale: float = 1.0):
        self.num_heads = int(num_heads)
        self.key_dim = int(key_dim)  # per-head dim
        self.causal = bool(causal)
        self.use_bias = bool(use_bias)
        self.attention_impl = attention_impl
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
            out = attention(q, k, v,
                            causal=self.causal, impl=self.attention_impl,
                            window=self.attention_window,
                            segment_ids=segment_ids)
        out = out.reshape(b, s, self.num_heads * dh)
        bias_o = params.get("bo") if self.use_bias else None
        return _project(out, params["wo"], bias_o, compute_dtype)


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

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None, segment_ids=None):
        ln = LayerNormalization()
        drop_rngs = (jax.random.split(rng, 2) if rng is not None else
                     (None, None))

        with jax.named_scope("attn"):
            h = ln.apply(params["ln1"], x, compute_dtype=compute_dtype)
            h = self._mha().apply(params["attn"], h,
                                  compute_dtype=compute_dtype, train=train,
                                  rng=None, segment_ids=segment_ids)
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
                                h.astype(x.dtype), train)


class Embedding(Layer):
    def __init__(self, input_dim: int, output_dim: int):
        self.input_dim = int(input_dim)
        self.output_dim = int(output_dim)

    def init(self, rng, in_shape):
        params = {"embedding": 0.02 * jax.random.normal(
            rng, (self.input_dim, self.output_dim), jnp.float32)}
        return params, tuple(in_shape) + (self.output_dim,)

    def apply(self, params, x, *, compute_dtype=jnp.bfloat16, train=False,
              rng=None):
        return params["embedding"].astype(compute_dtype)[x]


def scope_names(layers: Sequence[Layer]) -> List[str]:
    """The ``jax.named_scope`` each layer of a stack runs under, in the
    training forward and the decode forward alike (``metrics.py`` lists
    them): ``embed`` for the token and position tables, ``block_<i>`` for
    the i-th ``TransformerBlock``, and — in a stack that has blocks —
    ``final_norm`` for the normalization after the last one and ``lm_head``
    for a closing ``Dense``.  Any other layer runs under its class name in
    lower case."""
    blocks = [i for i, l in enumerate(layers)
              if isinstance(l, TransformerBlock)]
    names = []
    for i, layer in enumerate(layers):
        if isinstance(layer, (Embedding, PositionalEmbedding)):
            name = "embed"
        elif isinstance(layer, TransformerBlock):
            name = f"block_{blocks.index(i)}"
        elif (blocks and i > blocks[-1]
              and isinstance(layer, LayerNormalization)):
            name = "final_norm"
        elif blocks and i == len(layers) - 1 and isinstance(layer, Dense):
            name = "lm_head"
        else:
            name = type(layer).__name__.lower()
        names.append(name)
    return names
