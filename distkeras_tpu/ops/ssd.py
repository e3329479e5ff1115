"""The state-space recurrence of a Mamba-2 layer (state-space duality,
arXiv:2405.21060): chunkwise for prefill and one fused step for decode.

Per head ``h``, with a state ``S`` in R^{P x N} (float32; ``P`` the head's
channels, ``N`` the state size), a step ``dt_t > 0``, a scalar decay ``a_t =
exp(dt_t A_h)`` in (0, 1] (``A_h < 0``), an input ``x_t`` in R^P and the
group's ``B_t``, ``C_t`` in R^N (head ``h`` reads group ``h // (H / G)``)::

    S <- a_t S + dt_t x_t B_t^T
    y_t = S C_t

(the skip ``D_h x_t``, the gate and the norm are the layer's).  Three forms
of the same arithmetic:

- :func:`ssd_step` — one token a row in plain ``jnp`` (the oracle, and the
  decode step off the TPU);
- :func:`ssd_chunk` — the chunkwise form for L tokens a row: inside a chunk
  of ``chunk`` tokens the outputs are a masked ``(C B^T)`` product weighted
  by the decay between the two positions plus the read of the state the
  chunk opened with, the closing state is one more matmul, and the chunks of
  a row are a ``lax.scan`` that carries the state — from chunk to chunk, and
  (through the caller) from prefill unit to prefill unit.  Every decay enters
  as ``exp`` of a DIFFERENCE of cumulative log-decays between a later and an
  earlier position (never above 1), so a strong decay cannot overflow; the
  products are float32 under ``precision=HIGHEST``;
- :func:`ssd_decode` — the Pallas kernel of the serving decode step
  (``name="ssd_decode"``): the state of every LIVE slot is read once and
  written once, in place (``input_output_aliases``); a dead slot's state is
  neither read nor written (``ops/kda.py::kda_decode``'s contract).

A position that must not move the state (right-padding of a prefill unit, a
slot that holds no request) is given ``dt = 0`` by the caller: the decay is
then 1 and the write 0, in all three forms.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import _HEAD_BLOCK, _column

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 128         # tokens solved together inside a prefill unit


def _per_head(m, heads: int):
    """(..., G, N) per group -> (..., H, N) per head."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


def ssd_step(x, dt, a, b, c, state):
    """One token a row.  ``x``: (B, H, P); ``dt``: (B, H), after its
    softplus, 0 where the row is dead; ``a``: (H,), negative; ``b``, ``c``:
    (B, G, N); ``state``: (B, H, P, N) float32.  Returns ``(y (B, H, P)
    float32, new state)``."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    h = x.shape[1]
    decay = jnp.exp(dt * a.astype(f32))                          # (B, H)
    write = (dt[..., None] * x)[..., None] * _per_head(b, h)[:, :, None, :]
    s = state * decay[..., None, None] + write
    return jnp.sum(s * _per_head(c, h)[:, :, None, :], axis=-1), s


def _chunk_body(state, xs):
    """One chunk of every row: ``state`` (B, H, P, N); ``xs`` the chunk's
    x (B, C, H, P), log-decay and dt (B, C, H), b and c (B, C, G, N)."""
    x, log_a, dt, b, c = xs
    n_c, h = x.shape[1], x.shape[2]
    g = b.shape[2]
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    cum = jnp.cumsum(log_a, axis=1)                              # (B, C, H)
    # decay from position s to a later position t: exp(cum_t - cum_s) <= 1;
    # pairs with s > t are masked to 0
    later = (jnp.arange(n_c)[:, None] >= jnp.arange(n_c)[None, :])
    decay = jnp.exp(jnp.where(later[None, :, :, None],
                              cum[:, :, None, :] - cum[:, None, :, :],
                              -jnp.inf))                         # (B, T, S, H)
    cb = mm("btgn,bsgn->btsg", c, b)                             # (B, T, S, G)
    weight = jnp.repeat(cb, h // g, axis=-1) * decay * dt[:, None, :, :]
    xg = x.reshape(x.shape[:2] + (g, h // g, x.shape[-1]))       # by group
    sg = state.reshape((state.shape[0], g, h // g) + state.shape[2:])
    y = (mm("btsh,bshp->bthp", weight, x)
         + (mm("btgn,bgkpn->btgkp", c, sg).reshape(x.shape)
            * jnp.exp(cum)[..., None]))
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dt                  # (B, C, H)
    wx = (xg * to_end.reshape(to_end.shape[:2] + (g, h // g))[..., None])
    new = (state * jnp.exp(cum[:, -1, :])[..., None, None]
           + mm("bsgkp,bsgn->bgkpn", wx, b).reshape(state.shape))
    return new, y


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunk(x, dt, a, b, c, state, chunk: int = CHUNK):
    """L tokens a row, chunkwise.  ``x``: (B, L, H, P); ``dt``: (B, L, H);
    ``a``: (H,); ``b``, ``c``: (B, L, G, N); ``state``: (B, H, P, N) float32,
    the state each row opens with.  Returns ``(y (B, L, H, P) float32, the
    state after the row's last token)``.  ``L`` need not be a multiple of
    ``chunk``: the tail is filled with positions of ``dt = 0``, which leave
    the state alone.  Jitted under its own name so that a model's layers
    share one trace."""
    f32 = jnp.float32
    bsz, length = x.shape[:2]
    n_c = min(int(chunk), length)
    n = -(-length // n_c)
    pad = n * n_c - length

    def chunks(v):
        v = v.astype(f32)
        if pad:
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        # (B, n*C, ...) -> (n, B, C, ...)
        return jnp.moveaxis(v.reshape((bsz, n, n_c) + v.shape[2:]), 1, 0)

    dt = dt.astype(f32)
    xs = (chunks(x), chunks(dt * a.astype(f32)), chunks(dt), chunks(b),
          chunks(c))
    with jax.named_scope("ssd_chunk"):
        state, y = jax.lax.scan(_chunk_body, state.astype(f32), xs)
    y = jnp.moveaxis(y, 0, 1).reshape((bsz, n * n_c) + x.shape[2:])
    return y[:, :length], state


# ---------------------------------------------------------------------------
# the decode kernel
# ---------------------------------------------------------------------------

def kernel_tiles(state_shape, state_dtype) -> bool:
    """Can :func:`ssd_decode` take this state?  (slots, H, P, N) float32
    whose N fills the 128 lanes, whose P is whole sublane tiles, and whose
    heads divide into blocks of ``_HEAD_BLOCK``."""
    if len(state_shape) != 4 or jnp.dtype(state_dtype) != jnp.float32:
        return False
    _, h, p, n = state_shape
    return n == 128 and p % 8 == 0 and p <= 128 and h % _HEAD_BLOCK == 0


def _ssd_decode_kernel(order_ref, n_ref,                          # prefetch
                       x_ref, a_ref, b_ref, c_ref, s_ref,         # inputs
                       y_ref, s_out_ref):                         # outputs
    i = pl.program_id(0)
    hb, p = x_ref.shape[1], x_ref.shape[2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1))

    @pl.when(i < n_ref[0])
    def _():
        for h in range(hb):
            x_col = _column(x_ref[0, h:h + 1, :], eye)           # (P, 1)
            s = (s_ref[0, h] * a_ref[0, h:h + 1, :]
                 + x_col * b_ref[0, h:h + 1, :])                 # (P, N)
            s_out_ref[0, h] = s
            y_col = jnp.sum(s * c_ref[0, h:h + 1, :], axis=1, keepdims=True)
            # the (P, 1) column back as a (1, P) row, again by the diagonal
            y_ref[0, h:h + 1, :] = jnp.sum(jnp.where(eye, y_col, 0.0),
                                           axis=0, keepdims=True)

    @pl.when(n_ref[0] == 0)
    def _():
        # no live slot: every step sits on one block, which goes back as it
        # came (its output buffer is written out when the grid ends)
        s_out_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("state",))
def ssd_decode(x, dt, a, b, c, state, live, *,
               interpret: Optional[bool] = None):
    """The decode step of every slot in one kernel.  Arguments as
    :func:`ssd_step`'s; ``state`` is donated and updated in place; ``live``:
    (B,) bool.  A live slot's state is read once and written once; a dead
    slot's state is not touched and its output row is zero.  Returns ``(y
    (B, H, P) float32, state)``.

    The grid walks the live slots first (``order``, prefetched): step
    ``(i, j)`` holds heads ``j * 8 .. j * 8 + 7`` of slot ``order[i]``; the
    steps past the last live slot all map to the block of the step before
    them, so the pipeline moves nothing for them."""
    f32 = jnp.float32
    bsz, h, p = x.shape
    n = state.shape[-1]
    hb = _HEAD_BLOCK
    nj = h // hb
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    live = live.astype(bool)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)[None]
    dt = dt.astype(f32)
    decay = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None],
                             (bsz, h, n))
    write = dt[..., None] * x.astype(f32)

    def at(i, j, order_ref, n_ref):
        n_on = n_ref[0]
        on = i < n_on
        slot = order_ref[jnp.where(on, i, jnp.maximum(n_on - 1, 0))]
        return slot, jnp.where(on, j, nj - 1)

    def vec_map(i, j, order_ref, n_ref):
        return at(i, j, order_ref, n_ref) + (0,)

    def state_map(i, j, order_ref, n_ref):
        return at(i, j, order_ref, n_ref) + (0, 0)

    vec_p = pl.BlockSpec((1, hb, p), vec_map)
    vec_n = pl.BlockSpec((1, hb, n), vec_map)
    st = pl.BlockSpec((1, hb, p, n), state_map)
    y, state = pl.pallas_call(
        _ssd_decode_kernel,
        out_shape=(jax.ShapeDtypeStruct((bsz, h, p), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bsz, nj),
            in_specs=[vec_p, vec_n, vec_n, vec_n, st],
            out_specs=(vec_p, st)),
        # operands count the two prefetched scalars: the state is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_decode",
    )(order, n_live, write, decay, _per_head(b.astype(f32), h),
      _per_head(c.astype(f32), h), state)
    return jnp.where(live[:, None, None], y, 0.0), state
