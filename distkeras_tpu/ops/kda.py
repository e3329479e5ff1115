"""Gated delta-rule linear attention (Kimi Delta Attention, arXiv:2510.26692):
the recurrence, chunkwise for prefill and one fused step for decode.

Per head, with a state ``S`` in R^{Dk x Dv} (float32), a per-channel decay
``alpha_t = exp(g_t)`` in (0, 1]^Dk, a write strength ``beta_t`` (up to 2:
negative eigenvalues), an L2-normalised key ``k_t`` and a scaled, normalised
query ``q_t``::

    S <- Diag(alpha_t) S
    S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t

Three forms of the same arithmetic:

- :func:`kda_step` — one token a row in plain ``jnp`` (the oracle, and the
  decode step off the TPU);
- :func:`kda_chunk` — the chunkwise-parallel form for L tokens a row: within
  a chunk of ``chunk`` tokens the writes ``u_t = v_t - (Diag(alpha_t)
  S_{t-1})^T k_t`` solve a unit lower-triangular system, the chunk's outputs
  and its closing state are matmuls against the state it opened with, and the
  chunks of a row are a ``lax.scan`` that carries the state.  Every decay
  enters as ``exp`` of a DIFFERENCE of cumulative log-decays between a later
  and an earlier position (never above 1), so a strong decay cannot overflow;
  the triangular solve and the decayed products are float32 under
  ``precision=HIGHEST``;
- :func:`kda_decode` — the Pallas kernel of the serving decode step
  (``name="kda_decode"``): the state of every LIVE slot is read once and
  written once, in place (``input_output_aliases``); a dead slot's state is
  neither read nor written.

A position that must not move the state (right-padding of a prefill unit, a
slot that holds no request) is given ``g = 0`` and ``beta = 0`` by the caller:
the recurrence then leaves ``S`` as it was, in all three forms.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64          # tokens solved together inside a prefill unit
_HEAD_BLOCK = 8     # heads of one slot a kernel step holds in VMEM


def kda_step(q, k, v, g, beta, state):
    """One token a row.  ``q``, ``k``, ``g``: (B, H, Dk); ``v``: (B, H, Dv);
    ``beta``: (B, H); ``state``: (B, H, Dk, Dv) float32.  Returns
    ``(o (B, H, Dv) float32, new state)``."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    s = state * jnp.exp(g)[..., None]
    u = v - jnp.sum(s * k[..., None], axis=-2)
    s = s + (beta[..., None] * k)[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def _chunk_body(state, xs):
    """One chunk of every row and head: ``state`` (B, H, Dk, Dv); ``xs`` the
    chunk's q, k, g (B, H, C, Dk), v (B, H, C, Dv), beta (B, H, C)."""
    q, k, v, g, beta = xs
    c = q.shape[2]
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    cum = jnp.cumsum(g, axis=2)                                # (B, H, C, Dk)
    # decay from position s to a later position t, channel by channel:
    # exp(cum_t - cum_s) <= 1; pairs with s > t are masked to 0
    t_idx = jnp.arange(c)[:, None]
    s_idx = jnp.arange(c)[None, :]
    later = (t_idx >= s_idx)[None, None, :, :, None]
    decay = jnp.exp(jnp.where(
        later, cum[:, :, :, None, :] - cum[:, :, None, :, :], -jnp.inf))
    k_dec = k[:, :, None, :, :] * decay                    # (B, H, C, C, Dk)
    a = jnp.sum(k[:, :, :, None, :] * k_dec, axis=-1)      # k_t . k_s decayed
    b = jnp.sum(q[:, :, :, None, :] * k_dec, axis=-1)      # q_t . k_s decayed
    strict = (t_idx > s_idx)[None, None]
    # (I + tril(a, -1) Diag(beta)) u = v - (k e^cum) S0
    system = jnp.where(strict, a * beta[:, :, None, :], 0.0) \
        + jnp.eye(c, dtype=a.dtype)
    e_cum = jnp.exp(cum)
    rhs = v - mm("bhtc,bhcv->bhtv", k * e_cum, state)
    u = jax.scipy.linalg.solve_triangular(system, rhs, lower=True,
                                          unit_diagonal=True)
    w = beta[..., None] * u                                    # (B, H, C, Dv)
    o = (mm("bhtc,bhcv->bhtv", q * e_cum, state)
         + mm("bhts,bhsv->bhtv", jnp.where(later[..., 0], b, 0.0), w))
    last = cum[:, :, -1:, :]                                   # (B, H, 1, Dk)
    new_state = (jnp.exp(last[:, :, 0, :])[..., None] * state
                 + mm("bhsc,bhsv->bhcv", k * jnp.exp(last - cum), w))
    return new_state, o


@functools.partial(jax.jit, static_argnames=("chunk",))
def kda_chunk(q, k, v, g, beta, state, chunk: int = CHUNK):
    """L tokens a row, chunkwise.  ``q``, ``k``, ``g``: (B, L, H, Dk); ``v``:
    (B, L, H, Dv); ``beta``: (B, L, H); ``state``: (B, H, Dk, Dv) float32, the
    state each row opens with.  Returns ``(o (B, L, H, Dv) float32, the state
    after the row's last token)``.  ``L`` need not be a multiple of
    ``chunk``: the tail is filled with positions that leave the state alone.
    Jitted under its own name so that a model's layers share one trace."""
    f32 = jnp.float32
    b, length, h, dk = q.shape
    c = min(int(chunk), length)
    n = -(-length // c)
    pad = n * c - length

    def chunks(x):
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        # (B, n*C, H, ...) -> (n, B, H, C, ...)
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    xs = (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta))
    with jax.named_scope("kda_chunk"):
        state, o = jax.lax.scan(_chunk_body, state.astype(f32), xs)
    # (n, B, H, C, Dv) -> (B, L, H, Dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * c, h, -1)
    return o[:, :length], state


# ---------------------------------------------------------------------------
# the decode kernel
# ---------------------------------------------------------------------------

def kernel_tiles(state_shape, state_dtype) -> bool:
    """Can :func:`kda_decode` take this state?  (slots, H, Dk, Dv) float32
    whose (Dk, Dv) is one 128 x 128 tile and whose heads divide into blocks
    of ``_HEAD_BLOCK``."""
    if len(state_shape) != 4 or jnp.dtype(state_dtype) != jnp.float32:
        return False
    _, h, dk, dv = state_shape
    return dk == 128 and dv == 128 and h % _HEAD_BLOCK == 0


def _column(row, eye):
    """A (1, n) row as an (n, 1) column without a relayout: the diagonal of
    the row broadcast down the sublanes, summed along the lanes."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _kda_decode_kernel(order_ref, n_ref,                         # prefetch
                       q_ref, k_ref, v_ref, a_ref, b_ref, s_ref,  # inputs
                       o_ref, s_out_ref):                        # outputs
    i = pl.program_id(0)
    hb, dk = q_ref.shape[1], q_ref.shape[2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

    @pl.when(i < n_ref[0])
    def _():
        for h in range(hb):
            k_col = _column(k_ref[0, h:h + 1, :], eye)          # (Dk, 1)
            q_col = _column(q_ref[0, h:h + 1, :], eye)
            a_col = _column(a_ref[0, h:h + 1, :], eye)
            s = s_ref[0, h] * a_col                             # (Dk, Dv)
            u = v_ref[0, h:h + 1, :] - jnp.sum(s * k_col, axis=0,
                                               keepdims=True)
            s = s + k_col * (b_ref[0, h:h + 1, :] * u)
            s_out_ref[0, h] = s
            o_ref[0, h:h + 1, :] = jnp.sum(s * q_col, axis=0, keepdims=True)

    @pl.when(n_ref[0] == 0)
    def _():
        # no live slot: every step sits on one block, which goes back as it
        # came (its output buffer is written out when the grid ends)
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("state",))
def kda_decode(q, k, v, g, beta, state, live, *,
               interpret: Optional[bool] = None):
    """The decode step of every slot in one kernel.  ``q``, ``k``, ``g``:
    (B, H, Dk); ``v``: (B, H, Dv); ``beta``: (B, H); ``state``: (B, H, Dk, Dv)
    float32, donated and updated in place; ``live``: (B,) bool.  A live
    slot's state is read once and written once; a dead slot's state is not
    touched and its output row is zero.  Returns ``(o (B, H, Dv) float32,
    state)``.

    The grid walks the live slots first (``order``, prefetched): step
    ``(i, j)`` holds heads ``j * 8 .. j * 8 + 7`` of slot ``order[i]``; the
    steps past the last live slot all map to the block of the step before
    them, so the pipeline moves nothing for them."""
    f32 = jnp.float32
    b, h, dk = q.shape
    dv = v.shape[-1]
    hb = _HEAD_BLOCK
    nj = h // hb
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    live = live.astype(bool)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)[None]
    alpha = jnp.exp(g.astype(f32))
    beta_row = jnp.broadcast_to(beta.astype(f32)[..., None], (b, h, dv))

    def at(i, j, order_ref, n_ref):
        n = n_ref[0]
        on = i < n
        slot = order_ref[jnp.where(on, i, jnp.maximum(n - 1, 0))]
        return slot, jnp.where(on, j, nj - 1)

    def vec_map(i, j, order_ref, n_ref):
        return at(i, j, order_ref, n_ref) + (0,)

    def state_map(i, j, order_ref, n_ref):
        return at(i, j, order_ref, n_ref) + (0, 0)

    vec = pl.BlockSpec((1, hb, dk), vec_map)
    vec_v = pl.BlockSpec((1, hb, dv), vec_map)
    st = pl.BlockSpec((1, hb, dk, dv), state_map)
    o, state = pl.pallas_call(
        _kda_decode_kernel,
        out_shape=(jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, nj),
            in_specs=[vec, vec, vec_v, vec, vec_v, st],
            out_specs=(vec_v, st)),
        # operands count the two prefetched scalars: the state is the 8th
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode",
    )(order, n_live, q.astype(f32), k.astype(f32), v.astype(f32), alpha,
      beta_row, state)
    return jnp.where(live[:, None, None], o, 0.0), state
