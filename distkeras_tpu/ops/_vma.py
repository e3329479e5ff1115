"""Varying-mesh-axes (vma) plumbing for Pallas kernels under shard_map.

jax's shard_map tracks, per value, the set of mesh axes it varies over and
refuses ops that mix mismatched sets (``check_vma``).  Two places in a
Pallas kernel need explicit plumbing when the kernel is traced inside a
shard_map region (compiled TPU kernels trace in a fresh context and never
see vma; *interpret mode* — the CPU test path — inlines the kernel body
into the traced program, so its ops do):

 - ``out_struct``: pallas_call output avals must declare their vma (a
   kernel output varies exactly as its inputs do);
 - ``match_vma``: kernel-internal constants (iota position grids, masks)
   are unvarying and must be ``pcast`` to varying before meeting varying
   refs.

Both are no-ops outside shard_map and in compiled kernels.
"""

from __future__ import annotations

import jax

_EMPTY = frozenset()


def _vma_of(x):
    return getattr(jax.typeof(x), "vma", None) or _EMPTY


def out_struct(shape, dtype, like):
    """ShapeDtypeStruct for a pallas output varying as ``like`` does."""
    vma = _vma_of(like)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def match_vma(x, like):
    """Lift ``x`` (typically an iota/mask built in-kernel) to ``like``'s
    varying axes so elementwise ops between them type-check."""
    missing = tuple(a for a in _vma_of(like) if a not in _vma_of(x))
    return jax.lax.pcast(x, missing, to="varying") if missing else x
