"""The vma-typed ``shard_map`` surface the parallel stack is written
against (jax >= 0.9, ``setup.py``), resolved in one place: every call site
uses the keyword form (``mesh=``/``in_specs=``/``out_specs=``).
"""

import jax

shard_map = jax.shard_map
pcast = jax.lax.pcast
axis_size = jax.lax.axis_size


def vma_of(x):
    """Mesh axes ``x`` varies over (empty tuple when untyped)."""
    return getattr(jax.typeof(x), "vma", ()) or ()
