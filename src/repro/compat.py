"""The manual-collective surface every module imports.

Values inside ``shard_map`` carry *varying manual axes* (vma) metadata,
inspectable through ``jax.typeof(x).vma``.  With ``check_vma=True`` (the
default) a replicated value must be cast to varying before it is mixed
with varying operands; ``vary`` does that for exactly the axes it is
missing, so call sites need not track which axes a value already varies
over:

    from repro.compat import shard_map, vma_of, vary, axis_size
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax import lax

__all__ = ["shard_map", "vma_of", "vary", "axis_size"]

shard_map = jax.shard_map
axis_size = lax.axis_size


def vma_of(x) -> frozenset:
    """The set of manual axes ``x`` varies over."""
    return frozenset(jax.typeof(x).vma)


def vary(x, axis_names: Sequence[str]):
    """Cast ``x`` to varying over the axes of ``axis_names`` it does not
    already vary over."""
    missing = tuple(a for a in axis_names if a not in vma_of(x))
    return lax.pcast(x, missing, to="varying") if missing else x
