"""Mesh-axis typing for values computed inside ``shard_map``.

Inside ``shard_map`` every value carries the set of mesh axes it varies over
(its ``vma``). A value built from constants is replicated, while a result
computed from varying operands varies over their axes; scan carries,
``lax.cond`` branches and Pallas outputs must agree on that set, or the type
check rejects the mismatch. Outside ``shard_map`` every set is empty and
these helpers are no-ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def operand_vma(*operands) -> frozenset:
    """The union of the mesh axes the operands vary over."""
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def vary_all(*operands):
    """Cast every operand to vary over the union of their mesh axes, so a
    kernel body's elementwise ops see operands of one type; returns the
    operands and that union."""
    vma = operand_vma(*operands)
    return [jax.lax.pcast(x, tuple(vma - jax.typeof(x).vma), to="varying")
            for x in operands], vma


def zeros_varying_like(shape, *operands):
    """f32 zeros that vary over every mesh axis ``operands`` vary over: the
    start of an accumulator (or a skipped branch's result) that must type
    like values computed from those operands."""
    return jax.lax.pcast(jnp.zeros(shape, jnp.float32),
                         tuple(operand_vma(*operands)), to="varying")
