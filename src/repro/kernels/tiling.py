"""Row tiling shared by the 1-D kernels (radix pass, compaction, interleave).

Those kernels view a flat array as ``(rows, lanes)`` and give each grid
step a ``(block_rows, lanes)`` tile. The TPU lowering accepts a block
only if its second-to-last dimension is a multiple of 8 or the whole
extent, so short arrays take one full-extent block and longer ones are
padded up to whole 8-row multiples.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["block_rows", "pad_rows", "as_i32"]


def block_rows(rows: int, want: int = 8) -> Tuple[int, int]:
    """→ ``(block, padded_rows)`` for a ``rows``-row array."""
    if rows <= want:
        return rows, rows
    block = -(-want // 8) * 8
    return block, -(-rows // block) * block


def pad_rows(x: jax.Array, padded_rows: int, value=0) -> jax.Array:
    extra = padded_rows - x.shape[0]
    if extra == 0:
        return x
    return jnp.pad(x, ((0, extra), (0, 0)), constant_values=value)


def as_i32(x: jax.Array) -> jax.Array:
    """uint32 → int32 with the same bits (the kernels work in int32)."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)
