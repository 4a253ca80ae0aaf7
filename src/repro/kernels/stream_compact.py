"""Stream compaction (paper §4; Billeter et al. HPG'09) adapted to TPU.

The GPU algorithm is a 3-phase compaction built on intra-warp shuffles:
(1) per-work-group valid counts, (2) prefix over counts, (3) move.
Warp shuffles have no TPU analogue (DESIGN.md §2), so the per-block local
compaction is re-expressed as a **one-hot permutation matmul** on the MXU:

    p        = valid @ upper_tri(bs) - 1         # destination within block
    onehot   = (p[src] == dst) & valid[src]      # (bs × bs) 0/1 matrix
    compact  = values @ onehotᵀ                  # exact: one byte at a time

A grid step takes up to eight such blocks, one per sublane row of its
tile, since the TPU lowering needs 8-row tiles (or the whole array).

One Pallas pass emits, per block, the locally-compacted values and the
valid count. The global move (Billeter's phase 3) is one XLA scatter in
``ops.stream_compact``: survivor ``j`` of block ``b`` goes to slot
``base[b] + j``, ``base`` the exclusive prefix sum of the counts —
irregular data movement is XLA's job on TPU; regular compute stays in the
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .tiling import as_i32, block_rows, pad_rows

__all__ = ["pallas_local_compact"]

_NT = (((1,), (1,)), ((), ()))        # contract both operands' last dims


def _local_compact_kernel(x_ref, out_ref, cnt_ref, *, rows: int, bs: int,
                          drop_value: int):
    # Each row of the tile is one ``bs``-element block. Values move as four
    # bytes, which (like the 0/1 one-hot) are exact in bf16, so the MXU
    # permutation is exact at any matmul precision.
    x = x_ref[...]                                          # (rows, bs) int32
    valid = x != drop_value
    r = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    incl = jnp.dot(valid.astype(jnp.bfloat16), (r <= c).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)      # inclusive cumsum
    p = incl.astype(jnp.int32) - 1                          # dest idx
    cnt_ref[...] = jnp.sum(valid.astype(jnp.int32), axis=1, keepdims=True)
    for i in range(rows):
        onehot = ((p[i:i + 1] == r) & valid[i:i + 1]).astype(
            jnp.bfloat16)                                   # (dst, src)
        xi = x[i:i + 1]
        comp = jnp.zeros((1, bs), jnp.int32)
        for k in range(4):
            byte = jax.lax.shift_right_logical(xi, jnp.int32(8 * k)) & 0xFF
            moved = jax.lax.dot_general(
                byte.astype(jnp.float32).astype(jnp.bfloat16), onehot, _NT,
                preferred_element_type=jnp.float32)         # (1, bs) exact
            comp = comp | (moved.astype(jnp.int32) << (8 * k))
        out_ref[i:i + 1, :] = comp


@functools.partial(jax.jit, static_argnames=("bs", "drop_value", "interpret"))
def pallas_local_compact(x: jax.Array, *, bs: int = 256, drop_value: int = 0,
                         interpret: bool = False):
    """Per-block compaction. ``x`` is uint32 of length divisible by ``bs``.

    Returns ``(blocks, counts)``: ``blocks[b, :counts[b]]`` are the
    surviving elements of block ``b`` in order.
    """
    (n,) = x.shape
    assert n % bs == 0, (n, bs)
    nb = n // bs
    rows, padded = block_rows(nb)
    drop = int(np.uint32(drop_value).view(np.int32))
    xb = pad_rows(as_i32(x).reshape(nb, bs), padded, drop)
    blocks, counts = pl.pallas_call(
        functools.partial(_local_compact_kernel, rows=rows, bs=bs,
                          drop_value=drop),
        grid=(padded // rows,),
        in_specs=[pl.BlockSpec((rows, bs), lambda b: (b, 0))],
        out_specs=[
            pl.BlockSpec((rows, bs), lambda b: (b, 0)),
            pl.BlockSpec((rows, 1), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, bs), jnp.int32),
            jax.ShapeDtypeStruct((padded, 1), jnp.int32),
        ],
        interpret=interpret,
    )(xb)
    return jax.lax.bitcast_convert_type(blocks[:nb], jnp.uint32), counts[:nb]
