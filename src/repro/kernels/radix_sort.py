"""LSD radix sort digit pass (paper §4: "radix sort using a fixed
cardinality of 16 bits") adapted to TPU.

Per digit pass the GPU version builds per-work-group histograms and ranks
with warp ballots. The TPU kernel computes, per block and entirely on the
MXU/VPU:

    onehotT[bin, src] = (digit[src] == bin)              # (nbins × bs)
    hist[bin]         = ones(1,bs) @ onehotTᵀ            # digit histogram
    beforeT           = onehotT @ strict_upper_tri(bs)   # prefix per bin
    rank[src]         = Σ_bin beforeT[bin,src] * onehotT[bin,src]

A grid step takes up to eight such blocks, one per sublane row of its
tile, since the TPU lowering needs 8-row tiles (or the whole array).

The wrapper (``ops.radix_sort``) turns (hist, rank) into global
destination indices with two tiny cumsums and applies the permutation with
one XLA scatter per pass — the irregular move again delegated to XLA,
mirroring the compaction design (DESIGN.md §2).

The Pallas path supports digit widths up to 8 bits (nbins ≤ 256 keeps the
onehot in VMEM); the paper's 16-bit cardinality runs on the oracle path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import as_i32, block_rows, pad_rows

__all__ = ["pallas_radix_pass"]

_NT = (((1,), (1,)), ((), ()))        # contract both operands' last dims


def _radix_pass_kernel(x_ref, hist_ref, rank_ref, *, rows: int, bs: int,
                       nbins: int, shift: int):
    # Each row of the tile is one ``bs``-element block. The one-hot is
    # built transposed, (nbins, bs), so that the digits stay on the lanes
    # and no lane/sublane relayout is needed. 0/1 values are exact in bf16.
    bins = jax.lax.broadcasted_iota(jnp.int32, (nbins, bs), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    upper = (r < c).astype(jnp.bfloat16)                       # strictly upper
    ones_row = jnp.ones((1, bs), jnp.bfloat16)
    for i in range(rows):
        x = x_ref[i:i + 1, :]                                  # (1, bs) int32
        digit = jax.lax.shift_right_logical(x, jnp.int32(shift)) & (nbins - 1)
        onehot_t = (digit == bins).astype(jnp.bfloat16)        # (nbins, bs)
        hist = jax.lax.dot_general(ones_row, onehot_t, _NT,
                                   preferred_element_type=jnp.float32)
        hist_ref[i:i + 1, :] = hist.astype(jnp.int32)          # (1, nbins)
        before_t = jnp.dot(onehot_t, upper,
                           preferred_element_type=jnp.float32)  # (nbins, bs)
        rank = jnp.sum(before_t * onehot_t.astype(jnp.float32), axis=0,
                       keepdims=True)                          # (1, bs)
        rank_ref[i:i + 1, :] = rank.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bs", "bits", "shift", "interpret"))
def pallas_radix_pass(x: jax.Array, *, bs: int = 256, bits: int = 8,
                      shift: int = 0, interpret: bool = False):
    """One digit pass. Returns ``(hist[nb, nbins], rank[nb, bs])``."""
    assert bits <= 8, "Pallas path supports ≤8-bit digits (VMEM onehot)"
    (n,) = x.shape
    assert n % bs == 0, (n, bs)
    nb, nbins = n // bs, 1 << bits
    rows, padded = block_rows(nb)
    xb = pad_rows(as_i32(x).reshape(nb, bs), padded)
    hist, rank = pl.pallas_call(
        functools.partial(_radix_pass_kernel, rows=rows, bs=bs, nbins=nbins,
                          shift=shift),
        grid=(padded // rows,),
        in_specs=[pl.BlockSpec((rows, bs), lambda b: (b, 0))],
        out_specs=[
            pl.BlockSpec((rows, nbins), lambda b: (b, 0)),
            pl.BlockSpec((rows, bs), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, nbins), jnp.int32),
            jax.ShapeDtypeStruct((padded, bs), jnp.int32),
        ],
        interpret=interpret,
    )(xb)
    return hist[:nb], rank[:nb]
