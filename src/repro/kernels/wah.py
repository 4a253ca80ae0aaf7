"""WAH ``prepare_index`` kernel (paper §4, Listing 5; Fusco et al. IMC'13).

``fuseFillsLiterals`` first interleaves the fill and literal arrays into a
combined index array (``out[2i] = fills[i], out[2i+1] = literals[i]``)
before stream-compacting the zero entries. The interleave is a pure
layout transform — on TPU a ``(rows, 128)`` tile of each input per grid
step, written as an interleaved ``(rows, 256)`` tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import block_rows, pad_rows

__all__ = ["pallas_wah_interleave"]

_LANES = 128


def _interleave_kernel(f_ref, l_ref, o_ref):
    f, l = f_ref[...], l_ref[...]                    # (rows, 128)
    pair = jnp.stack([f, l], axis=-1)                # (rows, 128, 2)
    o_ref[...] = pair.reshape(f.shape[0], 2 * f.shape[1])


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def pallas_wah_interleave(fills: jax.Array, literals: jax.Array, *,
                          bs: int = 512, interpret: bool = False) -> jax.Array:
    """``bs`` is the number of elements of each input per grid step, rounded
    up to whole ``(8, 128)`` tiles."""
    (n,) = fills.shape
    assert fills.shape == literals.shape
    assert n % bs == 0 and bs % _LANES == 0, (n, bs)
    nr = n // _LANES
    rows, padded = block_rows(nr, bs // _LANES)
    f, l = (pad_rows(a.reshape(nr, _LANES), padded) for a in (fills, literals))
    out = pl.pallas_call(
        _interleave_kernel,
        grid=(padded // rows,),
        in_specs=[
            pl.BlockSpec((rows, _LANES), lambda b: (b, 0)),
            pl.BlockSpec((rows, _LANES), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((rows, 2 * _LANES), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, 2 * _LANES), fills.dtype),
        interpret=interpret,
    )(f, l)
    return out[:nr].reshape(2 * n)
