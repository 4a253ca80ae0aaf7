"""Mandelbrot iteration kernel (paper §5.4 offload workload).

One VPU tile of pixels per grid step. Coordinates are derived in-kernel
from the global IDs (``broadcasted_iota`` over the tile + grid offsets) —
the TPU analogue of the OpenCL kernel calling ``get_global_id`` — so the
only input is a tiny scalar description of the viewport and the only
output is the iteration-count image. The escape-time loop runs masked
(SIMD predication) exactly like the GPU version.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pallas_mandelbrot"]


def _mandelbrot_kernel(o_ref, zr_ref, zi_ref, *, max_iter: int,
                       re_min: float, im_min: float, re_step: float,
                       im_step: float, bh: int, bw: int, row_offset: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    # the TPU's iota is integer-only; pixel indices are exact in f32
    rows = jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 1)
    # global pixel coordinates of this tile (NDRange offsets, paper §3.4)
    y = (rows + (i * bh + row_offset)).astype(jnp.float32)
    x = (cols + j * bw).astype(jnp.float32)
    cr = re_min + x * re_step
    ci = im_min + y * im_step

    # z and the count live in VMEM refs rather than in the loop carry: the
    # TPU lowering cannot carry a splat-initialised tile through the loop.
    zr_ref[...] = jnp.zeros((bh, bw), jnp.float32)
    zi_ref[...] = jnp.zeros((bh, bw), jnp.float32)
    o_ref[...] = jnp.zeros((bh, bw), jnp.int32)

    @pl.loop(0, max_iter)
    def _(_):
        zr, zi = zr_ref[...], zi_ref[...]
        zr2, zi2 = zr * zr, zi * zi
        alive = (zr2 + zi2) <= 4.0
        nzr = zr2 - zi2 + cr
        nzi = 2.0 * zr * zi + ci
        zr_ref[...] = jnp.where(alive, nzr, zr)
        zi_ref[...] = jnp.where(alive, nzi, zi)
        o_ref[...] += alive.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "height", "width", "max_iter", "re_min", "re_max", "im_min", "im_max",
    "bh", "bw", "row_offset", "total_height", "interpret"))
def pallas_mandelbrot(*, height: int, width: int, max_iter: int,
                      re_min: float, re_max: float, im_min: float, im_max: float,
                      bh: int = 8, bw: int = 128, row_offset: int = 0,
                      total_height: int | None = None,
                      interpret: bool = False) -> jax.Array:
    """Iteration counts for an ``height × width`` viewport slice.

    ``row_offset``/``total_height`` support the paper's fractional offload:
    a worker renders rows [row_offset, row_offset+height) of a
    ``total_height``-row image with consistent coordinates.
    """
    assert height % bh == 0 and width % bw == 0
    th = total_height if total_height is not None else height
    re_step = (re_max - re_min) / max(width - 1, 1)
    im_step = (im_max - im_min) / max(th - 1, 1)
    grid = (height // bh, width // bw)
    return pl.pallas_call(
        functools.partial(_mandelbrot_kernel, max_iter=max_iter,
                          re_min=re_min, im_min=im_min, re_step=re_step,
                          im_step=im_step, bh=bh, bw=bw, row_offset=row_offset),
        grid=grid,
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((height, width), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bh, bw), jnp.float32)] * 2,
        interpret=interpret,
    )()
