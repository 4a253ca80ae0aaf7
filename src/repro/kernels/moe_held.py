"""Grouped SwiGLU over the routed experts one chip holds (decode MoE).

Megablox-style grouped matmul for the decode regime, where each held expert
sees a few tokens a step and the step's floor is the experts' weight bytes.
The routed (token, choice) rows arrive sorted by expert, each expert's group
padded to a whole number of ``tm``-row tiles (``ops.moe_held_experts`` does
the sort). Scalar prefetch carries, per expert, the group's first row and
its tile count, and which weight block to map.

The weights come stacked over a group's layers (``[L, E, ...]``) with the
layer as a scalar: a decode step's layer scan hands the kernel the whole
stacks, since one layer's slice as the kernel's operand is a copy of every
held expert of that layer, chosen or not, at every step.

The grid is (expert, F tile). Step ``(e, j)`` streams tile ``j`` of expert
``e``'s gate, up and down weights into VMEM and runs only the row tiles of
``e``'s group: ``acc[rows] += w_rows * (silu(x W_g) * (x W_u)) W_d``, all
rows of the step resident in VMEM with a float32 accumulator. An expert no
row chose maps the block the previous step already holds (or, before the
first chosen expert, the block the next one needs), so the pipeline fetches
nothing for it: a step reads the weights of chosen experts only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pallas_moe_held_experts", "weight_blocks"]


def _kernel(layer_ref, offs_ref, ntiles_ref, we_ref, act_ref, jfix_ref,
            x_ref, rw_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, tm: int):
    e = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((e == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = offs_ref[e]

    def row_tile(t, carry):
        r0 = pl.multiple_of(start + t * tm, tm)
        xs = x_ref[pl.ds(r0, tm), :]
        g = jnp.dot(xs, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(xs, wu_ref[0], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(wd_ref.dtype)
        y = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)
        acc_ref[pl.ds(r0, tm), :] += y * rw_ref[pl.ds(r0, tm), :]
        return carry

    jax.lax.fori_loop(0, ntiles_ref[e], row_tile, 0)

    @pl.when((e == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def weight_blocks(group_tiles, nj: int):
    """Which weight block grid step ``(e, j)`` maps: ``(we[e], j * act[e] +
    jfix[e])``. A chosen expert maps its own tiles in turn; an expert no
    row chose maps the last tile of the latest chosen expert before it, or
    the first tile of the first chosen one: the block the pipeline already
    holds or fetches next, so nothing is read for it."""
    ne = group_tiles.shape[0]
    chosen = group_tiles > 0
    idx = jnp.arange(ne, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(chosen, idx, -1), axis=0)
    first = jnp.argmax(chosen).astype(jnp.int32)
    we = jnp.where(chosen, idx, jnp.where(last >= 0, last, first))
    act = chosen.astype(jnp.int32)
    jfix = jnp.where(chosen | (last < 0), 0, nj - 1).astype(jnp.int32)
    return we, act, jfix


@functools.partial(jax.jit, static_argnames=("tm", "tf", "interpret"))
def pallas_moe_held_experts(x, row_w, group_start, group_tiles, w_gate, w_up,
                            w_down, layer, *, tm: int = 16, tf: int = 128,
                            interpret: bool = False):
    """Rows of ``x [M, D]`` (sorted by expert, groups ``tm``-aligned) through
    their experts' SwiGLU, scaled by ``row_w [M, 1]`` (float32).

    ``group_start[e]``/``group_tiles[e]``: expert ``e``'s first row and its
    number of ``tm``-row tiles (0: no row chose it). ``w_gate``/``w_up``
    ``[L, E, D, F]``, ``w_down [L, E, F, D]``, of which layer ``layer`` (an
    int32 scalar) is used; ``tf`` divides F. Rows outside every group come
    out 0. → ``[M, D]`` in ``x.dtype``.
    """
    m, d = x.shape
    _, ne, _, f = w_gate.shape
    assert m % tm == 0 and f % tf == 0, (x.shape, w_gate.shape, tm, tf)
    nj = f // tf
    we, act, jfix = weight_blocks(group_tiles, nj)

    def w_in(e, j, layer, offs, ntiles, we, act, jfix):
        return layer[0], we[e], 0, j * act[e] + jfix[e]

    def w_out(e, j, layer, offs, ntiles, we, act, jfix):
        return layer[0], we[e], j * act[e] + jfix[e], 0

    def whole(e, j, *_):
        return 0, 0

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(ne, nj),
        in_specs=[pl.BlockSpec((m, d), whole),
                  pl.BlockSpec((m, 1), whole),
                  pl.BlockSpec((None, 1, d, tf), w_in),
                  pl.BlockSpec((None, 1, d, tf), w_in),
                  pl.BlockSpec((None, 1, tf, d), w_out)],
        out_specs=pl.BlockSpec((m, d), whole),
        scratch_shapes=[pltpu.VMEM((m, d), jnp.float32)])
    weight_block = 3 * d * tf * w_gate.dtype.itemsize
    resident = m * d * (2 * x.dtype.itemsize + 4) + m * 4
    vmem = 2 * weight_block + 2 * resident + (8 << 20)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(max(vmem, 32 << 20), 120 << 20))),
        interpret=interpret,
        name="moe_held_experts",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      group_start.astype(jnp.int32), group_tiles.astype(jnp.int32), we, act,
      jfix, x, row_w.astype(jnp.float32), w_gate, w_up, w_down)
