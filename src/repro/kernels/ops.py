"""Public jit'd wrappers for the kernel layer.

Each op dispatches between the Pallas kernel (TPU target; ``interpret=True``
executes the kernel body on CPU for validation) and the pure-jnp oracle in
:mod:`repro.kernels.ref`. ``impl`` ∈ {"auto", "pallas", "ref"}: "auto"
selects Pallas on TPU and interpreted Pallas elsewhere for the compaction/
sort/interleave family, and the oracle for attention (where interpreted
execution would be prohibitively slow at model shapes).

These wrappers also hold the XLA halves of the TPU adaptations: the
compaction's prefix-sum scatter and the radix-scatter permutation (see the
kernel module docstrings for why the irregular move lives in XLA on TPU).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .ref import radix_passes
from .flash_attention import pallas_flash_attention
from .mandelbrot import pallas_mandelbrot
from .matmul import pallas_matmul
from .moe_held import pallas_moe_held_experts
from .radix_sort import pallas_radix_pass
from .stream_compact import pallas_local_compact
from .wah import pallas_wah_interleave

__all__ = ["matmul", "mandelbrot", "stream_compact", "radix_sort",
           "radix_passes", "wah_interleave", "flash_attention",
           "moe_held_experts", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(impl: str) -> Tuple[bool, bool]:
    """→ (use_pallas, interpret)."""
    if impl == "ref":
        return False, False
    if impl == "pallas":
        return True, not on_tpu()
    if impl == "auto":
        return True, not on_tpu()
    raise ValueError(f"impl={impl!r}")


# ----------------------------------------------------------------------------
def matmul(a, b, *, impl: str = "auto", bm: int = 128, bn: int = 128,
           bk: int = 128):
    use, interp = _use_pallas(impl)
    m, k = a.shape
    _, n = b.shape
    if not use or m % bm or n % bn or k % bk:
        return ref.matmul(a, b)
    return pallas_matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=interp)


# ----------------------------------------------------------------------------
def mandelbrot(*, height: int, width: int, max_iter: int,
               re_min: float, re_max: float, im_min: float, im_max: float,
               row_offset: int = 0, total_height: Optional[int] = None,
               impl: str = "auto"):
    use, interp = _use_pallas(impl)
    th = total_height if total_height is not None else height
    if use and height % 8 == 0 and width % 128 == 0:
        return pallas_mandelbrot(height=height, width=width, max_iter=max_iter,
                                 re_min=re_min, re_max=re_max, im_min=im_min,
                                 im_max=im_max, row_offset=row_offset,
                                 total_height=th, interpret=interp)
    re_step = (re_max - re_min) / max(width - 1, 1)
    im_step = (im_max - im_min) / max(th - 1, 1)
    x = re_min + jnp.arange(width, dtype=jnp.float32)[None, :] * re_step
    y = im_min + (jnp.arange(height, dtype=jnp.float32)[:, None] + row_offset) * im_step
    return ref.mandelbrot(x, y, max_iter)


# ----------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("bs", "drop_value", "impl"))
def stream_compact(x, *, bs: int = 256, drop_value: int = 0,
                   impl: str = "auto"):
    """Compacted array (prefix-valid layout) + surviving count."""
    use, interp = _use_pallas(impl)
    n = x.shape[0]
    if not use or n % bs:
        return ref.stream_compact(x, drop_value)
    blocks, counts = pallas_local_compact(x.astype(jnp.uint32), bs=bs,
                                          drop_value=drop_value,
                                          interpret=interp)
    out, total = _place_blocks(blocks, counts)
    return out.astype(x.dtype), total


def _place_blocks(blocks, counts):
    """Billeter phase 3: concatenate each block's valid prefix, in order.

    ``blocks[b, :counts[b, 0]]`` are block ``b``'s survivors (the layout
    of ``pallas_local_compact``), so survivor ``j`` lands at ``base[b] + j``
    with ``base`` the exclusive prefix sum of the counts: one scatter, no
    search. Every dropped lane gets an out-of-range index of its own (so
    ``unique_indices`` holds) and leaves its slot zero.
    → ``(out, total)``, ``out`` prefix-valid and as long as ``blocks``.
    """
    nb, bs = blocks.shape
    n = nb * bs
    base = jnp.cumsum(counts, axis=0) - counts                # (nb, 1)
    lane = jnp.arange(bs, dtype=jnp.int32)
    slot = jnp.arange(nb, dtype=jnp.int32)[:, None] * bs + lane
    dest = jnp.where(lane < counts, base + lane, n + slot)
    out = jnp.zeros(n, blocks.dtype).at[dest.reshape(-1)].set(
        blocks.reshape(-1), mode="drop", unique_indices=True)
    return out, jnp.sum(counts).astype(jnp.int32)


# ----------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("bits_per_pass", "key_bits", "bs", "impl"))
def radix_sort(keys, values=None, *, bits_per_pass: int = 8,
               key_bits: int = 32, bs: int = 256, impl: str = "auto"):
    """Stable LSD radix sort of uint32 keys (+ optional payload).

    Sorts by the low ``key_bits`` bits in ``radix_passes(key_bits,
    bits_per_pass)`` digit passes, so keys must lie below ``2**key_bits``:
    a pass over higher digits that are zero everywhere would only copy.
    """
    use, interp = _use_pallas(impl)
    n = keys.shape[0]
    if not use or n % bs or bits_per_pass > 8:
        return ref.radix_sort_u32(keys, values, bits_per_pass=bits_per_pass,
                                  key_bits=key_bits)
    k = keys.astype(jnp.uint32)
    nbins = 1 << bits_per_pass
    blk = jnp.arange(n, dtype=jnp.int32) // bs
    for p in range(radix_passes(key_bits, bits_per_pass)):
        with jax.named_scope(f"radix_pass{p}"):
            shift = p * bits_per_pass
            hist, rank = pallas_radix_pass(k, bs=bs, bits=bits_per_pass,
                                           shift=shift, interpret=interp)
            # global base per digit (exclusive over bins, summed over blocks)
            total = jnp.sum(hist, axis=0)                      # (nbins,)
            gbase = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                     jnp.cumsum(total)[:-1]])  # (nbins,)
            # per-(block, digit) offset: exclusive cumsum over blocks
            bprefix = jnp.concatenate(
                [jnp.zeros((1, nbins), jnp.int32),
                 jnp.cumsum(hist, axis=0)[:-1]], axis=0)       # (nb, nbins)
            digit = ((k >> jnp.uint32(shift))
                     & jnp.uint32(nbins - 1)).astype(jnp.int32)
            dest = gbase[digit] + bprefix[blk, digit] + rank.reshape(-1)
            # ``dest`` is a permutation of 0..n-1; the payload rides along
            k = jnp.zeros_like(k).at[dest].set(k, unique_indices=True)
            if values is not None:
                values = jnp.zeros_like(values).at[dest].set(
                    values, unique_indices=True)
    if values is None:
        return k
    return k, values


# ----------------------------------------------------------------------------
def wah_interleave(fills, literals, *, bs: int = 512, impl: str = "auto"):
    use, interp = _use_pallas(impl)
    n = fills.shape[0]
    if not use or n % bs:
        return ref.wah_interleave(fills, literals)
    return pallas_wah_interleave(fills, literals, bs=bs, interpret=interp)


# ----------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, impl: str = "auto",
                    bq: int = 128, bk: int = 128):
    if impl == "pallas" or (impl == "auto" and on_tpu()):
        return pallas_flash_attention(q, k, v, causal=causal, window=window,
                                      bq=bq, bk=bk, interpret=not on_tpu())
    return ref.flash_attention(q, k, v, causal=causal, window=window)


# ----------------------------------------------------------------------------
#: rows of a held-expert kernel tile; each expert's group is padded to whole
#: tiles
MOE_ROWS = 16


def _expert_tile(d: int, f: int, itemsize: int,
                 budget: int = 48 << 20) -> int:
    """The widest F tile (a multiple of 128 dividing F) whose gate, up and
    down blocks, double-buffered, fit ``budget`` bytes of VMEM."""
    if f % 128:
        return f
    fits = [t for t in range(128, f + 1, 128)
            if f % t == 0 and 2 * 3 * d * t * itemsize <= budget]
    return max(fits) if fits else 128


def moe_held_experts(x, expert_idx, expert_w, w_gate, w_up, w_down, lo: int,
                     *, layer=None, impl: str = "auto",
                     tf: Optional[int] = None):
    """Dropless routed part of an expert layer, over the experts held here.

    ``x [T, D]``; ``expert_idx``/``expert_w [T, k]``: each token's chosen
    experts (global ids) and their routing weights; ``w_* [E_h, ...]``: the
    held experts ``lo .. lo + E_h - 1``, or with ``layer`` (an int32
    scalar) those weights stacked over layers, ``[L, E_h, ...]``, of which
    that layer is used. Choices of experts held elsewhere add nothing.
    → ``(y [T, D], held_choices, touched_experts)``, the last two int32
    scalars: (token, choice) pairs that landed here, and held experts at
    least one token chose.

    Every token's choices are kept (no capacity): the choices are sorted by
    expert into groups of whole ``MOE_ROWS``-row tiles and
    ``pallas_moe_held_experts`` runs each chosen expert once over its
    group; "ref" runs every held expert on every token
    (``ref.moe_held_experts``).
    """
    t, k = expert_idx.shape
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    ne = w_gate.shape[1]
    local = expert_idx.reshape(-1).astype(jnp.int32) - lo
    held = (local >= 0) & (local < ne)
    onehot = (held[:, None] & (local[:, None] == jnp.arange(ne))
              ).astype(jnp.int32)                                # [T*k, E_h]
    counts = jnp.sum(onehot, axis=0)
    n_held = jnp.sum(counts)
    touched = jnp.sum((counts > 0).astype(jnp.int32))
    use, interp = _use_pallas(impl)
    if not use:
        return (ref.moe_held_experts(x, expert_idx, expert_w, w_gate[layer],
                                     w_up[layer], w_down[layer], lo),
                n_held, touched)
    tm = MOE_ROWS
    tiles = (counts + tm - 1) // tm
    start = jnp.cumsum(tiles * tm) - tiles * tm
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    m = -(-(t * k + ne * (tm - 1)) // tm) * tm
    pos = jnp.where(held, start[jnp.clip(local, 0, ne - 1)] + rank, m)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    src = jnp.full((m,), t, jnp.int32).at[pos].set(tok, mode="drop")
    row_w = jnp.zeros((m,), jnp.float32).at[pos].set(
        expert_w.reshape(-1).astype(jnp.float32), mode="drop")
    xs = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])[src]
    tf = tf or _expert_tile(x.shape[1], w_gate.shape[3],
                            jnp.dtype(w_gate.dtype).itemsize)

    def run(xs):
        return pallas_moe_held_experts(xs, row_w[:, None], start, tiles,
                                       w_gate, w_up, w_down, layer, tm=tm,
                                       tf=tf, interpret=interp)

    ys = jax.lax.cond(n_held > 0, run, jnp.zeros_like, xs)
    picked = jnp.where(held[:, None], ys[jnp.minimum(pos, m - 1)], 0)
    y = jnp.sum(picked.reshape(t, k, -1).astype(jnp.float32), axis=1)
    return y.astype(x.dtype), n_held, touched
