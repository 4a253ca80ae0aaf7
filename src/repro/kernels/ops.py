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
from .flash_attention import pallas_flash_attention
from .mandelbrot import pallas_mandelbrot
from .matmul import pallas_matmul
from .radix_sort import pallas_radix_pass
from .stream_compact import pallas_local_compact
from .wah import pallas_wah_interleave

__all__ = ["matmul", "mandelbrot", "stream_compact", "radix_sort",
           "wah_interleave", "flash_attention", "on_tpu"]


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
@functools.partial(jax.jit, static_argnames=("bits_per_pass", "bs", "impl"))
def radix_sort(keys, values=None, *, bits_per_pass: int = 8, bs: int = 256,
               impl: str = "auto"):
    """Stable LSD radix sort of uint32 keys (+ optional payload)."""
    use, interp = _use_pallas(impl)
    n = keys.shape[0]
    if not use or n % bs or bits_per_pass > 8:
        return ref.radix_sort_u32(keys, values, bits_per_pass=bits_per_pass)
    k = keys.astype(jnp.uint32)
    idx = jnp.arange(n, dtype=jnp.int32)
    nb, nbins = n // bs, 1 << bits_per_pass
    for p in range(32 // bits_per_pass):
        shift = p * bits_per_pass
        hist, rank = pallas_radix_pass(k, bs=bs, bits=bits_per_pass,
                                       shift=shift, interpret=interp)
        # global base per digit (exclusive over bins, summed over blocks)
        total = jnp.sum(hist, axis=0)                          # (nbins,)
        gbase = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                 jnp.cumsum(total)[:-1]])      # (nbins,)
        # per-(block, digit) offset: exclusive cumsum over blocks
        bprefix = jnp.concatenate(
            [jnp.zeros((1, nbins), jnp.int32),
             jnp.cumsum(hist, axis=0)[:-1]], axis=0)           # (nb, nbins)
        digit = ((k >> jnp.uint32(shift)) & jnp.uint32(nbins - 1)).astype(jnp.int32)
        blk = jnp.arange(n, dtype=jnp.int32) // bs
        dest = gbase[digit] + bprefix[blk, digit] + rank.reshape(-1)
        k = jnp.zeros_like(k).at[dest].set(k)
        idx = jnp.zeros_like(idx).at[dest].set(idx)
    if values is None:
        return k
    return k, jnp.take(values, idx)


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
