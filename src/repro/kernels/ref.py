"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth used by the per-kernel
allclose sweeps in ``tests/test_kernels_*.py`` and by the models/examples
when running on backends without Pallas support (``impl='ref'``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "matmul",
    "mandelbrot",
    "stream_compact",
    "radix_passes",
    "radix_sort_u32",
    "wah_interleave",
    "flash_attention",
]


# ----------------------------------------------------------------------------
# paper §3.3 — square (and rectangular) matrix product
# ----------------------------------------------------------------------------
def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


# ----------------------------------------------------------------------------
# paper §5.4 — Mandelbrot iteration counts
# ----------------------------------------------------------------------------
def mandelbrot(re0: jax.Array, im0: jax.Array, max_iter: int) -> jax.Array:
    """Iteration counts (int32) for z <- z^2 + c until |z| > 2.

    ``re0``/``im0`` are broadcastable coordinate grids. Implemented with a
    masked fori_loop — identical math to the kernel.
    """
    shape = jnp.broadcast_shapes(re0.shape, im0.shape)
    zr = jnp.zeros(shape, jnp.float32)
    zi = jnp.zeros(shape, jnp.float32)
    count = jnp.zeros(shape, jnp.int32)

    def body(_, carry):
        zr, zi, count = carry
        zr2, zi2 = zr * zr, zi * zi
        alive = (zr2 + zi2) <= 4.0
        new_zr = zr2 - zi2 + re0
        new_zi = 2.0 * zr * zi + im0
        zr = jnp.where(alive, new_zr, zr)
        zi = jnp.where(alive, new_zi, zi)
        count = count + alive.astype(jnp.int32)
        return zr, zi, count

    _, _, count = jax.lax.fori_loop(0, max_iter, body, (zr, zi, count))
    return count


# ----------------------------------------------------------------------------
# paper §4 — stream compaction (Billeter et al.)
# ----------------------------------------------------------------------------
def stream_compact(x: jax.Array, drop_value: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Remove all entries equal to ``drop_value``.

    Returns ``(compacted, count)`` where ``compacted`` has the input length
    with the ``count`` surviving elements first (prefix-valid layout — the
    TPU-friendly static-shape convention; OpenCL returns the new length in
    the config buffer the same way, paper Listing 5).
    """
    valid = x != drop_value
    count = jnp.sum(valid, dtype=jnp.int32)
    # stable order of survivors: sort by (invalid, original index)
    key = jnp.where(valid, 0, 1).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    compacted = jnp.where(jnp.arange(x.shape[0]) < count, x[order], 0)
    return compacted.astype(x.dtype), count


# ----------------------------------------------------------------------------
# paper §4 — LSD radix sort (fixed digit cardinality, paper uses 16 bits)
# ----------------------------------------------------------------------------
def radix_passes(key_bits: int, bits_per_pass: int) -> int:
    """Digit passes an LSD radix sort makes over keys of ``key_bits`` bits."""
    if not 1 <= key_bits <= 32:
        raise ValueError(f"key_bits={key_bits} not in 1..32")
    return -(-key_bits // bits_per_pass)


def radix_sort_u32(keys: jax.Array, values: Optional[jax.Array] = None,
                   bits_per_pass: int = 16, key_bits: int = 32):
    """Stable LSD radix sort of uint32 keys (optionally with a payload).

    Matches the paper's "radix sort using a fixed cardinality of 16 bits".
    The oracle uses jnp.argsort per digit pass to mirror pass structure.
    Only the low ``key_bits`` bits are sorted, so keys must lie below
    ``2**key_bits``.
    """
    k = keys.astype(jnp.uint32)
    idx = jnp.arange(k.shape[0])
    for p in range(radix_passes(key_bits, bits_per_pass)):
        digit = (k >> (p * bits_per_pass)) & ((1 << bits_per_pass) - 1)
        order = jnp.argsort(digit.astype(jnp.int32), stable=True)
        k = k[order]
        idx = idx[order]
    if values is None:
        return k
    return k, jnp.take(values, idx)


# ----------------------------------------------------------------------------
# paper §4 — fuseFillsLiterals 'prepare_index': interleave fills & literals
# ----------------------------------------------------------------------------
def wah_interleave(fills: jax.Array, literals: jax.Array) -> jax.Array:
    """out[2i] = fills[i]; out[2i+1] = literals[i] (length 2k)."""
    assert fills.shape == literals.shape
    return jnp.stack([fills, literals], axis=1).reshape(-1)


# ----------------------------------------------------------------------------
# LM training hot spot — online-softmax attention oracle
# ----------------------------------------------------------------------------
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Reference attention. Shapes: q [B,H,Sq,D], k/v [B,Hkv,Skv,D]; GQA is
    expressed by Hkv dividing H. ``window`` limits attention to the last
    ``window`` positions (local attention, RecurrentGemma-style)."""
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    assert h % hkv == 0
    group = h // hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    skv = k.shape[2]
    qpos = jnp.arange(sq)[:, None] + (skv - sq)  # right-aligned positions
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > (qpos - window)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def moe_held_experts(x, expert_idx, expert_w, w_gate, w_up, w_down, lo: int):
    """Oracle of ``ops.moe_held_experts``: every held expert's SwiGLU on
    every token, weighted by that token's routing weight for the expert (0
    where it did not choose it). x [T, D] → [T, D]."""
    ne = w_gate.shape[0]
    gates = jnp.sum(jnp.where(
        (expert_idx[..., None] - lo) == jnp.arange(ne),
        expert_w[..., None].astype(jnp.float32), 0.0), axis=1)    # [T, E_h]
    g = jnp.einsum("td,edf->tef", x, w_gate)
    u = jnp.einsum("td,edf->tef", x, w_up)
    h = jax.nn.silu(g) * u
    y = jnp.einsum("tef,efd->ted", h, w_down)
    return jnp.einsum("ted,te->td", y.astype(jnp.float32),
                      gates).astype(x.dtype)
