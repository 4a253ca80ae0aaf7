"""WAH bitmap-index construction, fully data-parallel (paper §4).

Follows Fusco et al. ("Indexing Million of Packets Per Second Using
GPUs", IMC'13) as summarized in the paper: (1) encode values with input
position, (2) stable sort by value, (3) derive 31-bit chunk literals via
segmented OR, (4) derive zero-fill words from chunk gaps, (5)
``fuseFillsLiterals`` — interleave + stream-compact (paper Listing 5),
(6) build the per-value lookup table.

WAH word format (Wu et al.): literal = MSB 0 + 31 payload bits;
fill = MSB 1, bit 30 = fill bit, bits 0..29 = count of 31-bit groups.
Trailing zero-fills are implicit (decode pads to ``n``).

Everything runs on static shapes with the prefix-valid convention so the
whole pipeline jits; the hot stages use the Pallas kernels. The
:func:`wah_index_pipeline_actors` variant wires the same computation as a
composed pipeline of kernel actors exchanging ``DeviceRef``s — the exact
shape of the paper's Listing 5.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

__all__ = ["build_wah_index", "build_wah_index_numpy", "decode_wah_bitmap",
           "wah_index_pipeline_actors"]

_FILL_FLAG = jnp.uint32(1) << 31
_COUNT_MASK = (1 << 30) - 1


@functools.partial(jax.jit, static_argnames=("cardinality", "impl"))
def build_wah_index(values: jax.Array, cardinality: int, impl: str = "auto"):
    """Build a WAH bitmap index of ``values`` (uint32 < cardinality).

    Every value must lie below ``cardinality``: the sort orders only the
    bits that ``cardinality - 1`` needs, and step (6) drops larger values.
    Returns ``(index_words, n_words, starts, counts)``: the compacted word
    stream, its logical length, and the per-value lookup table. ``impl``
    picks the sort/interleave/compaction kernels as in
    :mod:`repro.kernels.ops` (``"ref"``: the pure-jnp oracles).
    """
    n = values.shape[0]
    values = values.astype(jnp.uint32)
    pos = jnp.arange(n, dtype=jnp.int32)

    # (1)+(2): encode with position, stable sort by value → positions stay
    # ascending within each value, hence chunk ids are ascending.
    key_bits = max(1, (cardinality - 1).bit_length())
    v_sorted, pos_sorted = ops.radix_sort(values, pos, key_bits=key_bits,
                                          impl=impl)
    v_sorted = v_sorted.astype(jnp.int32)

    # (3): 31-bit chunk literals by segmented OR (distinct bits → sum).
    chunk = pos_sorted // 31
    bit = (pos_sorted % 31).astype(jnp.uint32)
    bitword = (jnp.uint32(1) << bit)

    first = jnp.ones((1,), bool)
    new_v = jnp.concatenate([first, v_sorted[1:] != v_sorted[:-1]])
    new_seg = new_v | jnp.concatenate([first, chunk[1:] != chunk[:-1]])
    seg = jnp.cumsum(new_seg.astype(jnp.int32)) - 1          # element → segment
    n_seg = seg[-1] + 1

    literals = jax.ops.segment_sum(bitword, seg, num_segments=n)
    seg_valid = jnp.arange(n) < n_seg
    literals = jnp.where(seg_valid, literals, 0).astype(jnp.uint32)
    seg_v = jnp.zeros(n, jnp.int32).at[seg].set(v_sorted)
    seg_chunk = jnp.zeros(n, jnp.int32).at[seg].set(chunk)

    # (4): zero-fill words from gaps between consecutive chunks of a value.
    prev_chunk = jnp.concatenate([jnp.full((1,), -1, jnp.int32), seg_chunk[:-1]])
    same_v = jnp.concatenate([jnp.zeros((1,), bool), seg_v[1:] == seg_v[:-1]])
    prev = jnp.where(same_v, prev_chunk, -1)
    gap = seg_chunk - prev - 1
    fills = jnp.where(seg_valid & (gap > 0),
                      _FILL_FLAG | gap.astype(jnp.uint32), 0).astype(jnp.uint32)

    # (5): fuseFillsLiterals — interleave then compact (paper Listing 5).
    fused = ops.wah_interleave(fills, literals, impl=impl)
    index_words, n_words = ops.stream_compact(fused, impl=impl)

    # (6): lookup table — words contributed per segment, summed per value.
    words_per_seg = jnp.where(seg_valid, (gap > 0).astype(jnp.int32) + 1, 0)
    counts = jax.ops.segment_sum(words_per_seg, seg_v, num_segments=cardinality)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    return index_words, n_words, starts, counts.astype(jnp.int32)


def build_wah_index_numpy(values: np.ndarray, cardinality: int):
    """Sequential CPU reference (the paper Fig. 3 CPU baseline)."""
    n = values.shape[0]
    words, starts, counts = [], np.zeros(cardinality, np.int64), np.zeros(
        cardinality, np.int64)
    for v in range(cardinality):
        starts[v] = len(words)
        positions = np.flatnonzero(values == v)
        prev_chunk = -1
        cur_chunk, cur_word = None, 0
        for p in positions:
            c, b = divmod(int(p), 31)
            if c != cur_chunk:
                if cur_chunk is not None:
                    words.append(cur_word)
                gap = c - prev_chunk - 1 if cur_chunk is None else c - cur_chunk - 1
                if cur_chunk is None:
                    gap = c
                if gap > 0:
                    words.append((1 << 31) | gap)
                prev_chunk = cur_chunk if cur_chunk is not None else -1
                cur_chunk, cur_word = c, 0
            cur_word |= (1 << b)
        if cur_chunk is not None:
            words.append(cur_word)
        counts[v] = len(words) - starts[v]
    return np.asarray(words, np.uint32), len(words), starts, counts


def decode_wah_bitmap(index_words: np.ndarray, start: int, count: int) -> np.ndarray:
    """Decode one value's WAH word stream back to a position list."""
    positions = []
    chunk = 0
    for w in np.asarray(index_words[start:start + count], np.uint32):
        w = int(w)
        if w >> 31:
            positions_len_before = len(positions)
            assert (w >> 30) & 1 == 0, "only zero-fills are emitted"
            chunk += w & _COUNT_MASK
            del positions_len_before
        else:
            for b in range(31):
                if w & (1 << b):
                    positions.append(chunk * 31 + b)
            chunk += 1
    return np.asarray(positions, np.int64)


# ----------------------------------------------------------------------------
# Actor-pipeline variant (paper Listing 5): three kernel actors composed.
# ----------------------------------------------------------------------------
def wah_index_pipeline_actors(system, k: int, mode: str = "staged"):
    """Build the prepare → count → move pipeline for length-``k`` inputs.

    The returned pipeline ref accepts ``(fills, literals)`` (uint32, length
    k) and responds with ``(index_words, n_words)``. In ``staged`` mode
    (paper Listing 5) intermediates travel as ``DeviceRef``s — data stays
    on the device between stages; ``fused`` traces the three kernels into
    one program.
    """
    from repro.core import In, NDRange, Out, Pipeline, dim_vec, kernel
    from repro.kernels.stream_compact import pallas_local_compact

    bs = 256
    assert (2 * k) % bs == 0

    def prepare_index(fills, literals):
        return ops.wah_interleave(fills, literals)

    def count_elements(index):
        return pallas_local_compact(index, bs=bs, interpret=not ops.on_tpu())

    def move_valid_elements(blocks, cnts):
        return ops._place_blocks(blocks, cnts)

    rng = NDRange(dim_vec(k))
    rng_sc = NDRange(dim_vec(2 * k), local_dims=dim_vec(bs))
    prepare = kernel(In(jnp.uint32), In(jnp.uint32),
                     Out(jnp.uint32, as_ref=True),
                     nd_range=rng, name="prepare_index")(prepare_index)
    count = kernel(In(jnp.uint32),
                   Out(jnp.uint32, as_ref=True),
                   Out(jnp.int32, as_ref=True),
                   nd_range=rng_sc, name="count_elements")(count_elements)
    move = kernel(In(jnp.uint32), In(jnp.int32),
                  Out(jnp.uint32), Out(jnp.int32),
                  nd_range=rng_sc, name="move_valid_elements")(
                      move_valid_elements)
    return (Pipeline(system, mode=mode, name="wah_index")
            .stage(prepare).stage(count).stage(move).build())
