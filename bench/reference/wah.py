"""Plain WAH bitmap index of a value array, in NumPy (Wu et al.'s word
format, as built by Fusco et al., IMC'13, and the paper's section 4).

For each value in ascending order, its bitmap over the input positions is
cut into 31-bit groups; every group holding a set bit becomes a literal
word (MSB 0, bit ``b`` set for position ``31 * group + b``), preceded by a
zero-fill word (MSB 1, bit 30 clear, count of empty groups in bits 0..29)
when empty groups lie between it and the value's previous literal (or the
start). Empty groups at the end are implicit. The lookup table gives each
value's first word and word count.

The steps are the paper's: (1) pair each value with its position, (2) sort
stably by value, (3) OR the bits of each (value, group) segment into its
literal, (4) derive the fills from gaps between a value's groups, (5) put
each fill before its literal and drop the empty fills, (6) count the words
per value.

Nothing here imports the program.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

FILL = np.uint32(1 << 31)


def _segments(values: np.ndarray, stable: bool = True):
    n = values.shape[0]
    pos = np.arange(n, dtype=np.int64)
    if stable:
        order = np.argsort(values, kind="stable")
    else:
        # the control: ties in reverse position order (an unstable sort)
        order = np.lexsort((-pos, values))
    v = values[order].astype(np.int64)
    p = pos[order]
    group = p // 31
    bits = np.left_shift(np.uint32(1), (p % 31).astype(np.uint32))
    new = np.ones(n, bool)
    new[1:] = (v[1:] != v[:-1]) | (group[1:] != group[:-1])
    first = np.flatnonzero(new)
    literals = np.bitwise_or.reduceat(bits, first).astype(np.uint32)
    seg_v = v[first]
    seg_g = group[first]
    prev = np.full(first.shape[0], -1, np.int64)
    same = np.zeros(first.shape[0], bool)
    same[1:] = seg_v[1:] == seg_v[:-1]
    prev[1:] = np.where(same[1:], seg_g[:-1], -1)
    gap = seg_g - prev - 1
    fills = np.where(gap > 0, FILL | gap.astype(np.uint32),
                     np.uint32(0)).astype(np.uint32)
    return seg_v, fills, literals


def wah_index(values: np.ndarray, cardinality: int, stable: bool = True
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """→ ``(words, starts, counts)`` of the WAH index of ``values``.
    ``stable=False`` is the control: positions of equal values out of
    order, which breaks the index's guarantee of ascending positions."""
    seg_v, fills, literals = _segments(np.asarray(values), stable)
    pairs = np.stack([fills, literals], axis=1).reshape(-1)
    words = pairs[pairs != 0]
    per_seg = (fills != 0).astype(np.int64) + 1
    counts = np.bincount(seg_v, weights=per_seg,
                         minlength=cardinality).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int64)
    return words.astype(np.uint32), starts, counts

