"""End-to-end WAH indexing tests (paper §4): build on 'device', decode on
host, verify round-trip against the raw data."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ActorSystem
from repro.indexing import (build_wah_index, build_wah_index_numpy,
                            decode_wah_bitmap, wah_index_pipeline_actors)


@pytest.mark.parametrize("n,card,seed", [(1024, 8, 0), (4096, 64, 1),
                                         (2048, 3, 2)])
def test_wah_roundtrip(n, card, seed):
    rng = np.random.default_rng(seed)
    values = rng.integers(0, card, n).astype(np.uint32)
    words, n_words, starts, counts = build_wah_index(jnp.asarray(values), card)
    words = np.asarray(words)[:int(n_words)]
    starts, counts = np.asarray(starts), np.asarray(counts)
    for v in range(card):
        got = decode_wah_bitmap(words, starts[v], counts[v])
        want = np.flatnonzero(values == v)
        np.testing.assert_array_equal(got, want)


def test_wah_skewed_distribution():
    rng = np.random.default_rng(7)
    values = (rng.pareto(1.5, 4096) * 3).astype(np.uint32)
    values = np.clip(values, 0, 31)
    words, n_words, starts, counts = build_wah_index(jnp.asarray(values), 32)
    words = np.asarray(words)[:int(n_words)]
    for v in range(32):
        got = decode_wah_bitmap(words, int(np.asarray(starts)[v]),
                                int(np.asarray(counts)[v]))
        np.testing.assert_array_equal(got, np.flatnonzero(values == v))


def test_wah_matches_numpy_reference_word_count():
    """The data-parallel index and the sequential CPU builder agree on the
    per-value word streams (same WAH encoding)."""
    rng = np.random.default_rng(3)
    values = rng.integers(0, 16, 2048).astype(np.uint32)
    words, n_words, starts, counts = build_wah_index(jnp.asarray(values), 16)
    words = np.asarray(words)[:int(n_words)]
    ref_words, ref_n, ref_starts, ref_counts = build_wah_index_numpy(values, 16)
    assert int(n_words) == ref_n
    np.testing.assert_array_equal(np.asarray(counts), ref_counts)
    for v in range(16):
        a = words[int(np.asarray(starts)[v]):][:int(np.asarray(counts)[v])]
        b = ref_words[ref_starts[v]:ref_starts[v] + ref_counts[v]]
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("card", [1, 256, 257])
def test_wah_matches_numpy_reference_across_digit_widths(card):
    """The sort orders only the bits ``card - 1`` needs: one 8-bit digit at
    256 values, two at 257. Words, count and table match the sequential
    CPU reference, and the oracle path (``impl="ref"``) gives the same
    index."""
    rng = np.random.default_rng(card)
    values = rng.integers(0, card, 4096).astype(np.uint32)
    values[-1] = card - 1          # the widest key is always present
    got = build_wah_index(jnp.asarray(values), card)
    words, n_words, starts, counts = (np.asarray(x) for x in got)
    ref_words, ref_n, ref_starts, ref_counts = build_wah_index_numpy(values,
                                                                     card)
    assert int(n_words) == ref_n
    np.testing.assert_array_equal(words[:ref_n], ref_words)
    assert not words[ref_n:].any()
    np.testing.assert_array_equal(starts, ref_starts)
    np.testing.assert_array_equal(counts, ref_counts)
    oracle = build_wah_index(jnp.asarray(values), card, impl="ref")
    for a, b in zip(got, oracle):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_wah_compresses_sparse_data():
    """A rare value's bitmap must be ≪ the dense bitmap size."""
    values = np.zeros(31 * 1000, np.uint32)
    values[31 * 999] = 1  # single set bit at the end for value 1
    words, n_words, starts, counts = build_wah_index(jnp.asarray(values), 2)
    counts = np.asarray(counts)
    assert counts[1] == 2  # one fill (999 chunks) + one literal


def test_actor_pipeline_matches_fused(tmp_path):
    """Paper Listing 5: the 3-stage composed actor produces the same fused
    index as the direct computation."""
    rng = np.random.default_rng(11)
    k = 1024
    fills = (rng.integers(0, 2, k) * ((1 << 31) | rng.integers(1, 100, k))).astype(
        np.uint32)
    literals = rng.integers(1, 2**31, k).astype(np.uint32)

    from repro.kernels import ops
    want_fused = np.asarray(ops.wah_interleave(jnp.asarray(fills),
                                               jnp.asarray(literals)))
    want_comp, want_n = ops.stream_compact(jnp.asarray(want_fused))

    with ActorSystem() as system:
        pipe = wah_index_pipeline_actors(system, k)
        out, n = pipe.ask(fills, literals)
        assert int(n) == int(want_n)
        np.testing.assert_array_equal(out, np.asarray(want_comp))
