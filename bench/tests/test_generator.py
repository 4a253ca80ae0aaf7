"""The generator gives every seed the same work in every block of
requests, in an order of the seed's own."""
import numpy as np

from bench import load_json
from bench.generator import BLOCK, schedule

SEEDS = (3_000_000_029, 4_000_000_033)


def test_same_seed_same_schedule():
    mix = load_json("traffic", "chat-mixed")
    assert schedule(mix, SEEDS[0], 45) == schedule(mix, SEEDS[0], 45)


def test_every_block_holds_the_same_sizes_and_gaps():
    mix = load_json("traffic", "chat-mixed")
    a, b = (schedule(mix, s, 45) for s in SEEDS)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_per_s"] * 45)
    assert [r["output_tokens"] for r in a] != [r["output_tokens"] for r in b]
    for k in range(len(a) // BLOCK):
        lo, hi = k * BLOCK, (k + 1) * BLOCK
        assert sorted(r["output_tokens"] for r in a[lo:hi]) == \
            sorted(r["output_tokens"] for r in b[lo:hi])
        if hi < len(a):
            # the block's arrivals span the same time on both seeds
            assert np.isclose(a[hi]["due"] - a[lo]["due"],
                              b[hi]["due"] - b[lo]["due"])
