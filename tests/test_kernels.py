"""Per-kernel allclose sweeps vs the ref.py oracles (interpret=True on CPU)."""
import functools

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(1234)


# ----------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (128, 256, 256), (384, 128, 384)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_matmul_sweep(m, k, n, dtype):
    a = RNG.standard_normal((m, k), np.float32).astype(dtype)
    b = RNG.standard_normal((k, n), np.float32).astype(dtype)
    got = ops.matmul(a, b, impl="pallas")
    want = ref.matmul(jnp.asarray(a), jnp.asarray(b))
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_matmul_nondivisible_falls_back():
    a = RNG.standard_normal((100, 100), np.float32)
    b = RNG.standard_normal((100, 100), np.float32)
    got = ops.matmul(jnp.asarray(a), jnp.asarray(b), impl="auto")
    np.testing.assert_allclose(np.asarray(got), a @ b, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------------
@pytest.mark.parametrize("h,w,it", [(8, 128, 16), (16, 256, 64), (24, 128, 100)])
def test_mandelbrot_sweep(h, w, it):
    kw = dict(height=h, width=w, max_iter=it, re_min=-0.5, re_max=0.1,
              im_min=-0.7375, im_max=-0.1375)
    np.testing.assert_array_equal(np.asarray(ops.mandelbrot(impl="pallas", **kw)),
                                  np.asarray(ops.mandelbrot(impl="ref", **kw)))


def test_mandelbrot_row_offset_consistency():
    """Fractional offload slices must tile to the full image (paper §5.4)."""
    kw = dict(width=128, max_iter=32, re_min=-2.0, re_max=1.0,
              im_min=-1.5, im_max=1.5)
    full = np.asarray(ops.mandelbrot(height=32, total_height=32, impl="pallas", **kw))
    top = np.asarray(ops.mandelbrot(height=16, row_offset=0, total_height=32,
                                    impl="pallas", **kw))
    bottom = np.asarray(ops.mandelbrot(height=16, row_offset=16, total_height=32,
                                       impl="pallas", **kw))
    np.testing.assert_array_equal(np.vstack([top, bottom]), full)


# ----------------------------------------------------------------------------
def _compaction_input(pattern, n, bs, drop_value):
    """``n`` uint32 whose survivors (entries other than ``drop_value``)
    follow ``pattern``: a density, or a layout over the ``bs``-blocks."""
    if pattern == "wah":        # build_wah_index's step (5) input
        k = n // 2
        seg = np.arange(k) < k // 2          # valid segments: first half only
        gap = seg & (RNG.random(k) < 0.4)
        fills = np.where(gap, (1 << 31) | RNG.integers(1, 99, k), 0)
        lits = np.where(seg, RNG.integers(1, 2**31, k), 0)
        return np.asarray(ref.wah_interleave(
            jnp.asarray(fills.astype(np.uint32)),
            jnp.asarray(lits.astype(np.uint32))))
    blk = np.arange(n) // bs
    last = n // bs - 1
    if isinstance(pattern, float):
        keep = RNG.random(n) < pattern
    else:
        keep = {"gaps": blk % 3 != 1,             # empty blocks between full
                "last-only": (blk == last) & (RNG.random(n) < 0.5),
                "partial-last": (blk < last) | (RNG.random(n) < 0.5),
                }[pattern]
    vals = RNG.integers(1, 2**32, n, dtype=np.uint64).astype(np.uint32)
    if drop_value:              # zeros survive when they are not dropped
        vals[RNG.random(n) < 0.2] = 0
        vals[vals == drop_value] = 1
    return np.where(keep, vals, np.uint32(drop_value)).astype(np.uint32)


_COMPACT_CASES = [
    pytest.param(d, n, bs, 0, id=f"{d}-{n}-{bs}")
    for d in (0.0, 0.3, 1.0)
    for n, bs in ((256, 256), (1024, 256), (2048, 512))
] + [
    pytest.param("gaps", 2048, 256, 0, id="empty-between-full"),
    pytest.param(0.0, 4096, 256, 0, id="all-empty"),
    pytest.param(1.0, 4096, 256, 0, id="all-full"),
    pytest.param("last-only", 2048, 256, 0, id="last-block-only"),
    pytest.param("partial-last", 2048, 256, 0, id="partial-last-block"),
    pytest.param(0.5, 2048, 256, 0x7FFFFFFF, id="drop-value-large"),
    pytest.param("gaps", 2048, 512, 7, id="drop-value-7-gaps"),
    pytest.param("wah", 4096, 256, 0, id="wah-interleaved-2e12"),
]


@pytest.mark.parametrize("pattern,n,bs,drop_value", _COMPACT_CASES)
def test_stream_compact_sweep(pattern, n, bs, drop_value):
    x = _compaction_input(pattern, n, bs, drop_value)
    got, cnt = ops.stream_compact(jnp.asarray(x), bs=bs, drop_value=drop_value,
                                  impl="pallas")
    want, wcnt = ref.stream_compact(jnp.asarray(x), drop_value)
    assert int(cnt) == int(wcnt) == int((x != drop_value).sum())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_stream_compact_moves_by_prefix_sums():
    """The global move addresses each survivor from the prefix sum of the
    block counts: outside the Pallas kernel no loop (a per-slot search) and
    no gather is left."""
    jaxpr = jax.make_jaxpr(functools.partial(ops.stream_compact,
                                             impl="pallas"))(
        jnp.zeros(2048, jnp.uint32))
    seen = set()

    def walk(j):
        for eqn in j.eqns:
            seen.add(eqn.primitive.name)
            if eqn.primitive.name != "pallas_call":
                for sub in _subjaxprs(eqn.params.values()):
                    walk(sub)

    walk(jaxpr.jaxpr)
    assert "pallas_call" in seen and "scatter" in seen, seen
    assert not seen & {"scan", "while", "gather"}, seen


def _subjaxprs(values):
    for v in values:
        if isinstance(v, jax.extend.core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax.extend.core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            yield from _subjaxprs(v)


def test_stream_compact_order_preserved():
    x = np.array([5, 0, 7, 0, 0, 9, 1, 0] * 32, np.uint32)
    got, cnt = ops.stream_compact(jnp.asarray(x), bs=256, impl="pallas")
    survivors = x[x != 0]
    np.testing.assert_array_equal(np.asarray(got)[:int(cnt)], survivors)


# ----------------------------------------------------------------------------
@pytest.mark.parametrize("n", [256, 1024, 4096])
@pytest.mark.parametrize("bits", [4, 8])
def test_radix_sort_sweep(n, bits):
    keys = RNG.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    vals = np.arange(n, dtype=np.int32)
    kp, vp = ops.radix_sort(jnp.asarray(keys), jnp.asarray(vals),
                            bits_per_pass=bits, impl="pallas")
    np.testing.assert_array_equal(np.asarray(kp), np.sort(keys))
    # payload permuted consistently
    np.testing.assert_array_equal(keys[np.asarray(vp)], np.asarray(kp))


def test_radix_sort_stability():
    """Equal keys keep input order (required by the WAH pipeline)."""
    keys = np.array([3, 1, 3, 1, 2, 3, 1, 2] * 32, np.uint32)
    vals = np.arange(keys.size, dtype=np.int32)
    _, vp = ops.radix_sort(jnp.asarray(keys), jnp.asarray(vals), impl="pallas")
    vp = np.asarray(vp)
    for key in (1, 2, 3):
        positions = vp[np.sort(np.flatnonzero(keys[vp] == key))]
        assert (np.diff(positions) > 0).all()


def test_radix_sort_16bit_oracle_path():
    keys = RNG.integers(0, 2**32, 512, dtype=np.uint64).astype(np.uint32)
    kp = ops.radix_sort(jnp.asarray(keys), bits_per_pass=16)
    np.testing.assert_array_equal(np.asarray(kp), np.sort(keys))


# ----------------------------------------------------------------------------
@pytest.mark.parametrize("n,bs", [(512, 512), (2048, 512), (1024, 256)])
def test_wah_interleave_sweep(n, bs):
    f = RNG.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    l = RNG.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    got = ops.wah_interleave(jnp.asarray(f), jnp.asarray(l), bs=bs, impl="pallas")
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.wah_interleave(jnp.asarray(f),
                                                                jnp.asarray(l))))


# ----------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,hkv,sq,skv,d", [
    (1, 2, 2, 128, 128, 64),     # MHA square
    (2, 4, 2, 128, 256, 64),     # GQA, kv longer (decode-ish)
    (1, 8, 1, 64, 128, 128),     # MQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, h, hkv, sq, skv, d, causal):
    q = RNG.standard_normal((b, h, sq, d), np.float32)
    k = RNG.standard_normal((b, hkv, skv, d), np.float32)
    v = RNG.standard_normal((b, hkv, skv, d), np.float32)
    got = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=causal, impl="pallas", bq=64, bk=64)
    want = ref.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                               causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [32, 100, 256])
def test_flash_attention_local_window(window):
    q = RNG.standard_normal((1, 2, 128, 64), np.float32)
    k = RNG.standard_normal((1, 2, 256, 64), np.float32)
    v = RNG.standard_normal((1, 2, 256, 64), np.float32)
    got = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True, window=window, impl="pallas",
                              bq=64, bk=64)
    want = ref.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                               causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    q = RNG.standard_normal((1, 2, 128, 64), np.float32).astype(jnp.bfloat16)
    k = RNG.standard_normal((1, 2, 128, 64), np.float32).astype(jnp.bfloat16)
    v = RNG.standard_normal((1, 2, 128, 64), np.float32).astype(jnp.bfloat16)
    got = ops.flash_attention(q, k, v, causal=True, impl="pallas", bq=64, bk=64)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=3e-2, atol=3e-2)


# ----------------------------------------------------------------------------
def _held_case(case, t, k, e, lo, ne):
    """Each token's chosen experts (global ids; held: lo .. lo+ne-1)."""
    if case == "no-token-on-a-held-expert":
        ids = [[x for x in range(e) if not lo <= x < lo + ne][:k]] * t
    elif case == "all-tokens-on-one-expert":
        ids = [[lo + 1] + [x for x in range(e) if not lo <= x < lo + ne][:k - 1]] * t
    else:  # uneven groups: a skewed draw, some held experts left idle
        p = np.arange(e, 0, -1, dtype=np.float64) ** 2
        ids = [RNG.choice(e, k, replace=False, p=p / p.sum()) for _ in range(t)]
    return jnp.asarray(np.asarray(ids), jnp.int32)


@pytest.mark.parametrize("case", ["no-token-on-a-held-expert",
                                  "all-tokens-on-one-expert", "uneven-groups"])
@pytest.mark.parametrize("t,tf", [(5, 128), (40, 256)])
def test_moe_held_experts_matches_einsum(case, t, tf):
    """The grouped kernel (interpreted) against every held expert run on
    every token, masked by the routing weights: rows sorted by expert into
    16-row tiles, groups longer than a tile, experts no token chose."""
    k, e, lo, ne, d, f = 3, 12, 4, 6, 128, 256
    idx = _held_case(case, t, k, e, lo, ne)
    w = jnp.asarray(RNG.random((t, k)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((t, d)), jnp.float32)
    wg, wu = (jnp.asarray(RNG.standard_normal((ne, d, f)) / 12, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(RNG.standard_normal((ne, f, d)) / 16, jnp.float32)
    got, n_held, touched = ops.moe_held_experts(x, idx, w, wg, wu, wd, lo,
                                                impl="pallas", tf=tf)
    want = ref.moe_held_experts(x, idx, w, wg, wu, wd, lo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    local = np.asarray(idx) - lo
    on_held = (local >= 0) & (local < ne)
    assert int(n_held) == int(on_held.sum())
    assert int(touched) == len(set(local[on_held].tolist()))
    if case == "no-token-on-a-held-expert":
        assert int(n_held) == 0 and not np.asarray(got).any()


@pytest.mark.parametrize("layer", [0, 2])
def test_moe_held_experts_uses_the_given_layer_of_stacked_weights(layer):
    """Weights stacked over layers, the layer a traced scalar (as decode's
    layer scan passes them): the kernel computes that layer's experts."""
    t, k, e, lo, ne, d, f, nl = 24, 3, 12, 4, 6, 128, 256, 3
    idx = _held_case("uneven-groups", t, k, e, lo, ne)
    w = jnp.asarray(RNG.random((t, k)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((t, d)), jnp.float32)
    wg, wu = (jnp.asarray(RNG.standard_normal((nl, ne, d, f)) / 12,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(RNG.standard_normal((nl, ne, f, d)) / 16, jnp.float32)
    run = jax.jit(lambda i: ops.moe_held_experts(
        x, idx, w, wg, wu, wd, lo, layer=i, impl="pallas", tf=128))
    got, n_held, _ = run(jnp.int32(layer))
    want = ref.moe_held_experts(x, idx, w, wg[layer], wu[layer], wd[layer],
                                lo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    assert int(n_held) > 0


@pytest.mark.parametrize("tiles", [[0, 2, 0, 0, 1, 0], [1, 0, 0, 3, 0, 0],
                                   [0, 0, 0, 0, 0, 4], [1, 1, 1, 1, 1, 1]])
def test_moe_held_experts_reads_only_chosen_experts(tiles):
    """The weight block of each grid step, in grid order: a new block (a
    fetch) only ever belongs to an expert some row chose, and each chosen
    expert's tiles are each fetched once."""
    from repro.kernels.moe_held import weight_blocks

    nj = 3
    we, act, jfix = (np.asarray(a) for a in
                     weight_blocks(jnp.asarray(tiles, jnp.int32), nj))
    blocks = [(int(we[e]), int(j * act[e] + jfix[e]))
              for e in range(len(tiles)) for j in range(nj)]
    fetched = [b for i, b in enumerate(blocks) if i == 0 or b != blocks[i - 1]]
    chosen = [e for e, n in enumerate(tiles) if n]
    assert fetched == [(e, j) for e in chosen for j in range(nj)]
