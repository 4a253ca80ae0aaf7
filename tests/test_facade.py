"""Tests for the kernel-actor facade, mem_refs, composition, scheduler
(paper §3.2–3.6), through ``@kernel`` declarations spawned on the device
manager."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ActorSystem, ChunkScheduler, DeviceRef, In, InOut,
                        NDRange, Out, Pipeline, SignatureMismatch, dim_vec,
                        kernel, split_offload)


@pytest.fixture(scope="module")
def system():
    s = ActorSystem(max_workers=4)
    yield s
    s.shutdown()


@pytest.fixture(scope="module")
def mngr(system):
    return system.opencl_manager()


def _mm(a, b):
    return a @ b


def test_matmul_facade_value_semantics(mngr):
    n = 32
    w = mngr.spawn(kernel(In(jnp.float32), In(jnp.float32),
                          Out(jnp.float32, shape=(n, n)),
                          nd_range=NDRange(dim_vec(n, n)),
                          name="m_mult")(_mm))
    a = np.random.default_rng(0).random((n, n), np.float32)
    b = np.random.default_rng(1).random((n, n), np.float32)
    r = w.ask(a, b)
    assert isinstance(r, np.ndarray)
    np.testing.assert_allclose(r, a @ b, rtol=1e-5)


def test_out_ref_returns_deviceref(mngr):
    w = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32, as_ref=True),
                          nd_range=NDRange(dim_vec(8)),
                          name="scale")(lambda x: x * 3.0))
    r = w.ask(np.ones(8, np.float32))
    assert isinstance(r, DeviceRef)
    np.testing.assert_allclose(r.to_value(), 3.0)
    r.release()
    with pytest.raises(RuntimeError):
        _ = r.array


def test_deviceref_not_serializable(mngr):
    import pickle
    w = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32, as_ref=True),
                          nd_range=NDRange(dim_vec(4)),
                          name="id")(lambda x: x))
    r = w.ask(np.zeros(4, np.float32))
    with pytest.raises(TypeError):
        pickle.dumps(r)


def test_inout_consumes_incoming_ref(mngr):
    producer = mngr.spawn(kernel(In(jnp.float32),
                                 Out(jnp.float32, as_ref=True),
                                 nd_range=NDRange(dim_vec(4)),
                                 name="p")(lambda x: x + 1.0))
    updater = mngr.spawn(kernel(InOut(jnp.float32, as_ref=True),
                                nd_range=NDRange(dim_vec(4)),
                                name="u")(lambda x: x * 2.0))
    ref = producer.ask(np.zeros(4, np.float32))
    out = updater.ask(ref)
    np.testing.assert_allclose(out.to_value(), 2.0)
    # incoming in_out ref has been consumed (buffer ownership transferred)
    with pytest.raises(RuntimeError):
        _ = ref.array


def test_dtype_mismatch_raises(mngr):
    w = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                          nd_range=NDRange(dim_vec(4)),
                          name="id2")(lambda x: x))
    with pytest.raises(SignatureMismatch):
        w.ask(np.zeros(4, np.int32))


def test_wrong_arity_raises(mngr):
    w = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                          nd_range=NDRange(dim_vec(4)),
                          name="id3")(lambda x: x))
    with pytest.raises(SignatureMismatch):
        w.ask(np.zeros(4, np.float32), np.zeros(4, np.float32))


def test_pre_post_processing(mngr):
    """Paper Listing 3: conversion functions around the kernel."""
    def pre(matrix_pair):
        a, b = matrix_pair
        return (a.astype(np.float32), b.astype(np.float32))

    def post(result):
        return {"matrix": result}

    n = 8
    w = mngr.spawn(kernel(In(jnp.float32), In(jnp.float32),
                          Out(jnp.float32, shape=(n, n)),
                          nd_range=NDRange(dim_vec(n, n)), name="mm_pp",
                          preprocess=pre, postprocess=post)(_mm))
    a = np.eye(n)
    out = w.ask((a, a))
    np.testing.assert_allclose(out["matrix"], a, rtol=1e-6)


def test_ndrange_validation():
    with pytest.raises(ValueError):
        NDRange(dim_vec(8), local_dims=(3,))
    with pytest.raises(ValueError):
        dim_vec(1, 2, 3, 4)
    r = NDRange(dim_vec(16, 8), local_dims=(4, 4))
    assert r.grid() == (4, 2)
    assert r.total_items == 128


def test_ndrange_split_fractions():
    r = NDRange(dim_vec(10))
    parts = r.split([0.5, 0.3, 0.2])
    sizes = [p.global_dims[0] for p in parts if p]
    assert sum(sizes) == 10
    offs = [p.offsets[0] for p in parts if p]
    assert offs == [0, sizes[0], sizes[0] + sizes[1]]
    parts = r.split([1.0, 0.0])
    assert parts[1] is None and parts[0].global_dims == (10,)


def test_staged_composition_device_resident(mngr, system):
    """Paper §3.5: references flow between stages, data stays on device."""
    s1 = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32, as_ref=True),
                           nd_range=NDRange(dim_vec(16)),
                           name="s1")(lambda x: x + 1.0))
    s2 = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32, as_ref=True),
                           nd_range=NDRange(dim_vec(16)),
                           name="s2")(lambda x: x * 2.0))
    s3 = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                           nd_range=NDRange(dim_vec(16)),
                           name="s3")(lambda x: x - 3.0))
    pipe = s3 * s2 * s1  # s3(s2(s1(x)))
    x = np.arange(16, dtype=np.float32)
    np.testing.assert_allclose(pipe.ask(x), (x + 1) * 2 - 3)


def test_fused_composition_single_program(mngr, system):
    s1 = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32, as_ref=True),
                           nd_range=NDRange(dim_vec(16)),
                           name="f1")(lambda x: x + 1.0))
    s2 = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                           nd_range=NDRange(dim_vec(16)),
                           name="f2")(lambda x: x * 2.0))
    fused = Pipeline(system, mode="fused", name="f12").stages([s1, s2]).build()
    assert fused.plan.fused_regions == [["f12/f1", "f12/f2"]]
    assert len(fused.node_refs) == 1
    x = np.arange(16, dtype=np.float32)
    np.testing.assert_allclose(fused.ask(x), (x + 1) * 2)


def test_fuse_with_adapter(mngr, system):
    a = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32, as_ref=True),
                          Out(jnp.float32, as_ref=True),
                          nd_range=NDRange(dim_vec(4)),
                          name="a")(lambda x: (x, x + 1.0)))
    b = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                          nd_range=NDRange(dim_vec(4)),
                          name="b")(lambda x: x * 10.0))
    fused = (Pipeline(system, mode="fused", name="ab")
             .stages([a, lambda x, y: x + y, b]).build())
    x = np.ones(4, np.float32)
    np.testing.assert_allclose(fused.ask(x), 30.0)


def test_split_offload_sweep(mngr):
    """Paper Fig. 7: fraction sweep across two heterogeneous workers."""
    def work(x):
        return x * x

    w1 = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                           nd_range=NDRange(dim_vec(64)), name="w1")(work))
    w2 = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                           nd_range=NDRange(dim_vec(64)), name="w2")(work))
    data = np.arange(64, dtype=np.float32)

    for frac in [0.0, 0.3, 0.5, 1.0]:
        def sizes_of(fr):
            a = int(64 * fr[0])
            return [a, 64 - a]

        out = split_offload(
            [w1, w2], [frac, 1.0 - frac],
            make_payload=lambda s, n: (data[s:s + n],),
            sizes_of=sizes_of,
            combine=lambda rs: np.concatenate(rs))
        np.testing.assert_allclose(out, data * data)


def test_chunk_scheduler_straggler_and_failure(mngr, system):
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return x + 1.0

    def steady(x):
        return x + 1.0

    # flaky dies after its first failure (actor semantics) — scheduler must
    # finish all chunks on the surviving worker.
    wf = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                           nd_range=NDRange(dim_vec(4)),
                           name="flaky")(flaky))
    ws = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                           nd_range=NDRange(dim_vec(4)),
                           name="steady")(steady))
    sched = ChunkScheduler([wf, ws])
    payloads = [(np.full(4, i, np.float32),) for i in range(6)]
    res = sched.run(payloads, timeout=60)
    for i, r in enumerate(res):
        np.testing.assert_allclose(r, i + 1)
    assert sched.stats["failed"] >= 1


def test_chunk_scheduler_speculative_reissue_beats_straggler(system):
    """A deliberately slow straggler must lose to the speculatively
    re-issued copy, and the chunk's result must appear exactly once."""
    def slow(x):
        time.sleep(1.0)          # the straggler: ~1000x the median
        return ("slow", x + 1)

    def fast(x):
        time.sleep(0.001)
        return ("fast", x + 1)

    ws, wf = system.spawn(slow), system.spawn(fast)
    sched = ChunkScheduler([ws, wf], straggler_factor=3.0, drain_grace=3.0)
    res = sched.run([(i,) for i in range(8)], timeout=60)
    # every chunk present exactly once, in order, with the right value —
    # the straggler's late duplicate completion must not double-record
    assert [v for _, v in res] == [i + 1 for i in range(8)]
    # the chunk the slow worker grabbed was re-issued and won by the fast
    # worker; the slow worker contributes no result
    assert all(tag == "fast" for tag, _ in res), res
    assert sched.stats["speculative"] >= 1
    assert sched.stats["dispatched"] >= 9    # 8 fresh + >=1 speculative


def test_chunk_scheduler_elastic_add_remove(mngr):
    w1 = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                           nd_range=NDRange(dim_vec(2)),
                           name="e1")(lambda x: x))
    sched = ChunkScheduler([w1])
    w2 = mngr.spawn(kernel(In(jnp.float32), Out(jnp.float32),
                           nd_range=NDRange(dim_vec(2)),
                           name="e2")(lambda x: x))
    sched.add_worker(w2)
    assert len(sched.workers) == 2
    res = sched.run([(np.full(2, i, np.float32),) for i in range(4)])
    assert len(res) == 4
    sched.remove_worker(w1)
    assert len(sched.workers) == 1
