"""Tests for the declarative kernel-actor API: @kernel declaration
capture, spawning only declared kernels, Pipeline staged/fused/auto
equivalence, and pool routing."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ActorPool, ActorSystem, ChunkScheduler, In, KernelDecl,
                        NDRange, Out, Pipeline, dim_vec, kernel)


@pytest.fixture(scope="module")
def system():
    s = ActorSystem(max_workers=6)
    yield s
    s.shutdown()


@pytest.fixture(scope="module")
def mngr(system):
    return system.opencl_manager()


N = 16


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="add_one")
def add_one(x):
    return x + 1.0


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)))
def double(x):
    return x * 2.0


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="sub_three")
def sub_three(x):
    return x - 3.0


# ----------------------------------------------------------------------------
# @kernel declaration capture
# ----------------------------------------------------------------------------
def test_kernel_decorator_captures_signature():
    assert isinstance(add_one, KernelDecl)
    assert add_one.name == "add_one"
    assert double.name == "double"          # defaults to fn.__name__
    assert add_one.nd_range == NDRange(dim_vec(N))
    assert len(add_one.signature.input_specs) == 1
    assert len(add_one.signature.output_specs) == 1
    # still directly callable (undecorated behavior)
    np.testing.assert_allclose(np.asarray(add_one(jnp.zeros(4))), 1.0)


def test_kernel_with_options_is_a_copy():
    wider = add_one.with_options(nd_range=NDRange(dim_vec(64)))
    assert wider.nd_range == NDRange(dim_vec(64))
    assert add_one.nd_range == NDRange(dim_vec(N))  # original untouched
    assert wider.fn is add_one.fn
    with pytest.raises(TypeError):
        add_one.with_options(bogus=1)


def test_spawn_decorated_kernel_from_system(system):
    worker = system.spawn(add_one)
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(worker.ask(x), x + 1)


def test_spawn_decorated_kernel_from_manager_with_overrides(system, mngr):
    dev = mngr.find_device()
    worker = mngr.spawn(double, device=dev)
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(worker.ask(x), x * 2)


def test_spawn_rejects_unknown_kwargs(mngr):
    with pytest.raises(TypeError):
        mngr.spawn(add_one, bogus_option=1)


@pytest.mark.parametrize("args", [
    (lambda x: x + 1.0,),
    (lambda x: x + 1.0, "add_one", NDRange(dim_vec(N)), In(jnp.float32),
     Out(jnp.float32)),
], ids=["bare-callable", "positional-name-ndrange-specs"])
def test_manager_spawn_rejects_undeclared_kernels(mngr, args):
    spawned = mngr.system.stats["spawned"]
    with pytest.raises(TypeError, match=r"@repro\.core\.kernel"):
        mngr.spawn(*args)
    assert mngr.system.stats["spawned"] == spawned


# ----------------------------------------------------------------------------
# Pipeline: staged / fused / auto equivalence (acceptance criterion)
# ----------------------------------------------------------------------------
def _expected(x):
    return (x + 1) * 2 - 3


def test_pipeline_modes_agree_on_three_stage_chain(system):
    x = np.arange(N, dtype=np.float32)
    staged = (Pipeline(system, mode="staged")
              .stage(add_one).stage(double).stage(sub_three).build())
    fused = (Pipeline(system, mode="fused")
             .stage(add_one).stage(double).stage(sub_three).build())
    auto = (Pipeline(system, mode="auto")
            .stage(add_one).stage(double).stage(sub_three).build())
    r_staged, r_fused, r_auto = staged.ask(x), fused.ask(x), auto.ask(x)
    np.testing.assert_allclose(r_staged, _expected(x))
    np.testing.assert_array_equal(np.asarray(r_staged), np.asarray(r_fused))
    np.testing.assert_array_equal(np.asarray(r_staged), np.asarray(r_auto))


def test_pipeline_auto_resolution(system):
    all_kernels = (Pipeline(system, mode="auto")
                   .stage(add_one).stage(double))
    assert all_kernels.resolved_mode() == "fused"

    opaque = system.spawn(lambda x: x + 1)  # plain actor: not traceable
    mixed = Pipeline(system, mode="auto").stage(opaque).stage(double)
    assert mixed.resolved_mode() == "staged"
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(mixed.build().ask(x), (x + 1) * 2)


def test_pipeline_with_adapter_callable(system):
    """Bare callables act as traceable adapters between kernel stages."""
    pipe = (Pipeline(system, mode="fused")
            .stage(add_one).stage(lambda x: x * 10.0).stage(double).build())
    x = np.ones(N, np.float32)
    np.testing.assert_allclose(pipe.ask(x), (x + 1) * 10 * 2)


def test_pipeline_accepts_existing_kernel_actor_refs(system):
    a = system.spawn(add_one)
    d = system.spawn(double)
    for mode in ("staged", "fused", "auto"):
        pipe = Pipeline(system, mode=mode).stages([a, d]).build()
        x = np.arange(N, dtype=np.float32)
        np.testing.assert_allclose(pipe.ask(x), (x + 1) * 2)


def test_pipeline_empty_or_bad_stage_raises(system):
    with pytest.raises(ValueError):
        Pipeline(system).build()
    with pytest.raises(TypeError):
        Pipeline(system).stage(42)
    with pytest.raises(ValueError):
        Pipeline(system, mode="bogus")


# ----------------------------------------------------------------------------
# pools
# ----------------------------------------------------------------------------
def test_spawn_pool_round_robin_and_scheduler(system, mngr):
    pool = mngr.spawn_pool(add_one, 3, policy="round_robin")
    assert len(pool.workers) == 3
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(pool.ask(x), x + 1)
    # plugs into ChunkScheduler (pull-based balancing over the replicas)
    payloads = [(np.full(N, i, np.float32),) for i in range(9)]
    res = ChunkScheduler(pool).run(payloads, timeout=60)
    for i, r in enumerate(res):
        np.testing.assert_allclose(r, i + 1)
    # pool.map is the one-call version of the same thing
    res2 = pool.map(payloads, timeout=60)
    for i, r in enumerate(res2):
        np.testing.assert_allclose(r, i + 1)


def test_pool_round_robin_cycles_workers(system):
    counts = [0, 0, 0]

    def make(i):
        def fn(x):
            counts[i] += 1
            return x
        return fn

    pool = ActorPool(system, [system.spawn(make(i)) for i in range(3)],
                     policy="round_robin")
    for i in range(9):
        pool.ask(i)
    assert counts == [3, 3, 3]


def test_pool_least_loaded_routes_around_slow_worker(system):
    """Under unequal worker speeds the load-aware policy must push most
    of the work to the fast replica (the backed-up one stops winning)."""
    counts = {"slow": 0, "fast": 0}
    lock = threading.Lock()

    def slow(x):
        with lock:
            counts["slow"] += 1
        time.sleep(0.05)
        return x

    def fast(x):
        with lock:
            counts["fast"] += 1
        time.sleep(0.001)
        return x

    pool = ActorPool(system, [system.spawn(slow), system.spawn(fast)],
                     policy="least_loaded")
    futs = []
    for i in range(30):
        futs.append(pool.request(i))
        time.sleep(0.002)
    for f in futs:
        f.result(30)
    assert counts["slow"] + counts["fast"] == 30
    assert counts["fast"] > counts["slow"], counts


def test_pool_outstanding_consistent_under_hammer(system):
    """Regression for the _pick/outstanding race: 8 threads hammering a
    4-worker pool must never lose or double-count an outstanding slot —
    the decrement runs in the done-callback under the pool lock."""
    pool = ActorPool(system, [system.spawn(lambda x: x + 1)
                              for _ in range(4)], policy="least_loaded")
    errors = []

    def hammer():
        try:
            for i in range(50):
                assert pool.ask(i, timeout=30) == i + 1
        except Exception as e:      # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        counts = [pool.outstanding(w) for w in pool.workers]
        if all(c == 0 for c in counts):
            break
        time.sleep(0.01)
    assert all(c == 0 for c in counts), counts


def test_pool_survives_dead_worker(system):
    def bad(x):
        raise RuntimeError("boom")

    good = system.spawn(lambda x: x + 1)
    dead = system.spawn(bad)
    pool = ActorPool(system, [dead, good], policy="round_robin")
    with pytest.raises(RuntimeError):
        pool.ask(0)          # routed to the bad worker, which dies
    # every subsequent message lands on the survivor
    assert [pool.ask(i) for i in range(4)] == [1, 2, 3, 4]
    assert pool.is_alive()
