"""Typed dataflow-graph composition tests (ISSUE 4 acceptance surface).

Covers: build-time topology validation (cycles, dangling ports, arity and
dtype/shape mismatches — each a distinct GraphError subclass naming the
offending node path), the diamond acceptance criterion (6 nodes, zero
host transfers on interior edges), the combinators (broadcast, zip_join,
select/merge, map_over), Pipeline-as-linear-Graph compatibility, the
PipelineRunner/ServeEngine integration points, and the satellite fixes
(pool ask timeouts naming the routed worker, DeviceRef diagnostic repr).
"""
import gc
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ActorPool, ActorSystem, ArityMismatchError,
                        DanglingPortError, DeviceRef, Graph, GraphCycleError,
                        GraphError, GraphRef, In, NDRange, Out, Pipeline,
                        PortType, PortTypeMismatchError, dim_vec, kernel,
                        live_ref_count, memory_stats, reset_transfer_stats,
                        transfer_count)


@pytest.fixture(scope="module")
def system():
    s = ActorSystem(max_workers=8)
    yield s
    s.shutdown()


@pytest.fixture(scope="module")
def mngr(system):
    return system.opencl_manager()


@pytest.fixture()
def ref_baseline():
    gc.collect()
    return live_ref_count()


N = 16


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="prep")
def prep(x):
    return x + 1.0


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="double")
def double(x):
    return x * 2.0


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="sub3")
def sub3(x):
    return x - 3.0


@kernel(In(jnp.float32), In(jnp.float32), Out(jnp.float32),
        nd_range=NDRange(dim_vec(N)), name="add2")
def add2(a, b):
    return a + b


@kernel(In(jnp.float32), Out(jnp.float32), Out(jnp.float32),
        nd_range=NDRange(dim_vec(N)), name="fork")
def fork(x):
    return x + 10.0, x - 10.0


def _diamond(system, name="diamond"):
    """The acceptance diamond, 6 nodes:
    source → broadcast(2) → double/sub3 branches → zip_join → add2 sink."""
    g = Graph(system, name=name)
    x = g.source("x", jnp.float32, shape=(N,))
    l, r = g.broadcast(x, 2)
    j1, j2 = g.zip_join(g.apply(double, l), g.apply(sub3, r))
    g.output(g.apply(add2, j1, j2))
    return g


def _diamond_expected(x):
    return x * 2 + x - 3


# ----------------------------------------------------------------------------
# the acceptance criterion: 6-node diamond, zero interior host transfers
# ----------------------------------------------------------------------------
def test_diamond_zero_host_transfers(system, ref_baseline):
    g = _diamond(system)
    assert len(g.nodes) == 6
    built = g.build()
    x = np.arange(N, dtype=np.float32)
    reset_transfer_stats()
    out = built.ask(x)
    np.testing.assert_allclose(out, _diamond_expected(x), rtol=1e-6)
    assert transfer_count() == 0, "an interior edge round-tripped the host"
    assert memory_stats()["readbacks"] == 1     # only the final output
    time.sleep(0.2)
    gc.collect()
    assert live_ref_count() == ref_baseline     # interior refs all released


def test_diamond_ref_output_stays_resident(system, ref_baseline):
    """With a ref-semantics sink the whole diamond does zero host traffic
    until the caller's explicit read-back."""
    sink = add2.with_options(
        specs=(In(jnp.float32), In(jnp.float32),
               Out(jnp.float32, as_ref=True)))
    g = Graph(system, name="diamond_ref")
    x = g.source("x", jnp.float32, shape=(N,))
    l, r = g.broadcast(x, 2)
    j1, j2 = g.zip_join(g.apply(double, l), g.apply(sub3, r))
    g.output(g.apply(sink, j1, j2))
    built = g.build()
    x_in = np.arange(N, dtype=np.float32)
    reset_transfer_stats()
    out = built.ask(x_in)
    assert isinstance(out, DeviceRef)
    assert transfer_count() == 0
    assert memory_stats()["readbacks"] == 0
    np.testing.assert_allclose(out.to_value(), _diamond_expected(x_in),
                               rtol=1e-6)
    assert transfer_count() == 1
    out.release()
    time.sleep(0.2)
    gc.collect()
    assert live_ref_count() == ref_baseline


def test_diamond_concurrent_runs(system):
    built = _diamond(system, name="diamond_cc").build()
    xs = [np.full(N, i, np.float32) for i in range(8)]
    futs = [built.request(x) for x in xs]
    for x, f in zip(xs, futs):
        np.testing.assert_allclose(f.result(30), _diamond_expected(x),
                                   rtol=1e-6)


# ----------------------------------------------------------------------------
# build-time topology validation (distinct GraphError subclasses)
# ----------------------------------------------------------------------------
def test_cycle_detection_names_nodes(system):
    g = Graph(system, name="cyclic")
    n1 = g.node(prep, name="p1")
    n2 = g.node(double, name="p2")
    g.bind(n1, 0, n2.out(0))
    g.bind(n2, 0, n1.out(0))
    g.output(n2.out(0))
    with pytest.raises(GraphCycleError, match=r"cyclic/p[12]"):
        g.build()


def test_unbound_input_slot_is_dangling(system):
    g = Graph(system, name="unbound")
    n = g.node(prep)                       # input slot never bound
    g.output(n.out(0))
    with pytest.raises(DanglingPortError, match="unbound/prep"):
        g.build()


def test_unconsumed_port_is_dangling(system):
    g = Graph(system, name="drop")
    x = g.source("x", jnp.float32, shape=(N,))
    l, r = g.broadcast(g.apply(prep, x), 2)
    g.output(g.apply(double, l))            # branch r never consumed
    with pytest.raises(DanglingPortError, match="drop/broadcast"):
        g.build()


def test_arity_mismatch_names_node(system):
    g = Graph(system, name="arity")
    x = g.source("x", jnp.float32, shape=(N,))
    g.output(g.apply(add2, x))              # add2 wants two inputs
    with pytest.raises(ArityMismatchError, match="arity/add2"):
        g.build()


def test_dtype_mismatch_names_edge(system):
    g = Graph(system, name="dtypes")
    x = g.source("x", jnp.int32, shape=(N,))
    g.output(g.apply(prep, x))              # prep wants float32
    with pytest.raises(PortTypeMismatchError, match="dtypes/prep"):
        g.build()


def test_shape_mismatch_names_edge(system):
    shaped = prep.with_options(
        specs=(In(jnp.float32, shape=(4,)), Out(jnp.float32)))
    g = Graph(system, name="shapes")
    x = g.source("x", jnp.float32, shape=(N,))
    g.output(g.apply(shaped, x))
    with pytest.raises(PortTypeMismatchError, match="shapes/prep"):
        g.build()


def test_no_outputs_is_an_error(system):
    g = Graph(system, name="noout")
    g.source("x", jnp.float32)
    with pytest.raises(GraphError, match="no outputs"):
        g.build()


def test_output_dtype_contradiction_caught_at_build(system):
    """eval_shape'd output dtype contradicting the declared Out spec is a
    build-time PortTypeMismatchError, not a runtime kernel death."""
    lying = kernel(In(jnp.float32), Out(jnp.int32),
                   nd_range=NDRange(dim_vec(N)),
                   name="lying")(lambda x: x + 1.0)   # computes float32
    g = Graph(system, name="liar")
    x = g.source("x", jnp.float32, shape=(N,))
    g.output(g.apply(lying, x))
    with pytest.raises(PortTypeMismatchError, match="liar/lying"):
        g.build()


def test_typed_ports_derived_via_eval_shape(system):
    g = _diamond(system, name="typed")
    g.validate()
    by_name = {n.name: n for n in g.nodes}
    assert by_name["double"].out_types == [PortType.of(jnp.float32, (N,))]
    assert by_name["zip_join"].out_types == [
        PortType.of(jnp.float32, (N,))] * 2
    assert by_name["add2"].out_types == [PortType.of(jnp.float32, (N,))]


# ----------------------------------------------------------------------------
# combinators
# ----------------------------------------------------------------------------
def test_multi_output_kernel_ports(system):
    g = Graph(system, name="fork2")
    x = g.source("x", jnp.float32, shape=(N,))
    hi, lo = g.apply(fork, x)
    g.output(g.apply(double, hi), g.apply(sub3, lo))
    built = g.build()
    xs = np.arange(N, dtype=np.float32)
    a, b = built.ask(xs)
    np.testing.assert_allclose(a, (xs + 10) * 2)
    np.testing.assert_allclose(b, (xs - 10) - 3)


def test_select_merge_routes_by_predicate(system):
    def pred(v):
        arr = v.to_value() if isinstance(v, DeviceRef) else np.asarray(v)
        return 0 if float(arr[0]) < 50 else 1

    g = Graph(system, name="route")
    x = g.source("x", jnp.float32, shape=(N,))
    t, f = g.select(x, pred)
    g.output(g.merge(g.apply(double, t), g.apply(sub3, f)))
    built = g.build()
    small = np.full(N, 1.0, np.float32)
    big = np.full(N, 100.0, np.float32)
    np.testing.assert_allclose(built.ask(small), small * 2)
    np.testing.assert_allclose(built.ask(big), big - 3)


def test_select_without_merge_yields_none_for_dead_output(system):
    g = Graph(system, name="deadout")
    x = g.source("x", jnp.float32, shape=(N,))
    t, f = g.select(x, lambda v: 0)          # branch 1 is always dead
    g.output(g.apply(double, t), g.apply(sub3, f))
    built = g.build()
    xs = np.ones(N, np.float32)
    taken, dead = built.ask(xs)
    np.testing.assert_allclose(taken, xs * 2)
    assert dead is None


def test_select_predicate_failure_fails_the_run_not_the_graph(system):
    g = Graph(system, name="badpred")
    x = g.source("x", jnp.float32, shape=(N,))
    t, f = g.select(x, lambda v: 1 / 0)
    g.output(g.merge(g.apply(double, t), g.apply(sub3, f)))
    built = g.build()
    with pytest.raises(ZeroDivisionError):
        built.ask(np.ones(N, np.float32))
    # the orchestrator survives: the next run is fine
    g2 = Graph(system, name="okpred")
    assert built.is_alive()


def test_map_over_chunks_through_scheduler(system, ref_baseline):
    g = Graph(system, name="mapped")
    x = g.source("x", jnp.float32)
    m = g.map_over(prep, x, chunks=4, replicas=3)
    g.output(g.apply(double, m))
    built = g.build()
    xs = np.arange(64, dtype=np.float32)
    reset_transfer_stats()
    out = built.ask(xs)
    np.testing.assert_allclose(out, (xs + 1) * 2)
    # chunk slices, per-chunk results, and the concat all stay on device
    assert transfer_count() == 0
    time.sleep(0.2)
    gc.collect()
    assert live_ref_count() == ref_baseline


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="minus_head")
def minus_head(x):
    return x - x[0]


@pytest.mark.parametrize("min_chunk_bytes,chunk_len", [(0, 16), (None, 64)],
                         ids=["forced-4-chunks", "default-keeps-small-whole"])
def test_map_over_min_chunk_bytes_sets_chunk_count(system, min_chunk_bytes,
                                                   chunk_len):
    """Each chunk sees only its own slice, so ``x - x[0]`` shows where the
    chunks start: ``min_chunk_bytes=0`` splits 256 bytes 4 ways, the 1 MiB
    default keeps them whole."""
    g = Graph(system, name=f"mapchunks{chunk_len}")
    x = g.source("x", jnp.float32)
    kw = {} if min_chunk_bytes is None else {
        "min_chunk_bytes": min_chunk_bytes}
    g.output(g.map_over(minus_head, x, chunks=4, replicas=2, **kw))
    xs = np.arange(64, dtype=np.float32)
    np.testing.assert_allclose(g.build().ask(xs), xs % chunk_len)


def test_map_over_rejects_multi_arg_kernels(system):
    g = Graph(system, name="mapbad")
    x = g.source("x", jnp.float32)
    with pytest.raises(GraphError, match="exactly one input"):
        g.map_over(add2, x)


def test_map_over_rejects_preprocess_kernels(system):
    """Chunk payloads are DeviceRefs; a preprocess (which runs before ref
    unwrapping) would crash every replica — rejected at graph-build time."""
    pre = prep.with_options(preprocess=lambda x: x * 2.0)
    g = Graph(system, name="mappre")
    x = g.source("x", jnp.float32)
    with pytest.raises(GraphError, match="mappre/.*preprocess"):
        g.map_over(pre, x)


def test_zero_input_node_fires(system):
    """A no-input producer (constant source stage) must execute even
    though no delivery ever triggers it."""
    g = Graph(system, name="const")
    x = g.source("x", jnp.float32, shape=(N,))
    c = g.apply(lambda: np.full(N, 5.0, np.float32), name="five")
    g.output(g.apply(add2, x, c))
    built = g.build()
    xs = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(built.ask(xs), xs + 5.0)


def test_broadcast_is_read_sharing(system):
    """Ref fan-out hands branches read-only views: a donating InOut
    consumer fails its own branch deterministically (AccessViolation)
    instead of invalidating the buffer under its sibling."""
    from repro.core import InOut
    updater = kernel(InOut(jnp.float32, as_ref=True),
                     nd_range=NDRange(dim_vec(N)),
                     name="upd")(lambda x: x * 2.0)
    g = Graph(system, name="donor")
    x = g.source("x", jnp.float32, shape=(N,))
    a, b = g.broadcast(g.apply(prep, x), 2)
    j1, j2 = g.zip_join(g.apply(updater, a), g.apply(double, b))
    g.output(g.apply(add2, j1, j2))
    built = g.build()
    from repro.core import AccessViolation
    with pytest.raises(AccessViolation):
        built.ask(np.arange(N, dtype=np.float32))


def test_broadcast_feeds_both_branches_same_buffer(system):
    g = Graph(system, name="fan")
    x = g.source("x", jnp.float32, shape=(N,))
    a, b = g.broadcast(g.apply(prep, x), 2)
    g.output(g.apply(double, a), g.apply(double, b))
    built = g.build()
    xs = np.arange(N, dtype=np.float32)
    r1, r2 = built.ask(xs)
    np.testing.assert_allclose(r1, (xs + 1) * 2)
    np.testing.assert_allclose(r2, (xs + 1) * 2)


def test_graph_failure_releases_refs_and_keeps_orchestrator(system,
                                                            ref_baseline):
    boom = kernel(In(jnp.float32), Out(jnp.float32),
                  nd_range=NDRange(dim_vec(N)),
                  name="boom")(lambda x: (_ for _ in ()).throw(
                      ValueError("kaboom")))
    g = Graph(system, name="failing")
    x = g.source("x", jnp.float32, shape=(N,))
    l, r = g.broadcast(g.apply(prep, x), 2)
    j1, j2 = g.zip_join(g.apply(double, l), g.apply(boom, r))
    g.output(g.apply(add2, j1, j2))
    built = g.build()
    with pytest.raises(Exception):
        built.ask(np.arange(N, dtype=np.float32))
    time.sleep(0.3)
    gc.collect()
    assert live_ref_count() == ref_baseline
    assert built.is_alive()


# ----------------------------------------------------------------------------
# Pipeline is a thin linear-Graph wrapper (behavior compatibility)
# ----------------------------------------------------------------------------
def test_pipeline_staged_is_graph_backed(system):
    pipe = (Pipeline(system, mode="staged")
            .stage(prep).stage(double).stage(sub3).build())
    assert isinstance(pipe, GraphRef)
    assert pipe.plan.chain_refs and len(pipe.plan.chain_refs) == 3
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(pipe.ask(x), (x + 1) * 2 - 3)


def test_linear_graph_matches_pipeline(system):
    x = np.arange(N, dtype=np.float32)
    pipe = (Pipeline(system, mode="staged")
            .stage(prep).stage(double).build())
    g = Graph(system, name="lin")
    s = g.source("x", jnp.float32, shape=(N,))
    g.output(g.apply(double, g.apply(prep, s)))
    np.testing.assert_array_equal(np.asarray(pipe.ask(x)),
                                  np.asarray(g.build().ask(x)))


def test_built_graph_usable_as_pipeline_stage(system):
    inner = _diamond(system, name="inner").build()
    outer = (Pipeline(system, mode="staged")
             .stage(prep).stage(inner).build())
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(outer.ask(x), _diamond_expected(x + 1),
                               rtol=1e-6)


def test_graph_in_actor_pool(system):
    built = [_diamond(system, name=f"pooled{i}").build() for i in range(2)]
    pool = ActorPool(system, built, policy="round_robin")
    x = np.arange(N, dtype=np.float32)
    for _ in range(4):
        np.testing.assert_allclose(pool.ask(x), _diamond_expected(x),
                                   rtol=1e-6)


def test_source_arity_checked_at_request_time(system):
    built = _diamond(system, name="arityrt").build()
    with pytest.raises(GraphError, match="source"):
        built.ask(np.zeros(N, np.float32), np.zeros(N, np.float32))
    assert built.is_alive()


def test_graph_placements_reported(system, mngr):
    built = _diamond(system, name="placed").build()
    assert set(built.placements) == {
        "placed/double", "placed/sub3", "placed/add2"}
    devices = set(mngr.devices())
    assert all(d in devices for d in built.placements.values())


# ----------------------------------------------------------------------------
# dist/serve integration
# ----------------------------------------------------------------------------
def test_pipeline_runner_over_graph(system):
    from repro.dist.pipeline import PipelineRunner
    g = Graph(system, name="runner")
    s = g.source("x", jnp.float32, shape=(N,))
    l, r = g.broadcast(g.apply(prep, s), 2)
    j1, j2 = g.zip_join(g.apply(double, l), g.apply(sub3, r))
    g.output(g.apply(add2, j1, j2))
    runner = PipelineRunner(system, graph=g, depth=3)
    mbs = [np.full(N, i, np.float32) for i in range(6)]
    outs = runner.run(mbs)
    for mb, out in zip(mbs, outs):
        np.testing.assert_allclose(out, _diamond_expected(mb + 1), rtol=1e-6)


def test_pipeline_runner_rejects_both_or_neither(system):
    from repro.dist.pipeline import PipelineRunner
    with pytest.raises(ValueError):
        PipelineRunner(system)
    g = Graph(system, name="both")
    with pytest.raises(ValueError):
        PipelineRunner(system, [system.spawn(lambda x: x)], graph=g)


def test_serve_engine_with_graph_step(system):
    from repro.serve import ServeEngine

    @kernel(In(jnp.int32), In(jnp.float32), Out(jnp.int32),
            Out(jnp.float32, as_ref=True), nd_range=NDRange(dim_vec(4)),
            name="decode_step")
    def decode_step(tok, acc):
        return tok + 1, acc + tok.astype(jnp.float32)

    g = Graph(system, name="decoder")
    tk = g.source("tokens", jnp.int32)
    ac = g.source("acc", jnp.float32)
    o_tok, o_acc = g.apply(decode_step, tk, ac)
    g.output(o_tok, o_acc)
    step_graph = g.build()

    def init(prompt):
        return {"acc": jnp.zeros((), jnp.float32)}, int(prompt)

    eng = ServeEngine(system, init_fn=init, step_graph=step_graph,
                      n_workers=1, max_batch=4).start()
    try:
        futs = [eng.submit(i, max_new_tokens=3) for i in range(5)]
        for i, f in enumerate(futs):
            assert f.result(60).tokens == [i + 1, i + 2, i + 3]
    finally:
        eng.stop()
    assert eng.stats()["completed"] == 5


def test_serve_graph_step_with_passthrough_leaf(system):
    """A cache leaf the graph forwards unchanged (source wired straight to
    an output) must survive the decode step: the worker may not release
    the input column before reading the result."""
    from repro.serve import ServeEngine

    @kernel(In(jnp.int32), In(jnp.float32), Out(jnp.int32),
            Out(jnp.float32, as_ref=True), nd_range=NDRange(dim_vec(4)),
            name="pt_step")
    def pt_step(tok, acc):
        return tok + 1, acc + tok.astype(jnp.float32)

    g = Graph(system, name="pt_decoder")
    tk = g.source("tokens", jnp.int32)
    ac = g.source("acc", jnp.float32)
    st = g.source("static", jnp.float32)
    o_tok, o_acc = g.apply(pt_step, tk, ac)
    g.output(o_tok, o_acc, st)           # "static" leaf passes through
    step_graph = g.build()

    def init(prompt):
        return {"acc": jnp.zeros((), jnp.float32),
                "static": jnp.full((), 7.0, jnp.float32)}, int(prompt)

    eng = ServeEngine(system, init_fn=init, step_graph=step_graph,
                      n_workers=1, max_batch=4).start()
    try:
        futs = [eng.submit(i, max_new_tokens=3) for i in range(3)]
        for i, f in enumerate(futs):
            assert f.result(60).tokens == [i + 1, i + 2, i + 3]
    finally:
        eng.stop()


# ----------------------------------------------------------------------------
# satellites: pool ask timeout + DeviceRef diagnostic repr
# ----------------------------------------------------------------------------
def test_pool_ask_timeout_names_routed_worker(system):
    from concurrent.futures import TimeoutError as FuturesTimeout
    sleepy = system.spawn(lambda x: time.sleep(5) or x)
    pool = ActorPool(system, [sleepy], default_timeout=0.05)
    with pytest.raises(FuturesTimeout, match=rf"ActorRef#{sleepy.actor_id}"):
        pool.ask(1)          # default_timeout from the pool
    with pytest.raises(FuturesTimeout, match="0.01"):
        pool.ask(1, timeout=0.01)


def test_pool_ask_preserves_worker_raised_timeout(system):
    """A TimeoutError raised *by the worker itself* must surface verbatim,
    not be relabeled as a pool timeout pointing at a healthy replica."""
    def impatient(x):
        raise TimeoutError("inner deadline blew up")

    pool = ActorPool(system, [system.spawn(impatient)], default_timeout=30.0)
    with pytest.raises(TimeoutError, match="inner deadline"):
        pool.ask(1)


def test_map_over_empty_input(system):
    """An empty leading axis flows one empty chunk through the kernel,
    yielding an empty result instead of a concatenate crash."""
    g = Graph(system, name="mapempty")
    x = g.source("x", jnp.float32)
    g.output(g.map_over(prep, x, chunks=4, replicas=2))
    built = g.build()
    out = built.ask(np.zeros((0,), np.float32))
    assert np.asarray(out).shape == (0,)
    assert np.asarray(out).dtype == np.float32


def test_serve_engine_cacheless_graph_step(system):
    """A zero-leaf cache works: the single-output graph resolves to a
    bare value and the worker still honours the step contract."""
    from repro.serve import ServeEngine

    @kernel(In(jnp.int32), Out(jnp.int32), nd_range=NDRange(dim_vec(4)),
            name="stateless_step")
    def stateless_step(tok):
        return tok + 2

    g = Graph(system, name="stateless")
    tk = g.source("tokens", jnp.int32)
    g.output(g.apply(stateless_step, tk))
    step_graph = g.build()

    eng = ServeEngine(system, init_fn=lambda p: ({}, int(p)),
                      step_graph=step_graph, n_workers=1, max_batch=4,
                      ).start()
    try:
        fut = eng.submit(10, max_new_tokens=2)
        assert fut.result(60).tokens == [12, 14]
    finally:
        eng.stop()


def test_serve_engine_rejects_pool_plus_step(system):
    from repro.serve import ServeEngine
    pool = ActorPool(system, [system.spawn(lambda *a: a)])
    with pytest.raises(ValueError, match="adopted pool"):
        ServeEngine(system, init_fn=lambda p: ({}, 0), pool=pool,
                    step_fn=lambda c, t: (t, c))


def test_spawn_pool_threads_default_timeout(system, mngr):
    pool = mngr.spawn_pool(prep, 2, default_timeout=7.5)
    assert pool.default_timeout == 7.5


def test_deviceref_repr_diagnostics():
    ref = DeviceRef.put(np.ones(N, np.float32), access="rw")
    live = repr(ref)
    assert "float32" in live and "rw" in live and f"{N * 4}B" in live
    assert "live" in live
    ref.spill()
    spilled = repr(ref)
    assert "spilled" in spilled and "host" in spilled
    ref.release()
    assert "released" in repr(ref)
