"""Graph fusion pass + inline-dispatch fast path (ISSUE 7 acceptance).

Covers: fused-vs-staged numerical equivalence, region reporting
(``plan.fused_regions``) and single-actor lowering, fusion-boundary
correctness (broadcast / select / merge / opaque-actor / cross-device
edges break regions), ``emit="ref"`` preservation at region boundaries,
the inline-dispatch counters (single-consumer same-device edges bypass
the mailbox on ``ask``; shared/monitored edges keep it), supervision
semantics under inline dispatch (DownMessage still delivered), crash
replay staying exactly-once, and run-scoped ref accounting for fused
runs on success and failure.
"""
import gc
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ActorSystem, DownMessage, Graph, GraphRef, In,
                        KernelActor, NDRange, Out, Pipeline, dim_vec, kernel,
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


def _chain(system, kernels, name="chain"):
    g = Graph(system, name=name)
    cur = g.source("x", jnp.float32, shape=(N,))
    for k in kernels:
        cur = g.apply(k, cur)
    g.output(cur)
    return g


def _prefixed_diamond(system, name="pdiamond"):
    """source → prep → double → broadcast(2) → double/sub3 → zip → add2:
    a two-kernel fusible prefix in front of the PR 4 diamond shape."""
    g = Graph(system, name=name)
    x = g.source("x", jnp.float32, shape=(N,))
    h = g.apply(double, g.apply(prep, x))
    l, r = g.broadcast(h, 2)
    j1, j2 = g.zip_join(g.apply(double, l), g.apply(sub3, r))
    g.output(g.apply(add2, j1, j2))
    return g


def _prefixed_diamond_expected(x):
    h = (x + 1) * 2
    return h * 2 + h - 3


# ----------------------------------------------------------------------------
# the fusion pass: regions, single-actor lowering, equivalence
# ----------------------------------------------------------------------------
def test_fused_chain_is_one_region_one_actor(system):
    built = _chain(system, [prep, double, sub3], name="fc").build(fuse=True)
    assert built.plan.fused_regions == [
        ["fc/prep", "fc/double", "fc/sub3"]]
    # one spawned node actor for the whole chain
    assert len(built.node_refs) == 1
    (path, ref), = built.node_refs.items()
    actor = system._actors[ref.actor_id].actor
    assert isinstance(actor, KernelActor)
    assert actor.fused_from == ("fc/prep", "fc/double", "fc/sub3")
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(built.ask(x), (x + 1) * 2 - 3, rtol=1e-6)


def test_fused_vs_staged_equivalence_on_diamond(system):
    x = np.arange(N, dtype=np.float32)
    staged = _prefixed_diamond(system, "pd_s").build()
    fused = _prefixed_diamond(system, "pd_f").build(fuse=True)
    assert staged.plan.fused_regions == []
    assert fused.plan.fused_regions == [["pd_f/prep", "pd_f/double"]]
    r_staged, r_fused = staged.ask(x), fused.ask(x)
    np.testing.assert_allclose(r_staged, _prefixed_diamond_expected(x),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(r_staged), np.asarray(r_fused))


def test_fused_boundary_emits_ref(system, ref_baseline):
    """A fused region feeding ref-capable consumers keeps emit="ref": the
    whole graph still moves zero bytes through the host."""
    built = _prefixed_diamond(system, "pd_ref").build(fuse=True)
    x = np.arange(N, dtype=np.float32)
    reset_transfer_stats()
    out = built.ask(x)
    np.testing.assert_allclose(out, _prefixed_diamond_expected(x), rtol=1e-6)
    assert transfer_count() == 0, "an interior edge round-tripped the host"
    assert memory_stats()["readbacks"] == 1     # only the final output
    time.sleep(0.2)
    gc.collect()
    assert live_ref_count() == ref_baseline


def test_pipeline_fused_mode_routes_through_graph_pass(system):
    pipe = (Pipeline(system, mode="fused", name="fp")
            .stage(prep).stage(double).stage(sub3).build())
    assert isinstance(pipe, GraphRef)
    assert len(pipe.plan.fused_regions) == 1
    assert len(pipe.plan.fused_regions[0]) == 3
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(pipe.ask(x), (x + 1) * 2 - 3, rtol=1e-6)


def test_traceable_adapter_fuses_into_region(system):
    g = Graph(system, name="adapt")
    cur = g.chain_source()
    cur = g.chain(prep, cur)
    cur = g.chain(lambda x: x * 10.0, cur, traceable=True)
    cur = g.chain(double, cur)
    g.output(cur)
    built = g.build(fuse=True)
    assert len(built.plan.fused_regions) == 1
    assert len(built.plan.fused_regions[0]) == 3
    x = np.ones(N, np.float32)
    np.testing.assert_allclose(built.ask(x), (x + 1) * 10 * 2, rtol=1e-6)


# ----------------------------------------------------------------------------
# fusion boundaries: what must NOT fuse
# ----------------------------------------------------------------------------
def test_broadcast_breaks_region(system):
    built = _prefixed_diamond(system, "pd_b").build(fuse=True)
    # only the prefix fuses; the broadcast arms and the sink stay separate
    assert built.plan.fused_regions == [["pd_b/prep", "pd_b/double"]]
    assert len(built.node_refs) == 4    # fused prefix + 2 arms + sink


def test_select_and_merge_break_regions(system):
    g = Graph(system, name="sm")
    x = g.source("x", jnp.float32, shape=(N,))
    h = g.apply(prep, x)
    hi, lo = g.select(h, lambda v: 0, 2)
    m = g.merge(g.apply(double, hi), g.apply(sub3, lo))
    g.output(g.apply(double, m))
    built = g.build(fuse=True)
    assert built.plan.fused_regions == []
    xs = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(built.ask(xs), (xs + 1) * 2 * 2, rtol=1e-6)


def test_opaque_actor_node_breaks_region(system):
    opaque = system.spawn(lambda x: x * 3.0)        # not traceable
    g = Graph(system, name="op")
    cur = g.chain_source()
    cur = g.chain(prep, cur)
    cur = g.chain(opaque, cur)
    cur = g.chain(double, cur)
    g.output(cur)
    built = g.build(fuse=True)
    assert built.plan.fused_regions == []
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(built.ask(x), (x + 1) * 3 * 2, rtol=1e-6)


def test_untraceable_python_stage_breaks_region(system):
    g = Graph(system, name="py")
    cur = g.chain_source()
    cur = g.chain(prep, cur)
    cur = g.chain(lambda x: x * 3.0, cur)       # no traceable=True
    cur = g.chain(double, cur)
    g.output(cur)
    assert g.build(fuse=True).plan.fused_regions == []


def test_cross_device_edge_breaks_region(system):
    class _FakeDev:
        def __init__(self):
            self.jax_device = object()

        def live_bytes(self):
            return 0

        def queue_depth(self):
            return 0

    d0, d1 = _FakeDev(), _FakeDev()
    g = Graph(system, name="xdev")
    x = g.source("x", jnp.float32, shape=(N,))
    cur = g.apply(prep, x, device=d0)
    cur = g.apply(double, cur, device=d1)
    g.output(cur)
    built = g.build(fuse=True)      # build-time only: never dispatched
    assert built.plan.fused_regions == []
    assert len(built.node_refs) == 2


# ----------------------------------------------------------------------------
# inline-dispatch fast path
# ----------------------------------------------------------------------------
def test_chain_ask_dispatches_inline(system):
    built = _chain(system, [prep, double, sub3], name="inl").build()
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(built.ask(x), (x + 1) * 2 - 3, rtol=1e-6)
    stats = built.dispatch_stats
    assert stats == {"inline": 3, "mailbox": 0}


def test_fused_chain_ask_dispatches_inline(system):
    built = _chain(system, [prep, double, sub3], name="finl").build(fuse=True)
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(built.ask(x), (x + 1) * 2 - 3, rtol=1e-6)
    assert built.dispatch_stats == {"inline": 1, "mailbox": 0}


def test_request_keeps_mailbox_path(system):
    built = _chain(system, [prep, double], name="mbx").build()
    x = np.arange(N, dtype=np.float32)
    fut = built.request(x)
    np.testing.assert_allclose(fut.result(timeout=30), (x + 1) * 2, rtol=1e-6)
    assert built.dispatch_stats == {"inline": 0, "mailbox": 2}


def test_broadcast_arms_keep_mailbox(system):
    built = _prefixed_diamond(system, "pd_c").build(fuse=True)
    x = np.arange(N, dtype=np.float32)
    built.ask(x)
    stats = built.dispatch_stats
    # fused prefix + sink dispatch inline; the two broadcast arms are
    # shared-producer edges and must keep the mailbox
    assert stats["inline"] == 2
    assert stats["mailbox"] == 2


def test_monitor_forces_mailbox_and_down_message(system):
    built = _chain(system, [prep, double], name="mon").build()
    seen = []
    watcher = system.spawn(lambda msg: seen.append(msg))
    stage1 = built.node_refs["mon/prep"]
    system.monitor(watcher, stage1)
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(built.ask(x), (x + 1) * 2, rtol=1e-6)
    stats = built.dispatch_stats
    # the monitored stage falls back to the mailbox; the other stays inline
    assert stats == {"inline": 1, "mailbox": 1}
    # crash the monitored stage: supervision semantics intact
    with pytest.raises(Exception):
        built.ask(np.arange(4, dtype=np.int64))
    deadline = time.monotonic() + 10
    while not seen and time.monotonic() < deadline:
        time.sleep(0.02)
    assert seen and isinstance(seen[0], DownMessage)
    assert seen[0].actor_id == stage1.actor_id


def test_inline_crash_replay_exactly_once(system):
    state = {"crashed": False, "runs": []}

    def flaky_pre(x):
        if not state["crashed"]:
            state["crashed"] = True
            raise RuntimeError("injected crash")
        state["runs"].append(float(np.asarray(x)[0]))
        return (x,)

    flaky = prep.with_options(name="flaky", preprocess=flaky_pre)
    workers = [_chain(system, [flaky], name=f"flk{i}").build()
               for i in range(2)]
    payloads = [np.full(N, float(i), np.float32) for i in range(4)]
    results = []
    for x in payloads:
        for w in workers:
            try:
                results.append(w.ask(x))
                break
            except Exception:
                continue        # failover: re-issue on the next worker
        else:
            pytest.fail("payload lost: every worker failed")
    for i, r in enumerate(results):
        np.testing.assert_allclose(r, payloads[i] + 1, rtol=1e-6)
    # the crashed attempt was replayed exactly once: every payload ran to
    # completion on exactly one worker, no duplicates
    assert sorted(state["runs"]) == [0.0, 1.0, 2.0, 3.0]
    # the crash happened on the inline path of worker 0
    assert workers[0].dispatch_stats["inline"] >= 1


# ----------------------------------------------------------------------------
# ref accounting for fused runs
# ----------------------------------------------------------------------------
def test_fused_run_releases_refs_on_failure(system, ref_baseline):
    @kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
            name="boom")
    def boom(x):
        raise RuntimeError("downstream failure")

    g = Graph(system, name="leak")
    x = g.source("x", jnp.float32, shape=(N,))
    cur = g.apply(double, g.apply(prep, x))     # fusible prefix, emits a ref
    l, r = g.broadcast(cur, 2)                  # boundary: prefix stays fused
    j1, j2 = g.zip_join(g.apply(boom, l), g.apply(sub3, r))
    g.output(g.apply(add2, j1, j2))
    built = g.build(fuse=True)
    assert built.plan.fused_regions == [["leak/prep", "leak/double"]]
    with pytest.raises(Exception):
        built.ask(np.arange(N, dtype=np.float32))
    time.sleep(0.2)
    gc.collect()
    assert live_ref_count() == ref_baseline


def test_fused_run_releases_refs_on_success(system, ref_baseline):
    built = _chain(system, [prep, double, sub3], name="ok").build(fuse=True)
    x = np.arange(N, dtype=np.float32)
    for _ in range(3):
        np.testing.assert_allclose(built.ask(x), (x + 1) * 2 - 3, rtol=1e-6)
    time.sleep(0.2)
    gc.collect()
    assert live_ref_count() == ref_baseline
