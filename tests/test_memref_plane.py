"""The DeviceRef data plane (ISSUE 2 acceptance surface).

Covers: ref forwarding through staged pipelines with **zero** host
transfers between stages, access-rights enforcement, donation-after-use
errors, spill/unspill round-trips (incl. pickling — the paper's
distribution option (b)), placement-aware pool/scheduler routing, the
registry's live-bytes watermark accounting, and leak checks via
``live_ref_count()`` after every pipeline/pool run.
"""
import gc
import pickle
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AccessViolation, ActorPool, ActorSystem,
                        ChunkScheduler, DeviceRef, In, InOut, NDRange, Out,
                        Pipeline, dim_vec, kernel, live_ref_count,
                        memory_stats, reset_transfer_stats,
                        transfer_count)
from repro.core.memref import registry


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
    """Live-ref baseline for leak checks (GC first: other test modules may
    have dropped refs whose __del__ hasn't run yet)."""
    gc.collect()
    return live_ref_count()


def assert_refs_settle(baseline: int, timeout: float = 5.0) -> None:
    """Leak check that tolerates *in-flight* releases: a chain's cleanup
    runs in actor done-callbacks that can lag the caller's result by a
    scheduler beat (and stray callbacks from earlier test modules may
    still be draining), so poll with GC instead of sampling once. A real
    leak still fails — the count never comes back down to the baseline."""
    deadline = time.monotonic() + timeout
    while True:
        gc.collect()
        n = live_ref_count()
        if n <= baseline:
            return
        if time.monotonic() > deadline:
            assert n == baseline, f"{n - baseline} DeviceRefs leaked"
        time.sleep(0.02)


N = 16


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="p1")
def p1(x):
    return x + 1.0


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="p2")
def p2(x):
    return x * 2.0


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="p3")
def p3(x):
    return x - 3.0


@kernel(In(jnp.float32), Out(jnp.float32), nd_range=NDRange(dim_vec(N)),
        name="p4")
def p4(x):
    return x / 2.0


@kernel(In(jnp.float32), Out(jnp.float32, as_ref=True),
        nd_range=NDRange(dim_vec(N)), name="p4_ref")
def p4_ref(x):
    return x / 2.0


def _expected(x):
    return ((x + 1.0) * 2.0 - 3.0) / 2.0


# ----------------------------------------------------------------------------
# zero-copy staged pipelines (tentpole acceptance criterion)
# ----------------------------------------------------------------------------
def test_staged_4_stage_pipeline_zero_host_transfers(system, ref_baseline):
    """A 4-stage staged pipeline must forward DeviceRefs between stages:
    zero ``to_value()`` host transfers, exactly one final read-back."""
    pipe = (Pipeline(system, mode="staged")
            .stage(p1).stage(p2).stage(p3).stage(p4).build())
    x = np.arange(N, dtype=np.float32)
    reset_transfer_stats()
    r = pipe.ask(x)
    np.testing.assert_allclose(r, _expected(x), rtol=1e-6)
    assert transfer_count() == 0, "stages round-tripped through the host"
    stats = memory_stats()
    assert stats["readbacks"] == 1      # only the final value read-back
    assert stats["spills"] == 0
    # intermediate refs were released by the chain
    assert_refs_settle(ref_baseline)


def test_staged_pipeline_ref_output_no_transfers_at_all(system, ref_baseline):
    """With a ref-semantics final stage the whole run does zero host
    traffic; the single transfer happens only at the explicit read-back."""
    pipe = (Pipeline(system, mode="staged")
            .stage(p1).stage(p2).stage(p3).stage(p4_ref).build())
    x = np.arange(N, dtype=np.float32)
    reset_transfer_stats()
    out = pipe.ask(x)
    assert isinstance(out, DeviceRef)
    assert transfer_count() == 0
    assert memory_stats()["readbacks"] == 0
    np.testing.assert_allclose(out.to_value(), _expected(x), rtol=1e-6)
    assert transfer_count() == 1        # the explicit read-back, counted
    out.release()
    assert_refs_settle(ref_baseline)


def test_staged_value_stages_promoted_to_refs_only_internally(system):
    """Promotion to ref emission must not leak into direct use: a worker
    spawned from the same decl still returns host values."""
    w = system.spawn(p1)
    x = np.arange(N, dtype=np.float32)
    out = w.ask(x)
    assert isinstance(out, np.ndarray)


def test_staged_from_existing_actors_forwards_refs(system, ref_baseline):
    """Existing value-semantics kernel actors get cloned (not mutated)
    into ref-emitting intermediates."""
    a, b = system.spawn(p1), system.spawn(p2)
    pipe = Pipeline(system, mode="staged").stages([a, b]).build()
    x = np.arange(N, dtype=np.float32)
    reset_transfer_stats()
    np.testing.assert_allclose(pipe.ask(x), (x + 1) * 2)
    assert transfer_count() == 0
    assert memory_stats()["readbacks"] == 1
    # the original actor is untouched: still value-emitting
    assert isinstance(a.ask(x), np.ndarray)
    assert_refs_settle(ref_baseline)


def test_staged_stage_with_preprocess_gets_values(system):
    """A successor stage with a preprocess must receive value payloads:
    the preprocess runs before ref unwrapping, so promoting the upstream
    stage to ref emission would hand it a DeviceRef."""
    consumer = p2.with_options(preprocess=lambda x: x * 2.0)
    pipe = Pipeline(system, mode="staged").stage(p1).stage(consumer).build()
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(pipe.ask(x), (x + 1) * 2 * 2)


def test_staged_passthrough_final_stage_keeps_ref_alive(system, ref_baseline):
    """An opaque final stage forwarding the upstream ref unchanged must
    hand the caller a *live* ref — the chain may not release a ref that
    escapes into the result."""
    ident = system.spawn(lambda r: r)
    pipe = Pipeline(system, mode="staged").stage(p4_ref).stage(ident).build()
    x = np.arange(N, dtype=np.float32)
    out = pipe.ask(x)
    assert isinstance(out, DeviceRef)
    np.testing.assert_allclose(out.to_value(), x / 2.0)   # still live
    out.release()
    assert_refs_settle(ref_baseline)


def test_staged_opaque_stage_gets_values(system):
    """A plain (non-kernel) actor downstream forces the kernel before it
    back to value emission — opaque actors never see DeviceRefs."""
    seen = []
    opaque = system.spawn(lambda x: (seen.append(type(x)), x + 1.0)[1])
    pipe = Pipeline(system, mode="staged").stage(p1).stage(opaque).build()
    x = np.arange(N, dtype=np.float32)
    np.testing.assert_allclose(pipe.ask(x), x + 2)
    assert seen and not issubclass(seen[0], DeviceRef)


# ----------------------------------------------------------------------------
# access rights (paper §3.5)
# ----------------------------------------------------------------------------
def test_read_only_ref_cannot_be_donated_or_updated(mngr, system):
    updater = system.spawn(
        kernel(InOut(jnp.float32, as_ref=True),
               nd_range=NDRange(dim_vec(4)), name="upd")(lambda x: x * 2.0))
    full = DeviceRef.put(np.ones(4, np.float32))
    ro = full.restrict("r")
    with pytest.raises(AccessViolation):
        ro.donate()
    # the buffer is usable through the original rw ref ...
    out = updater.ask(full)
    np.testing.assert_allclose(out.to_value(), 2.0)
    # ... but an in_out kernel rejects the read-only view (and dies with
    # the violation — actor fault semantics)
    with pytest.raises(AccessViolation):
        updater.ask(ro)
    out.release()
    ro.release()


def test_write_only_ref_cannot_be_read():
    ref = DeviceRef.put(np.ones(4, np.float32), access="w")
    with pytest.raises(AccessViolation):
        _ = ref.array
    with pytest.raises(AccessViolation):
        ref.to_value()
    with pytest.raises(AccessViolation):
        ref.spill()     # spilling serializes the contents: needs 'r' too
    ref.release()


def test_rights_cannot_widen():
    ref = DeviceRef.put(np.ones(4, np.float32), access="r")
    with pytest.raises(AccessViolation):
        ref.restrict("rw")
    with pytest.raises(ValueError):
        ref.restrict("x")
    ref.release()


# ----------------------------------------------------------------------------
# donation
# ----------------------------------------------------------------------------
def test_donation_after_use_raises(mngr, system):
    updater = system.spawn(
        kernel(InOut(jnp.float32, as_ref=True),
               nd_range=NDRange(dim_vec(4)), name="upd2")(lambda x: x + 1.0))
    ref = DeviceRef.put(np.zeros(4, np.float32))
    out = updater.ask(ref)
    np.testing.assert_allclose(out.to_value(), 1.0)
    # the incoming in_out ref was donated: every further use raises
    with pytest.raises(RuntimeError, match="donat"):
        _ = ref.array
    with pytest.raises(RuntimeError, match="donat"):
        ref.donate()
    with pytest.raises(RuntimeError, match="donat"):
        ref.spill()
    ref.release()   # release after donation is a no-op, not an error
    out.release()


def test_donate_returns_array_and_retires_accounting():
    base_bytes = registry.live_bytes()
    ref = DeviceRef.put(np.ones(8, np.float32))
    assert registry.live_bytes() == base_bytes + 32
    arr = ref.donate()
    assert arr.shape == (8,)
    assert registry.live_bytes() == base_bytes


# ----------------------------------------------------------------------------
# spill / unspill (distribution option (b))
# ----------------------------------------------------------------------------
def test_spill_roundtrip_through_pickle(ref_baseline):
    data = np.arange(12, dtype=np.float32).reshape(3, 4)
    ref = DeviceRef.put(data)
    with pytest.raises(TypeError):
        pickle.dumps(ref)               # device-resident: option (a)
    ref.spill()
    assert ref.is_spilled
    clone = pickle.loads(pickle.dumps(ref))     # option (b): explicit
    assert clone.is_spilled and clone.shape == (3, 4)
    clone.unspill()
    np.testing.assert_array_equal(clone.to_value(), data)
    ref.unspill()
    np.testing.assert_array_equal(ref.to_value(), data)
    ref.release()
    clone.release()
    assert_refs_settle(ref_baseline)


def test_spill_moves_bytes_off_device():
    base = registry.live_bytes()
    ref = DeviceRef.put(np.zeros(256, np.float32))
    assert registry.live_bytes() == base + 1024
    ref.spill()
    assert registry.live_bytes() == base        # host copy doesn't count
    ref.unspill()
    assert registry.live_bytes() == base + 1024
    ref.release()
    assert registry.live_bytes() == base


def test_spilled_ref_array_access_requires_unspill():
    ref = DeviceRef.put(np.ones(4, np.float32)).spill()
    with pytest.raises(RuntimeError, match="spill"):
        _ = ref.array
    # to_value on a spilled ref serves the host copy without a transfer
    before = transfer_count()
    np.testing.assert_allclose(ref.to_value(), 1.0)
    assert transfer_count() == before
    ref.release()


# ----------------------------------------------------------------------------
# registry accounting / watermarks
# ----------------------------------------------------------------------------
def test_registry_watermark_and_device_stats(mngr):
    dev = mngr.find_device()
    base_live = dev.live_bytes()
    refs = [DeviceRef.put(np.zeros(64, np.float32)) for _ in range(4)]
    assert dev.live_bytes() == base_live + 4 * 256
    assert dev.peak_bytes() >= dev.live_bytes()
    stats = mngr.memory_stats()
    assert stats[dev.name]["live_bytes"] == dev.live_bytes()
    for r in refs:
        r.release()
    assert dev.live_bytes() == base_live


def test_release_is_idempotent_and_terminal(ref_baseline):
    ref = DeviceRef.put(np.ones(4, np.float32))
    ref.release()
    ref.release()
    with pytest.raises(RuntimeError):
        _ = ref.array
    assert_refs_settle(ref_baseline)


# ----------------------------------------------------------------------------
# placement-aware routing
# ----------------------------------------------------------------------------
class _StubDevice:
    """Quacks like repro.core.manager.Device for routing tests."""

    def __init__(self, jax_device):
        self.jax_device = jax_device

    def queue_depth(self):
        return 0

    def live_bytes(self):
        return 0


def test_pool_prefers_worker_holding_the_ref(system):
    counts = [0, 0]

    def make(i):
        def fn(r):
            counts[i] += 1
            return np.float32(0.0)
        return fn

    ref = DeviceRef.put(np.ones(4, np.float32))
    local = _StubDevice(ref.device)
    remote = _StubDevice("somewhere-else")
    pool = ActorPool(system, [system.spawn(make(0)), system.spawn(make(1))],
                     policy="round_robin", devices=[remote, local])
    for _ in range(6):
        pool.ask(ref)
    assert counts == [0, 6], counts     # every request routed to `local`
    # without a ref payload, round-robin resumes cycling
    for _ in range(6):
        pool.ask(np.float32(1.0))
    assert counts[0] > 0
    ref.release()


def test_chunk_scheduler_take_pending_prefers_resident_chunks(system):
    """The placement-aware pop: a worker grabs the chunk already resident
    on its device, a foreign worker prefers affinity-free chunks, and FIFO
    is the fallback (strict affinity must never starve a worker)."""
    from repro.core.scheduler import WorkItem

    w_other = system.spawn(lambda *a: None)
    w_local = system.spawn(lambda *a: None)
    ref = DeviceRef.put(np.ones(2, np.float32))
    sched = ChunkScheduler(
        [w_other, w_local],
        devices=[_StubDevice("elsewhere"), _StubDevice(ref.device)])
    items = [WorkItem(0, (0, None)), WorkItem(1, (1, ref)),
             WorkItem(2, (2, ref))]
    pending = list(items)
    assert sched._take_pending(pending, w_local) is items[1]
    assert sched._take_pending(pending, w_other) is items[0]
    # only foreign-affinity chunks left: FIFO fallback keeps w_other busy
    assert sched._take_pending(pending, w_other) is items[2]
    ref.release()


def test_chunk_scheduler_ref_payloads_end_to_end(system):
    ref = DeviceRef.put(np.float32(10.0))
    workers = [system.spawn(
        lambda i, r: i + (float(r.to_value()) if r is not None else 0.0))
        for _ in range(2)]
    sched = ChunkScheduler(workers)
    res = sched.run([(i, ref if i % 2 else None) for i in range(6)],
                    timeout=60)
    assert [int(x) for x in res] == [0, 11, 2, 13, 4, 15]
    ref.release()


# ----------------------------------------------------------------------------
# pools + pipelines leave no refs behind
# ----------------------------------------------------------------------------
def test_pool_of_ref_kernels_leak_free(system, mngr, ref_baseline):
    pool = mngr.spawn_pool(p4_ref, 3, policy="least_loaded")
    x = np.arange(N, dtype=np.float32)
    outs = [pool.ask(x) for _ in range(9)]
    for o in outs:
        assert isinstance(o, DeviceRef)
        np.testing.assert_allclose(o.to_value(), x / 2.0)
        o.release()
    assert_refs_settle(ref_baseline)


def test_pipeline_failure_releases_intermediate_refs(system, ref_baseline):
    boom = system.spawn(
        kernel(In(jnp.float32), Out(jnp.float32),
               nd_range=NDRange(dim_vec(N)),
               name="boom")(lambda x: (_ for _ in ()).throw(ValueError("x"))))
    pipe = Pipeline(system, mode="staged").stage(p1).stage(p2).build()
    # chain p1 -> p2 -> boom manually: boom's failure must not leak p2's ref
    full = Pipeline(system, mode="staged").stages([pipe, boom]).build()
    with pytest.raises(Exception):
        full.ask(np.arange(N, dtype=np.float32))
    time.sleep(0.2)     # let the failure callback run
    assert_refs_settle(ref_baseline)


# ----------------------------------------------------------------------------
# compressed wire format on refs (dist/collectives)
# ----------------------------------------------------------------------------
def test_quantize_ref_roundtrip_and_wire_bytes(ref_baseline):
    from repro.dist.collectives import dequantize_ref, quantize_ref
    x = np.linspace(-1.0, 1.0, 128).astype(np.float32)
    ref = DeviceRef.put(x)
    qref, scale = quantize_ref(ref)
    assert qref.nbytes == ref.nbytes // 4       # int8: 4x fewer wire bytes
    qref.spill()                                # the compressed boundary
    shipped = pickle.loads(pickle.dumps(qref))
    deq = dequantize_ref(shipped.unspill(), scale)
    np.testing.assert_allclose(deq.to_value(), x, atol=2.0 / 254)
    for r in (ref, qref, shipped, deq):
        r.release()
    assert_refs_settle(ref_baseline)
