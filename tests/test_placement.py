"""Tests for the unified cost-model scheduler (``repro.core.placement``).

Everything placement-related is testable here with *fake cost tables*:
duck-typed devices expose ``live_bytes()``/``queue_depth()``/``name``,
``WireCostModel`` instances are built with crafted latency/throughput so
raw vs int8 outcomes are deterministic, and ``NodeTarget`` only needs an
object with a ``compress`` attribute until a spawn actually happens. The
final section swaps the process-wide service (``set_placement_service``)
and places a real graph across two in-process ``NodeRuntime``\\ s,
asserting a cross-node edge is chosen exactly when the wire model says
int8 compression amortizes the hop.
"""
import gc
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ActorSystem, DeviceRef, Graph, In, NDRange, Out,
                        dim_vec, kernel, payload_nbytes, placement_service,
                        set_placement_service)
from repro.core.placement import (GraphSite, NodeTarget, PlacementDecision,
                                  PlacementService, WireCostModel)
from repro.net import NodeRuntime


# ----------------------------------------------------------------------------
# fakes
# ----------------------------------------------------------------------------
class FakeDev:
    """Duck-typed stand-in for :class:`repro.core.manager.Device`."""

    def __init__(self, name, live=0, queue=0, jax_device=None):
        self.name = name
        self._live = live
        self._queue = queue
        self.jax_device = jax_device

    def live_bytes(self):
        return self._live

    def queue_depth(self):
        return self._queue

    def __repr__(self):
        return f"FakeDev({self.name})"


class FakeNode:
    """Just enough node for a NodeTarget that never spawns."""

    def __init__(self, compress=False):
        self.compress = compress


def svc(**kw):
    kw.setdefault("audit", 64)
    return PlacementService(**kw)


# ----------------------------------------------------------------------------
# WireCostModel
# ----------------------------------------------------------------------------
def test_wire_model_hop_and_roundtrip_prefer_int8_when_amortized():
    # slow wire, cheap compression: int8 must win when allowed
    m = WireCostModel(latency_s=1e-3, bytes_per_s=1e6, int8_ratio=4.0,
                      compress_overhead_s=1e-5, compress_bytes_per_s=1e9)
    n = 1 << 20
    assert m.hop_seconds(n, compressed=True) < m.hop_seconds(n)
    assert m.amortizes(n)
    s, enc = m.round_trip_seconds(n, n, allow_compress=True)
    assert enc == "int8"
    raw_s, raw_enc = m.round_trip_seconds(n, n, allow_compress=False)
    assert raw_enc == "raw" and s < raw_s


def test_wire_model_fast_wire_keeps_raw():
    # wire so fast the quantize pass never pays for itself
    m = WireCostModel(latency_s=1e-6, bytes_per_s=1e12,
                      compress_overhead_s=1e-2)
    _, enc = m.round_trip_seconds(1 << 20, 1 << 20, allow_compress=True)
    assert enc == "raw"
    assert not m.amortizes(1 << 20)


def test_wire_model_choose_compress_respects_min_bytes():
    m = WireCostModel(latency_s=1e-3, bytes_per_s=1e6,
                      compress_overhead_s=1e-5, min_compress_bytes=4096)
    assert not m.choose_compress(1024)      # below the floor
    assert m.choose_compress(1 << 20)


def test_wire_model_observe_small_updates_latency_large_updates_rate():
    m = WireCostModel(latency_s=1e-3, bytes_per_s=1e6, alpha=0.5)
    m.observe(512, 3e-3)                    # latency probe
    assert m.latency_s == pytest.approx(2e-3)
    rate0 = m.bytes_per_s
    m.observe(1 << 20, 2.0)                 # throughput sample
    assert m.bytes_per_s != rate0
    assert m.observations == 2


def test_wire_model_observe_per_peer_cells():
    m = WireCostModel(latency_s=1e-3, bytes_per_s=1e6, alpha=1.0)
    m.observe(256, 0.5, peer="slow")
    m.observe(256, 0.002, peer="fast")
    # per-peer cells diverge even though both fold into the global EWMA
    assert m.hop_seconds(256, peer="slow") > m.hop_seconds(256, peer="fast")
    snap = m.snapshot()
    assert snap["peers"]["slow"]["latency_s"] == pytest.approx(0.5)
    assert snap["peers"]["fast"]["latency_s"] == pytest.approx(0.002)


# ----------------------------------------------------------------------------
# rank(): the ActorPool query
# ----------------------------------------------------------------------------
def test_rank_least_loaded_orders_by_outstanding_then_queue_then_live():
    s = svc()
    cands = [("w0", FakeDev("d0", live=100, queue=5)),
             ("w1", FakeDev("d1", live=999, queue=0)),
             ("w2", FakeDev("d2", live=0, queue=0))]
    d = s.rank(cands, outstanding={"w0": 0, "w1": 0, "w2": 3})
    assert d.chosen == "w1"                 # w2 loses on outstanding
    assert d.reason == "least-loaded"
    d = s.rank(cands, outstanding={"w1": 1})
    assert d.chosen == "w2"


def test_rank_tie_keeps_candidate_order():
    s = svc()
    cands = [("a", FakeDev("d", 0, 0)), ("b", FakeDev("d", 0, 0))]
    assert s.rank(cands).chosen == "a"      # first-wins, like the old min()


def test_rank_residency_prefers_payload_device():
    s = svc()
    ref = DeviceRef(jnp.arange(8.0))
    try:
        home = FakeDev("home", live=10**9, queue=99, jax_device=ref.device)
        away = FakeDev("away", live=0, queue=0, jax_device=None)
        d = s.rank([("away", away), ("home", home)], payload=(ref,))
        # resident worker wins despite being far more loaded
        assert d.chosen == "home" and d.reason == "residency"
        assert d.terms["resident"] is True
    finally:
        ref.release()


def test_rank_round_robin_ticks_only_on_fallback():
    s = svc()
    ticks = itertools.count()
    cands = [("a", FakeDev("d0")), ("b", FakeDev("d1")), ("c", FakeDev("d2"))]
    picks = [s.rank(cands, policy="round_robin",
                    rr_tick=lambda: next(ticks)).chosen for _ in range(4)]
    assert picks == ["a", "b", "c", "a"]
    assert next(ticks) == 4                 # one tick per pick, no extras
    # a residency match must NOT consume a tick
    ref = DeviceRef(jnp.arange(4.0))
    try:
        resident = [("res", FakeDev("dr", jax_device=ref.device))]
        d = s.rank(resident, payload=(ref,), policy="round_robin",
                   rr_tick=lambda: next(ticks))
        assert d.chosen == "res"
        assert next(ticks) == 5             # counter untouched by rank()
    finally:
        ref.release()


def test_rank_decision_records_all_alternatives():
    s = svc()
    cands = [("w0", FakeDev("d0", live=5)), ("w1", FakeDev("d1", live=0))]
    d = s.rank(cands, context="pool:test")
    assert isinstance(d, PlacementDecision)
    assert {a.target for a in d.alternatives} == {"w0", "w1"}
    loser = next(a for a in d.alternatives if a.target == "w0")
    assert loser.terms["live_bytes"] == 5   # loser's terms reconstructible
    assert "pool:test" in d.explain()
    assert s.decisions("pool")[-1] is d


def test_rank_empty_candidates_raises():
    with pytest.raises(ValueError):
        svc().rank([])


# ----------------------------------------------------------------------------
# pick_device: deterministic tie-break (satellite fix)
# ----------------------------------------------------------------------------
def test_pick_device_name_tiebreak_is_deterministic():
    s = svc()
    # equal load in both orders: the *name* must decide, not list order
    for order in ([FakeDev("zz"), FakeDev("aa")],
                  [FakeDev("aa"), FakeDev("zz")]):
        assert s.pick_device(order).chosen.name == "aa"


def test_pick_device_load_beats_name():
    s = svc()
    d = s.pick_device([FakeDev("aa", live=100), FakeDev("zz", live=0)])
    assert d.chosen.name == "zz"
    assert d.terms == {"live_bytes": 0, "queue_depth": 0}


def test_pick_device_empty_raises():
    with pytest.raises(LookupError):
        svc().pick_device([])


# ----------------------------------------------------------------------------
# classify_chunks: the ChunkScheduler query
# ----------------------------------------------------------------------------
def test_classify_chunks_partitions_by_residency():
    s = svc()
    ref = DeviceRef(jnp.arange(8.0))
    try:
        payloads = [(ref,), ("opaque",), (np.arange(3),)]
        local, neutral = s.classify_chunks(payloads, ref.device)
        assert local == [0]
        assert neutral == [1, 2]
        # a worker on some other device sees no local chunks
        local, neutral = s.classify_chunks(payloads, None)
        assert local == [] and neutral == [1, 2]
    finally:
        ref.release()


# ----------------------------------------------------------------------------
# rank_replicas: the MeshRouter query
# ----------------------------------------------------------------------------
def test_rank_replicas_least_expected_wait():
    s = svc()
    d = s.rank_replicas([("r0", 0.5, 0), ("r1", 0.1, 1), ("r2", 0.1, 0)])
    assert d.chosen == "r2"
    assert d.reason == "least-expected-wait"
    assert len(d.alternatives) == 3
    # ties keep snapshot order
    assert s.rank_replicas([("x", 0.1, 0), ("y", 0.1, 0)]).chosen == "x"


def test_observe_replica_feeds_peer_load_into_graph_placement():
    s = svc()
    s.observe_replica("rep-1", wait_s=3.0, inflight=1, peer="b",
                      load={"queue_depth": 7})
    assert s.peer_load_s("b") == pytest.approx((3.0 + 1e-3) * 2)
    assert s.replica_load()["rep-1"]["queue_depth"] == 7
    # a loaded peer loses a hop it would otherwise win
    s.wire = WireCostModel(latency_s=1e-6, bytes_per_s=1e12)
    site = GraphSite(idx=0, path="g/k", in_bytes=1024, out_bytes=1024,
                     remote_ok=True)
    placements, _ = s.place_graph([site], [FakeDev("local")],
                                  remotes=[NodeTarget(FakeNode(), "b")])
    assert placements[0].name == "local"


def test_observe_hop_refines_wire_model():
    s = svc(wire=WireCostModel(latency_s=1e-3, alpha=0.5))
    s.observe_hop("b", 256, 5e-3)
    assert s.wire.observations == 1
    assert s.wire.snapshot()["peers"]["b"]["latency_s"] > 1e-3
    assert s.choose_compress(64, "b") is False   # below min_compress_bytes


# ----------------------------------------------------------------------------
# place_graph against fake cost tables
# ----------------------------------------------------------------------------
def _chain_sites(**kw):
    """source-fed kernel chain: k0 -> k1 (k1 inherits from k0)."""
    return [GraphSite(idx=1, path="g/k0", in_bytes=4096, out_bytes=4096,
                      remote_ok=True, **kw),
            GraphSite(idx=2, path="g/k1", producers=(1,), in_bytes=4096,
                      out_bytes=4096, remote_ok=True)]


def test_place_graph_local_only_inherits_upstream():
    s = svc()
    devs = [FakeDev("d0", live=50), FakeDev("d1", live=0)]
    placements, decisions = s.place_graph(_chain_sites(), devs)
    assert placements[1].name == "d1"       # least loaded
    assert placements[2].name == "d1"       # inherited, zero-move
    assert decisions[1].terms["reason"] == "inherit-upstream"


def test_place_graph_fallback_name_tiebreak():
    s = svc()
    devs = [FakeDev("zz"), FakeDev("aa")]   # equal load, adversarial order
    placements, _ = s.place_graph(
        [GraphSite(idx=0, path="g/k")], devs)
    assert placements[0].name == "aa"


def test_place_graph_pinned_and_fixed_sites():
    s = svc()
    pin = FakeDev("pinned")
    sites = [GraphSite(idx=0, path="g/pin", pinned=pin),
             GraphSite(idx=1, path="g/actor", fixed=True)]
    placements, decisions = s.place_graph(sites, [FakeDev("other")])
    assert placements[0] is pin
    assert decisions[0].reason == "explicit"
    assert 1 not in placements              # existing actor: left alone


def test_place_graph_cheap_wire_goes_remote():
    s = svc(mem_s_per_byte=1e-6)            # local pressure is expensive
    devs = [FakeDev("d0", live=10**7)]      # 10 s of modeled local cost
    s.wire = WireCostModel(latency_s=1e-4, bytes_per_s=1e9)
    target = NodeTarget(FakeNode(), "b")
    placements, decisions = s.place_graph(_chain_sites(), devs,
                                          remotes=[target])
    assert placements[1] is target
    assert decisions[0].reason == "wire-amortized:raw"
    # the losing local device is in the audit record
    assert any(a.target == "d0" for a in decisions[0].alternatives)


def test_place_graph_expensive_wire_stays_local():
    s = svc(mem_s_per_byte=1e-6)
    devs = [FakeDev("d0", live=10**7)]
    s.wire = WireCostModel(latency_s=1e3, bytes_per_s=1e6)  # 1000 s hops
    placements, decisions = s.place_graph(
        _chain_sites(), devs, remotes=[NodeTarget(FakeNode(), "b")])
    assert placements[1].name == "d0"
    # the rejected hop is still auditable
    remote_alt = next(a for a in decisions[0].alternatives
                      if a.target == "node:b")
    assert remote_alt.cost > decisions[0].cost


@pytest.mark.parametrize("compress", [False, "auto"], ids=["raw", "int8"])
def test_place_graph_default_costs_keep_near_free_kernel_local(compress):
    """Under the service's default costs a kernel on an idle device stays
    local: neither a raw nor an int8 round trip beats it, and the
    rejected hop is still in the audit record."""
    site = GraphSite(idx=0, path="g/scale", in_bytes=1 << 18,
                     out_bytes=1 << 18, remote_ok=True)
    placements, decisions = svc().place_graph(
        [site], [FakeDev("d0")],
        remotes=[NodeTarget(FakeNode(compress=compress), "b")])
    assert placements[0].name == "d0"
    assert decisions[0].reason in ("least-loaded", "inherit-upstream")
    rejected = next(a for a in decisions[0].alternatives
                    if a.target == "node:b")
    assert rejected.cost > decisions[0].cost


def test_place_graph_int8_amortization_decides_the_hop():
    """The acceptance shape: raw round trip costs MORE than local, int8
    costs LESS — so the cross-node edge is chosen iff the target's node
    allows compression."""
    nbytes = 1 << 20
    site = GraphSite(idx=0, path="g/k", in_bytes=nbytes, out_bytes=nbytes,
                     remote_ok=True)
    # raw round trip: 2*(0.1 + 1M/4e6)s ~ 0.72s; int8: 2*(0.1+0.25M/4e6+
    # ~0.001)s ~ 0.33s; local modeled cost pinned between the two
    wire = WireCostModel(latency_s=0.1, bytes_per_s=4e6, int8_ratio=4.0,
                         compress_overhead_s=1e-3,
                         compress_bytes_per_s=1e9, envelope_bytes=0)
    local = FakeDev("d0", live=5 * 10**5)
    raw_s, _ = wire.round_trip_seconds(nbytes, nbytes)
    int8_s, enc = wire.round_trip_seconds(nbytes, nbytes,
                                          allow_compress=True)
    s = svc(wire=wire, mem_s_per_byte=1e-6)
    local_s = local.live_bytes() * s.mem_s_per_byte
    assert int8_s < local_s < raw_s and enc == "int8"   # the setup holds

    plain = NodeTarget(FakeNode(compress=False), "plain")
    compressing = NodeTarget(FakeNode(compress="auto"), "zipped")
    placements, decisions = s.place_graph([site], [local], remotes=[plain])
    assert placements[0] is local           # raw hop never amortizes
    placements, decisions = s.place_graph([site], [local],
                                          remotes=[compressing])
    assert placements[0] is compressing     # int8 does
    assert decisions[0].reason == "wire-amortized:int8"
    assert decisions[0].terms["encoding"] == "int8"
    # audit: both the local device and the hop were scored
    assert {a.target for a in decisions[0].alternatives} >= \
        {"d0", "node:zipped"}


def test_place_graph_untyped_edges_never_remote():
    s = svc(mem_s_per_byte=1e-3)
    s.wire = WireCostModel(latency_s=1e-9, bytes_per_s=1e15)  # free hops
    devs = [FakeDev("d0", live=10**9)]
    sites = [GraphSite(idx=0, path="g/untyped", in_bytes=None,
                       out_bytes=4096, remote_ok=True),
             GraphSite(idx=1, path="g/noremote", in_bytes=4096,
                       out_bytes=4096, remote_ok=False)]
    placements, _ = s.place_graph(sites, devs,
                                  remotes=[NodeTarget(FakeNode(), "b")])
    assert placements[0].name == "d0"
    assert placements[1].name == "d0"


def test_place_graph_remote_never_inherited_downstream():
    """A node fed by a remotely placed producer does not 'inherit' the
    NodeTarget — inheritance is a zero-copy argument, which only holds
    for local devices."""
    s = svc(mem_s_per_byte=1e-6)
    devs = [FakeDev("d0", live=10**7)]
    s.wire = WireCostModel(latency_s=1e-4, bytes_per_s=1e9)
    target = NodeTarget(FakeNode(), "b")
    sites = [GraphSite(idx=0, path="g/k0", in_bytes=4096, out_bytes=4096,
                       remote_ok=True),
             GraphSite(idx=1, path="g/k1", producers=(0,))]  # untyped
    placements, decisions = s.place_graph(sites, devs, remotes=[target])
    assert placements[0] is target
    assert placements[1].name == "d0"
    assert decisions[1].terms["reason"] == "least-loaded"


def test_decisions_ring_filters_and_clears():
    s = svc(audit=4)
    s.pick_device([FakeDev("a")], context="serve-engine")
    s.rank([("w", FakeDev("a"))], context="pool:least_loaded")
    assert len(s.decisions()) == 2
    assert [d.context for d in s.decisions("pool")] == ["pool:least_loaded"]
    for _ in range(10):                     # ring is bounded
        s.pick_device([FakeDev("a")])
    assert len(s.decisions()) == 4
    s.clear_decisions()
    assert s.decisions() == []


def test_payload_nbytes_walks_containers():
    ref = DeviceRef(jnp.arange(16.0))       # 64 bytes f32
    try:
        assert payload_nbytes((ref,)) == 64
        assert payload_nbytes(((ref, [np.zeros(4, np.float32)]),
                               {"k": "opaque"})) == 64 + 16
        assert payload_nbytes(("a", 3, None)) == 0
    finally:
        ref.release()


# ----------------------------------------------------------------------------
# end to end: a Graph placed across two in-process nodes
# ----------------------------------------------------------------------------
N = 64


# the decl must wrap a function that is still reachable by reference
# (spawn_remote pickles the declaration, and pickle resolves the wrapped
# function through its module attribute — which the decorator form shadows)
def _scale_impl(x):
    return x * 2.0


p_scale = kernel(In(jnp.float32), Out(jnp.float32),
                 nd_range=NDRange(dim_vec(N)), name="p_scale")(_scale_impl)


@pytest.fixture()
def node_pair():
    sa = ActorSystem("place-a", max_workers=4)
    sb = ActorSystem("place-b", max_workers=4)
    na = NodeRuntime(sa, name="a", listen=("127.0.0.1", 0),
                     heartbeat_interval=0.2, heartbeat_timeout=2.0,
                     compress="auto")
    nb = NodeRuntime(sb, name="b", heartbeat_interval=0.2,
                     heartbeat_timeout=2.0)
    nb.connect(na.address)
    assert na.wait_for_peer("b", 10)
    yield sa, sb, na, nb
    na.shutdown()
    nb.shutdown()
    sa.shutdown()
    sb.shutdown()


def _scale_graph(system, name):
    g = Graph(system, name=name)
    x = g.source("x", jnp.float32, shape=(N,))
    g.output(g.apply(p_scale, x))
    return g


def test_graph_cross_node_edge_only_when_amortized(node_pair):
    """Acceptance: the same graph over the same node pair goes remote
    under a wire model where int8 amortizes the hop, and stays local
    under one where it doesn't — with the audit trail proving why."""
    sa, sb, na, nb = node_pair
    ballast = DeviceRef(jnp.zeros(1 << 18, jnp.float32))  # 1 MiB live
    x = np.arange(N, dtype=np.float32)

    cheap = PlacementService(
        wire=WireCostModel(latency_s=1e-6, bytes_per_s=1e12,
                           compress_overhead_s=0.0, min_compress_bytes=1),
        mem_s_per_byte=1e-3)                # >= ~1000 s modeled local cost
    dear = PlacementService(
        wire=WireCostModel(latency_s=1e6, bytes_per_s=1.0))
    prev = set_placement_service(cheap)
    try:
        target = NodeTarget(na, "b")
        remote_before = len(sb._actors)
        built = _scale_graph(sa, "xnode").build(remotes=[target])
        assert built.placements["xnode/p_scale"] is target
        assert len(sb._actors) == remote_before + 1    # spawned on the peer
        np.testing.assert_allclose(built.ask(x), x * 2.0, rtol=1e-6)
        dec = built.placement_decisions[0]
        assert dec.reason.startswith("wire-amortized")
        assert any(a.target == "node:b" for a in dec.alternatives)

        # identical graph, punitive wire: stays local, hop still audited
        set_placement_service(dear)
        built2 = _scale_graph(sa, "local").build(remotes=[target])
        placed = built2.placements["local/p_scale"]
        assert not isinstance(placed, NodeTarget)
        np.testing.assert_allclose(built2.ask(x), x * 2.0, rtol=1e-6)
        dec2 = built2.placement_decisions[0]
        assert dec2.reason in ("least-loaded", "inherit-upstream")
        rejected = next(a for a in dec2.alternatives
                        if a.target == "node:b")
        assert rejected.cost > dec2.cost
    finally:
        set_placement_service(prev)
        ballast.release()
        gc.collect()


def test_default_service_is_process_wide():
    a = placement_service()
    assert a is placement_service()
    assert isinstance(a, PlacementService)
    assert isinstance(a.wire, WireCostModel)
