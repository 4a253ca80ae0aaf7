"""Tests for repro.analysis: the AST lint rules (driven by the fixture
snippets under tests/fixtures/lint/), the baseline workflow, the CLI,
and the dynamic TrackedLock / leak-sentinel runtime."""
import os
import subprocess
import sys
import threading

import pytest

from repro.analysis import order as order_mod
from repro.analysis import runtime as rt
from repro.analysis.lint import (compare, fingerprints, load_baseline,
                                 run_rules, write_baseline)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(name):
    findings, errors = run_rules([os.path.join(FIXTURES, name)])
    assert not errors, errors
    return findings


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ----------------------------------------------------------------------------
# rule fixtures: one failing + one passing file per rule
# ----------------------------------------------------------------------------
def test_silent_except_fixture():
    bad = lint("silent_except_bad.py")
    assert rules_of(bad) == ["silent-except"]
    assert len(bad) == 2          # except Exception: pass + bare except
    assert {f.detail for f in bad} == {"Exception", "bare"}
    assert lint("silent_except_ok.py") == []


def test_blocking_call_fixture():
    bad = lint("blocking_call_bad.py")
    assert rules_of(bad) == ["blocking-call-in-behavior"]
    assert {f.detail for f in bad} == {"time.sleep", ".ask()", ".result()"}
    assert {f.qualname for f in bad} == {
        "worker", "make_poller.poll", "Service._run"}
    assert lint("blocking_call_ok.py") == []


def test_ref_lifecycle_fixture():
    bad = lint("ref_lifecycle_bad.py")
    assert rules_of(bad) == ["ref-lifecycle"]
    details = {f.detail for f in bad}
    assert details == {
        "use-after-donate:ref", "use-after-release:ref",
        "pickle-without-spill:ref", "unreleased-ref:ref",
        "use-after-release:r",
    }
    assert lint("ref_lifecycle_ok.py") == []


def test_lock_order_fixture():
    bad = lint("lock_order_bad.py")
    assert rules_of(bad) == ["lock-order"]
    details = sorted(f.detail for f in bad)
    assert any(d.startswith("cycle:") and "FixtureA" in d and
               "FixtureB" in d for d in details), details
    assert "inversion:RefRegistry->PagePool" in details
    assert lint("lock_order_ok.py") == []


# ----------------------------------------------------------------------------
# baseline workflow
# ----------------------------------------------------------------------------
def test_baseline_roundtrip(tmp_path):
    findings = lint("silent_except_bad.py")
    bl = tmp_path / "baseline.txt"
    write_baseline(str(bl), findings)
    loaded = load_baseline(str(bl))
    assert loaded == fingerprints(findings)

    new, stale = compare(findings, loaded)
    assert new == [] and stale == []

    # deleting any one entry resurfaces exactly that finding
    new, stale = compare(findings, loaded[1:])
    assert len(new) == 1 and stale == []

    # fixing a finding leaves a stale entry (warning, not failure)
    new, stale = compare(findings[1:], loaded)
    assert new == [] and len(stale) == 1


def test_fingerprints_are_line_free_and_deduped():
    findings = lint("ref_lifecycle_bad.py")
    fps = fingerprints(findings)
    assert len(set(fps)) == len(fps)
    for fp in fps:
        relpath, rule, qual, detail = fp.split("::")
        assert relpath.endswith("ref_lifecycle_bad.py")
        assert not any(ch.isdigit() and "#" not in fp for ch in ())  # shape only
        assert rule == "ref-lifecycle" and qual and detail


def test_cli_gate(tmp_path):
    """End-to-end: bad fixture fails, --write-baseline then passes, and
    deleting a baseline line fails again."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"), REPRO_ANALYSIS="")
    bad = os.path.join(FIXTURES, "silent_except_bad.py")
    bl = str(tmp_path / "bl.txt")

    def cli(*args):
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis", *args],
            cwd=REPO, env=env, capture_output=True, text=True)

    assert cli(bad).returncode == 1
    assert cli(bad, "--baseline", bl, "--write-baseline").returncode == 0
    assert cli(bad, "--baseline", bl).returncode == 0
    lines = open(bl).read().splitlines()
    open(bl, "w").write("\n".join(lines[:-1]) + "\n")
    assert cli(bad, "--baseline", bl).returncode == 1


def test_repo_tree_is_clean_under_checked_in_baseline():
    findings, errors = run_rules([os.path.join(REPO, "src", "repro")])
    assert not errors, errors
    baseline = load_baseline(os.path.join(REPO, "analysis-baseline.txt"))
    new, _stale = compare(findings, baseline)
    assert new == [], [f.render() for f in new]


# ----------------------------------------------------------------------------
# ORDER.md <-> order.py
# ----------------------------------------------------------------------------
def test_canonical_order_parses_order_md():
    names = order_mod.CANONICAL_LOCK_ORDER
    assert names[0] == "MeshRouter"
    assert names[-1] == "RefRegistry"
    assert len(names) == len(set(names)) >= 19
    for expected in ("ChunkScheduler", "PagePool", "ActorState",
                     "NodeRuntime", "GraphRun", "PlacementService"):
        assert expected in names
    assert order_mod.rank_of("PagePool") < order_mod.rank_of("RefRegistry")
    # the placement service is queried by every dispatcher (pool,
    # scheduler, router, node runtime — all while holding their own
    # locks) and reads live-bytes through the registry while held: its
    # rank must sit strictly between DeviceManager and RefRegistry
    assert (order_mod.rank_of("DeviceManager")
            < order_mod.rank_of("PlacementService")
            < order_mod.rank_of("RefRegistry"))
    for outer in ("ActorPool", "ChunkScheduler", "MeshRouter",
                  "NodeRuntime"):
        assert order_mod.rank_of(outer) < \
            order_mod.rank_of("PlacementService")
    assert order_mod.rank_of("not-a-lock") is None
    assert os.path.exists(order_mod.order_path())


# ----------------------------------------------------------------------------
# dynamic runtime: TrackedLock / TrackedRLock
# ----------------------------------------------------------------------------
@pytest.fixture
def clean_lock_graph():
    """Deliberate-violation tests must not leave cycles/violations in
    the process-wide graph: the REPRO_ANALYSIS sessionfinish gate would
    (correctly) fail the whole run on them."""
    rt.reset_lock_graph()
    yield
    rt.reset_lock_graph()


def test_tracked_lock_cycle_fires(clean_lock_graph):
    a, b = rt.TrackedLock("CycA"), rt.TrackedLock("CycB")
    with a:
        with b:                       # records CycA -> CycB
            pass
    with b:
        with pytest.raises(rt.LockOrderViolation, match="cycle"):
            a.acquire()               # CycB -> CycA closes the cycle
    assert rt.recorded_violations()


def test_tracked_lock_canonical_rank_fires(clean_lock_graph):
    reg = rt.TrackedLock("RefRegistry")   # rank 20
    pool = rt.TrackedLock("PagePool")     # rank 11: must be taken first
    with reg:
        with pytest.raises(rt.LockOrderViolation, match="canonical"):
            pool.acquire()
    # the documented order is fine
    with pool:
        with reg:
            pass


def test_tracked_lock_failed_try_lock_leaves_no_edge(clean_lock_graph):
    """A non-blocking acquire that loses the race must not seed a
    phantom edge — try-lock fallback patterns would otherwise surface
    as false cycles."""
    a, b = rt.TrackedLock("ProbeA"), rt.TrackedLock("ProbeB")
    b._inner.acquire()                 # make b contended
    try:
        with a:
            assert not b.acquire(blocking=False)
    finally:
        b._inner.release()
    assert rt.lock_order_graph() == {}
    # a *successful* non-blocking acquire still records the edge
    with a:
        assert b.acquire(blocking=False)
        b.release()
    assert "ProbeB" in rt.lock_order_graph().get("ProbeA", {})


def test_tracked_lock_rank_check_sees_past_unranked(clean_lock_graph):
    """An unranked lock on top of the stack must not mask an inversion
    against a ranked lock held beneath it."""
    inner = rt.TrackedLock("RefRegistry")       # innermost rank
    mid = rt.TrackedLock("UnrankedMiddle")
    outer = rt.TrackedLock("PagePool")          # outer rank
    with inner:
        with mid:
            with pytest.raises(rt.LockOrderViolation, match="canonical"):
                outer.acquire()


def test_tracked_lock_self_deadlock_fires(clean_lock_graph):
    l = rt.TrackedLock("SelfL")
    with l:
        with pytest.raises(rt.LockOrderViolation, match="re-acquired"):
            l.acquire()


def test_tracked_rlock_reentrant_and_condition(clean_lock_graph):
    l = rt.TrackedRLock("ReentL")
    with l:
        with l:                       # reentrancy is fine
            assert l._is_owned()
    cv = threading.Condition(l)
    fired = []

    def waiter():
        with cv:
            fired.append(cv.wait(5.0))

    t = threading.Thread(target=waiter)
    t.start()
    for _ in range(500):
        with cv:
            if fired or cv._waiters:  # wait until the waiter is parked
                cv.notify_all()
                break
        threading.Event().wait(0.01)
    t.join(5.0)
    assert fired == [True]


def test_tracked_graph_snapshot_and_reset(clean_lock_graph):
    a, b = rt.TrackedLock("SnapA"), rt.TrackedLock("SnapB")
    with a:
        with b:
            pass
    graph = rt.lock_order_graph()
    assert "SnapB" in graph.get("SnapA", {})
    assert rt.lock_order_cycles() == []
    rt.reset_lock_graph()
    assert rt.lock_order_graph() == {}


def test_make_lock_seam_respects_env(monkeypatch):
    monkeypatch.setenv("REPRO_ANALYSIS", "1")
    assert isinstance(rt.make_lock("X"), rt.TrackedLock)
    assert isinstance(rt.make_rlock("X"), rt.TrackedRLock)
    monkeypatch.setenv("REPRO_ANALYSIS", "0")
    assert not isinstance(rt.make_lock("X"), rt.TrackedLock)
    assert not isinstance(rt.make_rlock("X"), rt.TrackedRLock)


# ----------------------------------------------------------------------------
# leak sentinel
# ----------------------------------------------------------------------------
def test_settled_ref_growth_counts_leaks():
    import jax.numpy as jnp

    from repro.core.memref import DeviceRef, live_ref_count

    before = live_ref_count()
    ref = DeviceRef(jnp.arange(8.0))
    assert rt.settled_ref_growth(before, timeout=0.2) == 1
    ref.release()
    assert rt.settled_ref_growth(before, timeout=2.0) <= 0


@pytest.mark.ref_leak_ok
def test_ref_leak_ok_marker_opts_out():
    """Holds a ref past the test body on purpose; the sentinel must not
    fail it (module-level holder released by the next test)."""
    import jax.numpy as jnp

    from repro.core.memref import DeviceRef

    _leaky.append(DeviceRef(jnp.arange(4.0)))


_leaky = []


def test_ref_leak_ok_cleanup():
    while _leaky:
        _leaky.pop().release()
