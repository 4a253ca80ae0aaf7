"""The program's spans (``repro.trace.span``) in a profiler trace.

One tiny engine run (a toy decode step, gang scheduling, two workers,
three requests) and one kernel-actor ask are recorded with
``jax.profiler``; every span of the serving engine and the actor runtime
must land in the ``/host:CPU`` plane with its arguments, nested where the
work nests. Without a profiler session the engine must serve the same
tokens and count the same.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import ActorSystem, In, NDRange, Out, dim_vec, kernel
from repro.serve import ServeEngine

PROMPTS = (3, 5, 7)
NEW_TOKENS = (2, 3, 4)

#: span → the arguments it carries
SPANS = {
    "repro.serve.batch_wait": {"got"},
    "repro.serve.admit": {"request", "queued_ms"},
    "repro.serve.step": {"step", "batch"},
    "repro.serve.dispatch": set(),
    "repro.actor.receive": {"actor", "queued_ms"},
    "repro.serve.combine": set(),
    "repro.serve.forward": set(),
    "repro.serve.split": set(),
    "repro.serve.readback": set(),
}

#: (inner, outer): every inner span lies inside an outer one of its thread
NESTED = [("repro.serve.combine", "repro.actor.receive"),
          ("repro.serve.forward", "repro.actor.receive"),
          ("repro.serve.split", "repro.actor.receive"),
          ("repro.serve.readback", "repro.actor.receive"),
          ("repro.serve.dispatch", "repro.serve.step")]

#: stats() entries that are timings, not counts
TIMINGS = ("latency", "ttft", "max_step_gap_ms")


# toy decode model: cache row = [seed, step]; token = seed*1000 + step
def counter_step(cache, tokens):
    next_tok = (cache[:, 0] * 1000 + cache[:, 1]).astype(jnp.int32)
    return next_tok, cache.at[:, 1].add(1)


def counter_init(prompt):
    return jnp.asarray([int(prompt), 0], jnp.int32), 0


def run_engine_and_ask():
    """Serve the three requests as one gang, then ask a kernel actor once.
    → (results, stats, kernel actor id, kernel answer)."""
    system = ActorSystem(max_workers=4)
    try:
        engine = ServeEngine(system, counter_step, counter_init, n_workers=2,
                             max_batch=4, allow_join=False)
        # queued before the engine starts, so the gang is all three
        futs = [engine.submit(p, max_new_tokens=n)
                for p, n in zip(PROMPTS, NEW_TOKENS)]
        engine.start()
        results = [f.result(timeout=120) for f in futs]
        engine.stop()
        doubler = system.spawn(
            kernel(In(jnp.float32), Out(jnp.float32),
                   nd_range=NDRange(dim_vec(8)),
                   name="double")(lambda x: x * 2.0))
        answer = doubler.ask(np.arange(8, dtype=np.float32))
        return results, engine.stats(), doubler.actor_id, answer
    finally:
        system.shutdown()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The traced run, and its host events: name → [(thread line, start
    ns, end ns, args)]."""
    out = str(tmp_path_factory.mktemp("trace"))
    # no Python function tracing: it slows the process, even after the
    # session has stopped, and the spans do not need it
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        results, stats, kernel_id, answer = run_engine_and_ask()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    events.setdefault(e.name, []).append(
                        (k, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return {"results": results, "stats": stats, "kernel_id": kernel_id,
            "answer": answer, "events": events}


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_recorded_with_its_args(recorded, name):
    evs = recorded["events"].get(name, [])
    assert evs, f"no {name} span in the host plane"
    for _, start, end, args in evs:
        assert end >= start
        assert set(args) == SPANS[name]
        assert all(isinstance(v, (int, float)) for v in args.values())


@pytest.mark.parametrize("inner,outer", NESTED,
                         ids=[f"{i}-in-{o}" for i, o in NESTED])
def test_spans_nest(recorded, inner, outer):
    ev = recorded["events"]
    for line, start, end, _ in ev[inner]:
        assert any(ol == line and os_ <= start and end <= oe
                   for ol, os_, oe, _ in ev[outer]), \
            f"{inner} at {start} is in no {outer} of its thread"


def test_span_args_count_the_run(recorded):
    ev, stats = recorded["events"], recorded["stats"]
    steps = ev["repro.serve.step"]
    assert sorted(a["step"] for *_, a in steps) == list(range(stats["steps"]))
    assert sum(a["batch"] for *_, a in steps) == stats["batch_slots"]
    assert sum(a["got"] for *_, a in ev["repro.serve.batch_wait"]) == \
        len(PROMPTS)
    assert sorted(a["request"] for *_, a in ev["repro.serve.admit"]) == \
        sorted(r.request_id for r in recorded["results"])
    assert len(ev["repro.serve.dispatch"]) == stats["steps"]
    for name in ("combine", "forward", "split", "readback"):
        assert len(ev[f"repro.serve.{name}"]) == stats["steps"]


def test_admission_wait_within_ttft(recorded):
    ttft_ms = {r.request_id: r.ttft_s * 1e3 for r in recorded["results"]}
    for *_, a in recorded["events"]["repro.serve.admit"]:
        assert 0 <= a["queued_ms"] <= ttft_ms[a["request"]]


def test_kernel_ask_has_a_receive_span(recorded):
    np.testing.assert_allclose(recorded["answer"],
                               2.0 * np.arange(8, dtype=np.float32))
    mine = [a for *_, a in recorded["events"]["repro.actor.receive"]
            if a["actor"] == recorded["kernel_id"]]
    assert len(mine) == 1 and mine[0]["queued_ms"] >= 0
    workers = [a for *_, a in recorded["events"]["repro.actor.receive"]
               if a["actor"] != recorded["kernel_id"]]
    assert len(workers) == recorded["stats"]["steps"]
    assert all(a["queued_ms"] >= 0 for a in workers)


def test_engine_serves_and_counts_the_same_untraced(recorded):
    results, stats, _, answer = run_engine_and_ask()
    want = recorded["results"]
    assert [r.tokens for r in results] == [r.tokens for r in want] == [
        [p * 1000 + i for i in range(n)] for p, n in zip(PROMPTS, NEW_TOKENS)]
    assert [r.steps for r in results] == [r.steps for r in want]
    assert set(stats) == set(recorded["stats"])
    counts = {k: v for k, v in stats.items() if k not in TIMINGS}
    assert counts == {k: v for k, v in recorded["stats"].items()
                      if k not in TIMINGS}
    np.testing.assert_array_equal(answer, recorded["answer"])

