"""Three-process serve-mesh demo: a MeshRouter on the driver sharding
requests across EngineReplica actors on worker nodes, surviving a
worker SIGKILL mid-traffic.

    python -m repro.launch.serve_mesh --workers 2 --rps 40 --duration 6

The driver listens, ``multiprocessing``-spawns generic worker processes
(:func:`repro.launch.node.run_worker` — the same binary every
distributed demo uses; behaviors ship at spawn time), ``spawn_remote``\\ s
one engine replica per worker, and drives an offered-load sweep. Midway
one worker is SIGKILLed: the router's monitor fires on NodeDown, the
requests in flight on the dead replica replay on the survivors, and the
demo asserts **zero lost and zero duplicated requests** — every
submitted request resolves exactly once with the tokens the toy model
predicts. The returned summary records achieved RPS and p99 latency
before / during / after the failure window.

Everything here is module-level so both sides of the spawn can import it
(the worker needs :func:`toy_engine` importable to build the shipped
:class:`~repro.serve.mesh.ReplicaSpec`).

The worker processes run JAX on the CPU (``run_worker(platforms="cpu")``,
set before their first JAX call); only the parent process may take an
accelerator. A TPU belongs to one process: without the setting, a worker
on a TPU host whose parent holds the chip fails at its first JAX call.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

__all__ = ["toy_engine", "run_demo", "main"]


# ----------------------------------------------------------------------------
# toy decode model (module-level: shipped to workers inside a ReplicaSpec)
# ----------------------------------------------------------------------------
def toy_engine(system, *, service_delay_s: float = 0.01, n_workers: int = 1,
               max_batch: int = 8, max_wait_ms: float = 2.0):
    """Engine factory for :class:`~repro.serve.mesh.ReplicaSpec`: the
    counter toy model (cache row ``[seed, step]``, token ``seed*1000 +
    step`` — every request's output is predictable, so exactly-once is
    checkable from results alone), slowed by ``service_delay_s`` per
    decode step to simulate real model cost. The sleep forces
    ``jit_step=False``: inside a jitted step it would only fire at trace
    time."""
    import jax.numpy as jnp

    from repro.serve import ServeEngine

    def step(cache, tokens):
        if service_delay_s:
            time.sleep(service_delay_s)
        next_tok = (cache[:, 0] * 1000 + cache[:, 1]).astype(jnp.int32)
        return next_tok, cache.at[:, 1].add(1)

    def init(prompt):
        return jnp.asarray([int(prompt), 0], jnp.int32), 0

    return ServeEngine(system, step, init, n_workers=n_workers,
                       max_batch=max_batch, max_wait_ms=max_wait_ms,
                       jit_step=False)


def expected_tokens(seed: int, n: int) -> List[int]:
    return [seed * 1000 + i for i in range(n)]


# ----------------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------------
def _window_metrics(records, done_times, start: float, end: float,
                    label: str) -> Dict[str, Any]:
    """Achieved RPS (completions landing in the window) and p99 latency
    (requests *submitted* in the window) for one wall-clock slice."""
    done_in = [t for t in done_times.values() if start <= t < end]
    lats = sorted(done_times[i] - sub for i, (sub, _) in enumerate(records)
                  if start <= sub < end and i in done_times)
    span = max(end - start, 1e-9)
    return {
        "window": label,
        "start_s": round(start, 3),
        "end_s": round(end, 3),
        "completed": len(done_in),
        "achieved_rps": len(done_in) / span,
        "p99_ms": (lats[min(len(lats) - 1,
                            int(round(0.99 * (len(lats) - 1))))] * 1e3
                   if lats else 0.0),
    }


def run_demo(workers: int = 2, *, rps: float = 40.0, duration_s: float = 6.0,
             kill_at_s: float = 2.0, recover_window_s: float = 1.5,
             max_new_tokens: int = 4, service_delay_s: float = 0.01,
             kill_one: bool = True, timeout: float = 120.0) -> dict:
    """Run the 1-driver + ``workers``-worker mesh sweep; returns a
    summary dict (also asserts the acceptance invariants — an
    AssertionError here is a real regression)."""
    import multiprocessing as mp

    from repro.core import ActorSystem
    from repro.net import NodeRuntime
    from repro.serve import MeshRouter, ReplicaSpec

    from .node import run_worker

    summary: dict = {"workers": workers, "offered_rps": rps,
                     "duration_s": duration_s, "kill_one": kill_one}
    system = ActorSystem("mesh-driver")
    node = NodeRuntime(system, name="driver", listen=("127.0.0.1", 0))
    ctx = mp.get_context("spawn")
    children: Dict[str, Any] = {}
    killer: Optional[threading.Timer] = None
    try:
        for i in range(workers):
            name = f"worker{i}"
            p = ctx.Process(target=run_worker, args=(node.address, name),
                            kwargs={"platforms": "cpu"}, daemon=True)
            p.start()
            children[name] = p
        for name in children:
            if not node.wait_for_peer(name, timeout):
                raise TimeoutError(f"{name} never connected")

        spec = ReplicaSpec(toy_engine, service_delay_s=service_delay_s)
        router = MeshRouter(system, node, spec=spec, slo_budget_s=5.0,
                            min_replicas=workers, max_replicas=workers,
                            control_interval=0.1, max_attempts=5)
        for name in children:
            router.spawn_replica(name)
        router.start()
        # first touch builds each replica's engine (lazy on_start), and a
        # short warm-up sweep pays every replica's first-step cost before
        # the clock starts — the pre-failure window should measure steady
        # state, not cold start
        for rep in list(router._replicas.values()):
            rep.ref.ask("ping", timeout=timeout)
        n_warm = 4 * workers
        for f in [router.submit(0, max_new_tokens=2)
                  for _ in range(n_warm)]:
            f.result(timeout)

        victim = f"worker{workers - 1}"
        if kill_one:
            killer = threading.Timer(kill_at_s, children[victim].kill)
            killer.start()

        t0 = time.monotonic()
        records: List[tuple] = []        # (submit_rel_s, future)
        done_times: Dict[int, float] = {}  # index -> completion_rel_s

        def on_done(i, fut):
            done_times[i] = time.monotonic() - t0

        interval = 1.0 / rps
        n = 0
        while True:
            rel = time.monotonic() - t0
            if rel >= duration_s:
                break
            fut = router.submit(n, max_new_tokens=max_new_tokens)
            fut.add_done_callback(lambda f, i=n: on_done(i, f))
            records.append((rel, fut))
            n += 1
            time.sleep(max(0.0, (t0 + n * interval) - time.monotonic()))

        # every request resolves — lost requests would hang/raise here,
        # duplicates are impossible by construction (a future resolves
        # once; first-wins)
        for i, (_, fut) in enumerate(records):
            res = fut.result(timeout)
            assert res.tokens == expected_tokens(i, max_new_tokens), \
                f"request {i} got wrong tokens {res.tokens}"
        assert len(done_times) == len(records), "a completion went missing"

        s = router.stats()
        summary["submitted"] = s["submitted"] - n_warm
        summary["completed"] = s["completed"] - n_warm
        summary["replayed"] = s["replayed"]
        summary["replicas_lost"] = s["replicas_lost"]
        summary["lost"] = s["submitted"] - s["completed"]
        assert s["completed"] == len(records) + n_warm, s
        assert s["failed"] == 0 and s["shed"] == 0, s

        if kill_one:
            assert s["replicas_lost"] == 1, s
            assert s["replayed"] >= 1, \
                f"no request was in flight on {victim} at kill time: {s}"

        end = max(done_times.values())
        pre = _window_metrics(records, done_times, 0.0, kill_at_s, "pre")
        during = _window_metrics(records, done_times, kill_at_s,
                                 kill_at_s + recover_window_s, "during")
        post = _window_metrics(records, done_times,
                               kill_at_s + recover_window_s,
                               max(duration_s, end), "post")
        summary["windows"] = [pre, during, post]
        if kill_one:
            assert post["achieved_rps"] >= 0.8 * pre["achieved_rps"], \
                (f"throughput did not recover: pre {pre['achieved_rps']:.1f} "
                 f"rps, post {post['achieved_rps']:.1f} rps")
        router.shutdown()
        return summary
    finally:
        if killer is not None:
            killer.cancel()
        node.shutdown()
        system.shutdown()
        for p in children.values():
            if p.is_alive():
                p.kill()
            p.join(timeout=30)


def main(argv=None) -> None:
    import argparse
    import json

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--rps", type=float, default=40.0)
    p.add_argument("--duration", type=float, default=6.0)
    p.add_argument("--kill-at", type=float, default=2.0)
    p.add_argument("--no-kill", action="store_true",
                   help="skip the mid-run worker SIGKILL")
    args = p.parse_args(argv)
    out = run_demo(args.workers, rps=args.rps, duration_s=args.duration,
                   kill_at_s=args.kill_at, kill_one=not args.no_kill)
    print(json.dumps(out, indent=2, default=str))


if __name__ == "__main__":
    main()
