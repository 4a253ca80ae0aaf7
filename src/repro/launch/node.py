"""Worker-node launcher: join a cluster and serve actors until the
driver goes away.

    python -m repro.launch.node --connect 127.0.0.1:45123 --name worker0

A bare worker node publishes nothing of its own — the driver populates it
with ``NodeRuntime.spawn_remote(peer, behavior, publish=...)``. That keeps
the worker binary generic: behaviors live in driver-side code (any
picklable module-level callable / Actor subclass / KernelDecl) and are
shipped at spawn time, the same way CAF ships typed actor messages to a
remote ``middleman``.

:func:`run_worker` is the library entry point the two-process tests and
``examples/dist_pipeline.py`` run in their child processes (it must be an
importable module-level function for ``multiprocessing``'s spawn start
method to pickle).
"""
from __future__ import annotations

import argparse
from typing import Optional, Tuple

__all__ = ["run_worker", "main"]


def run_worker(addr: Tuple[str, int], name: str, *,
               compress: bool = False,
               max_workers: int = 8,
               timeout: Optional[float] = None,
               platforms: Optional[str] = None) -> None:
    """Connect to the driver at ``addr`` and serve until it disconnects.

    Blocks in ``NodeRuntime.join()``; on return the local actor system is
    shut down. Runs in a fresh process, so imports stay inside.

    ``platforms`` (e.g. ``"cpu"``) pins this process's JAX backends before
    its first JAX call. A worker spawned on its parent's host takes
    ``"cpu"``: a TPU belongs to one process, so once the parent holds the
    chip a worker's first JAX call fails on libtpu's lock (or, where
    ``JAX_PLATFORMS`` is unset, quietly falls back to the CPU). ``None``
    (a worker on a host of its own) lets JAX pick the host's devices."""
    if platforms is not None:
        import jax
        jax.config.update("jax_platforms", platforms)
    from repro.core import ActorSystem
    from repro.net import NodeRuntime
    from repro.serve.mesh import local_replica_stats

    system = ActorSystem(name, max_workers=max_workers)
    node = NodeRuntime(system, name=name, compress=compress)
    # any EngineReplica the driver spawn_remotes here reports its load
    # through peer_stats (a mesh router reads this out of band of the
    # per-replica "stats" message path)
    node.add_stats_provider("serve", local_replica_stats)
    try:
        node.connect(tuple(addr))
        node.join(timeout=timeout)
    finally:
        node.shutdown()
        system.shutdown()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--connect", required=True, metavar="HOST:PORT",
                   help="driver node address to dial")
    p.add_argument("--name", default=None, help="cluster-unique node name")
    p.add_argument("--compress", action="store_true",
                   help="int8-compress float refs at the wire boundary")
    p.add_argument("--max-workers", type=int, default=8,
                   help="actor scheduler threads")
    args = p.parse_args(argv)
    host, _, port = args.connect.rpartition(":")
    import os
    run_worker((host, int(port)), args.name or f"worker-{os.getpid():x}",
               compress=args.compress, max_workers=args.max_workers)


if __name__ == "__main__":
    main()
