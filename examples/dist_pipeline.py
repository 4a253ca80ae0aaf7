"""Network-transparent two-process pipeline demo (paper §2.1/§3.5, ISSUE 5).

Spawns a worker **process**, connects it as a cluster node, and runs a
3-stage pipeline whose middle stage is a ``RemoteActorRef`` — the stage
boundary crosses the wire as exactly one int8-compressed spill/unspill
pair per hop (asserted on both processes' ``memory_stats()`` counters).
Then it SIGKILLs the worker mid-run to show cross-node supervision: local
monitors get a ``DownMessage`` and the dead node's in-flight chunks are
re-issued on the surviving local worker, every result exactly once.

The driver logic lives in ``repro.net.demo`` (module-level so the
``multiprocessing`` spawn child can import it); this file is the runnable
front door. The worker process runs JAX on the CPU (set in
``repro.net.demo.run_child``), so on a TPU host the chip stays with the
process that runs this file.

Run:  PYTHONPATH=src python examples/dist_pipeline.py
"""
import json

from repro.net import demo

if __name__ == "__main__":
    summary = demo.main()
    print(json.dumps(
        {k: (sorted(v) if isinstance(v, set) else v)
         for k, v in summary.items()}, indent=2, default=str))
    print("\nPASS: 3-stage cross-node pipeline, one spill/unspill pair per "
          "hop on each side, DownMessage + exactly-once re-issue after "
          "node death.")
