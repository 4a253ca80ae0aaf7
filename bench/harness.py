"""One run of one cell: set up, measure a window, check, report.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``) runs from process start to the first timed request:
the system adapter makes its weights or data from the seed on the device
and warms up every shape the cell's traffic uses. The window then drives
the traffic for ``--seconds``; nothing may compile inside it (the count
is printed). After the window the peak device memory is read, the
program's state is freed, and the adapter compares what the timed path
produced with the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window, from the program's counters and from the benchmark's own spans
(``bench.*`` ``TraceAnnotation``s, only recorded when tracing).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``checks``: each compared number with its
limit. The checks are also the last lines of standard error.

There is no CPU fallback: with no TPU, or fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from bench import ROOT, load_json, load_module
from bench import generator, trace_reduce
from bench.peaks import PEAKS


class CompileLog:
    """Backend compile seconds and count, and persistent-cache hits and
    misses, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

    def install(self) -> None:
        from jax import monitoring

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> Dict[str, float]:
        return {"seconds": self.seconds, "compiles": self.compiles,
                "hits": self.hits, "misses": self.misses}


@dataclass
class Run:
    """What a metric reader gets (``bench/metrics/<name>.py: read(run)``)."""
    cell: dict
    config: dict
    traffic: dict
    records: List[dict]
    window: tuple                      # host monotonic seconds
    setup_s: float
    setup_compile: Dict[str, float]
    counters_start: Dict[str, Any]
    counters_end: Dict[str, Any]
    steps: List[tuple]                 # serving: (time, batch, position)
    device_kind: str
    peaks: Optional[dict]              # None where the kind has no entry
    trace: Optional[trace_reduce.Reduced] = None


def fail(msg: str, code: int) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str, overrides: Optional[dict] = None):
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[name]
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    overrides = overrides or {}
    if overrides.get("smoke"):
        config.update(config.get("smoke", {}))
        traffic.update(traffic.get("smoke", {}))
    config.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))
    return spec, cell, config, traffic


def metric_entries(spec: dict, cell: dict, trace: bool) -> List[dict]:
    return [m for m in spec["per_layer" if trace else "end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def drive(sut, traffic: dict, reqs: List[dict], seconds: float,
          span=generator.no_span):
    """Drive the traffic through the system for ``seconds``. → records,
    window, how late the open-loop generator ran (seconds)."""
    if traffic["loop"] == "open":
        return generator.drive_open(sut.submit, reqs, seconds,
                                    float(traffic.get("drain_s", 120)), span)
    records, window = generator.drive_closed(
        sut.issue, reqs, int(traffic["clients"]), seconds, span)
    return records, window, 0.0


def open_cell(workload: str, allow_cpu: bool = False,
              overrides: Optional[dict] = None):
    """Load the cell and the program, turn the compile cache on and find
    the chips. → (spec, cell, config, traffic, devices) or an exit code."""
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"{ROOT / 'src' / 'repro'} not found: run from a "
                    "checkout of the repository", 2)
    try:
        spec, cell, config, traffic = load_cell(workload, overrides)
    except (KeyError, FileNotFoundError) as exc:
        return fail(str(exc), 2)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    # the persistent compile cache lives at a fixed path in the checkout,
    # and the program is handed that path: runs of one checkout share it,
    # two checkouts never do
    cache_dir = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        return fail(f"no TPU (JAX found {platform}); the benchmark does not "
                    "run on the CPU", 3)
    if len(devices) < int(cell["chips"]):
        return fail(f"{cell['name']} needs {cell['chips']} chips, JAX found "
                    f"{len(devices)}", 3)
    print(f"bench: {cell['name']} on {len(devices)} x "
          f"{devices[0].device_kind}; compile cache {cache_dir}", flush=True)
    return spec, cell, config, traffic, devices


def main(argv=None, *, t_start: Optional[float] = None,
         allow_cpu: bool = False, overrides: Optional[dict] = None) -> int:
    """Run one cell. ``allow_cpu`` and ``overrides`` exist for the CPU
    rehearsal tests only, and the command line never sets them:
    ``overrides={"smoke": True}`` applies the ``smoke`` sizes of the
    configuration and traffic files; ``"config"``/``"traffic"`` dicts
    replace keys."""
    t_start = time.monotonic() if t_start is None else t_start
    args = parse(argv)
    log = CompileLog()
    log.install()
    opened = open_cell(args.workload, allow_cpu, overrides)
    if isinstance(opened, int):
        return opened
    spec, cell, config, traffic, devices = opened
    import jax

    kind = devices[0].device_kind
    span = ((lambda name: jax.profiler.TraceAnnotation(name)) if args.trace
            else generator.no_span)
    system = load_module("systems", config["system"])
    sut = system.System(config, traffic, args.seed, span=span)
    try:
        return measure(args, spec, cell, config, traffic, sut, log, span,
                       t_start, devices, kind)
    finally:
        sut.close()


def measure(args, spec, cell, config, traffic, sut, log, span, t_start,
            devices, kind) -> int:
    import jax

    setup_s = time.monotonic() - t_start
    setup_compile = log.snapshot()
    print(f"bench: set-up {setup_s:.3f} s, backend compile "
          f"{setup_compile['seconds']:.3f} s ({setup_compile['compiles']} "
          f"compiles, cache hits {setup_compile['hits']}, misses "
          f"{setup_compile['misses']})", flush=True)
    reqs = generator.schedule(traffic, args.seed, args.seconds)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    counters_start = sut.counters()
    if trace_dir:
        # host TraceMe events (the benchmark's spans among them), no
        # Python function tracing: it would slow the host several-fold
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with span(trace_reduce.WINDOW_SPAN):
        records, window, late = drive(sut, traffic, reqs, args.seconds, span)
    counters_end = sut.counters()
    if trace_dir:
        jax.profiler.stop_trace()
    after = log.snapshot()
    in_window = {k: after[k] - setup_compile[k] for k in after}
    print(f"bench: window {window[1] - window[0]:.3f} s, {len(records)} "
          f"requests, generator at most {late * 1e3:.3f} ms late; compiles "
          f"in the window: {in_window['compiles']} (cache hits "
          f"{in_window['hits']}, misses {in_window['misses']})", flush=True)
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices), default=0)

    sut.release()
    checks = sut.check(records)
    reduced = None
    if trace_dir:
        reduced = trace_reduce.reduce(trace_reduce.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(cell=cell, config=config, traffic=traffic, records=records,
              window=window, setup_s=setup_s, setup_compile=setup_compile,
              counters_start=counters_start, counters_end=counters_end,
              steps=list(getattr(sut, "steps", [])), device_kind=kind,
              peaks=PEAKS.get(kind), trace=reduced)
    metrics = {}
    for m in metric_entries(spec, cell, bool(args.trace)):
        value = load_module("metrics", m["name"]).read(run)
        if value is None or not math.isfinite(value):
            print(f"bench: metric {m['name']}: nothing to read", flush=True)
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    failed = sum(1 for r in records if not r.get("ok"))
    correct = failed == 0 and bool(records) and all(
        lim is None or (math.isfinite(value) and value <= lim)
        for _, value, lim in checks)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": len(records), "failed": failed,
                              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.top_gaps(10)}
    result["checks"] = {name: {"value": value if math.isfinite(value)
                               else None, "limit": lim}
                        for name, value, lim in checks}
    for r in records:
        if not r.get("ok"):
            print(f"bench: request {r['req']['i']} failed: "
                  f"{r.get('error', 'no answer')}", file=sys.stderr)
            break
    for name, value, lim in checks:
        print(f"check {name}: {value} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
