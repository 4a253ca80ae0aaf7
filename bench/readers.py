"""Arithmetic the metric readers share (``bench/metrics/<name>.py``).

Each function takes the ``Run`` of ``bench/harness.py`` and returns a
number, or ``None`` where the run holds nothing to read (no trace, no
device operations, no steps): the harness then leaves the metric out.
A share of a peak or a roofline is never made 0 for want of data.
"""
from __future__ import annotations

from typing import List, Optional

from bench import counts
from bench.generator import percentile
from bench.trace_reduce import is_custom_kernel, uncovered_per_span

#: name of the serving adapter's jitted decode step, as the trace's
#: ``XLA Modules`` line shows its executions
DECODE_STEP_PROGRAM = "jit_batched_step"


def ok(run) -> List[dict]:
    return [r for r in run.records if r.get("ok")]


def window_s(run) -> float:
    return run.window[1] - run.window[0]


# -- end to end -----------------------------------------------------------
def calls_per_s(run) -> Optional[float]:
    done = ok(run)
    return len(done) / window_s(run) if done else None


def ttft_p_ms(run, q: float) -> Optional[float]:
    """Due time to first token, every request due in the window; a failed
    or unfinished one counts as infinitely late."""
    if not run.records:
        return None
    vals = [(r["first"] - r["due"]) * 1e3 if r.get("ok") else float("inf")
            for r in run.records]
    return percentile(vals, q)


def tpot_p_ms(run, q: float) -> Optional[float]:
    vals = []
    for r in run.records:
        if not r.get("ok"):
            vals.append(float("inf"))
        elif len(r["tokens"]) > 1:
            vals.append((r["end"] - r["first"]) / (len(r["tokens"]) - 1)
                        * 1e3)
    return percentile(vals, q) if vals else None


# -- program counters -------------------------------------------------------
def delta(run, key: str):
    return run.counters_end[key] - run.counters_start[key]


def occupancy_pct(run) -> Optional[float]:
    steps = delta(run, "steps")
    if steps <= 0:
        return None
    return (100.0 * delta(run, "batch_slots")
            / (steps * int(run.traffic["max_batch"])))


# -- device trace -----------------------------------------------------------
def idle_share_pct(run) -> Optional[float]:
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.idle_share


def decode_step_runs(run) -> List[float]:
    if run.trace is None:
        return []
    return run.trace.module_runs.get(DECODE_STEP_PROGRAM, [])


def decode_step_ms(run) -> Optional[float]:
    runs = decode_step_runs(run)
    return 1e3 * sum(runs) / len(runs) if runs else None


def steps_in_window(run) -> List[tuple]:
    lo, hi = run.window
    return [s for s in run.steps if lo <= s[0] <= hi]


def _contexts(step) -> List[int]:
    _, batch, pos = step
    return [pos + 1] * batch


def decode_mfu_pct(run) -> Optional[float]:
    """Model FLOPs of the steps dispatched in the window over the window
    at the chip's bf16 peak."""
    steps = steps_in_window(run)
    if not steps or run.peaks is None:
        return None
    flops = sum(counts.decode_step_flops(run.config, _contexts(s))
                for s in steps)
    return 100.0 * flops / (window_s(run) * run.peaks["bf16_flops_per_s"])


def decode_hbm_roofline_pct(run) -> Optional[float]:
    """Least bytes a step must read (parameters, and K/V at the occupied
    lengths) at peak HBM bandwidth, over the step's device time; means
    over the window's steps."""
    steps = steps_in_window(run)
    ms = decode_step_ms(run)
    if not steps or ms is None or run.peaks is None:
        return None
    bytes_per_step = sum(counts.decode_step_bytes(run.config, _contexts(s))
                         for s in steps) / len(steps)
    return (100.0 * bytes_per_step / run.peaks["hbm_bytes_per_s"]
            / (ms / 1e3))


def asks_in_trace(run) -> int:
    return len(run.trace.spans.get("bench.ask", [])) if run.trace else 0


def host_ms_per_call(run) -> Optional[float]:
    if run.trace is None or not run.trace.ops:
        return None
    un = uncovered_per_span(run.trace, "bench.ask")
    return 1e3 * sum(un) / len(un) if un else None


def kernel_ms_per_call(run, pallas: bool) -> Optional[float]:
    n = asks_in_trace(run)
    if not n or run.trace is None or not run.trace.ops:
        return None
    ns = sum(e.end - e.start for e in run.trace.ops if is_custom_kernel(e) == pallas)
    return ns / 1e6 / n
