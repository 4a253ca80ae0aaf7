"""Readers of the ``.moonlight`` metrics: a DeepSeek-V3 decoder's decode
step, one chip's share of its experts (``bench/counts_mla_moe.py`` for the
arithmetic). Each takes the ``Run`` of ``bench/harness.py`` and returns a
number, or ``None`` where the run holds nothing to read; the expert
counters (``held_choices``, ``touched_experts``) are the adapter's totals
over the steps between the window's two counter readings.
"""
from __future__ import annotations

from typing import Optional

from bench import counts_mla_moe as counts
from bench import readers
from bench.trace_reduce import is_custom_kernel


def _delta(run, key: str) -> Optional[int]:
    if key not in run.counters_start or key not in run.counters_end:
        return None
    return readers.delta(run, key)


def _contexts(step):
    _, batch, pos = step
    return [pos + 1] * batch


def decode_mfu_pct(run) -> Optional[float]:
    """The chip's-share FLOPs of the window's steps (every weight outside
    the routed experts, each choice routed to a held expert, absorbed
    attention at each context) over the window at the bf16 peak."""
    steps = readers.steps_in_window(run)
    held = _delta(run, "held_choices")
    if not steps or held is None or run.peaks is None:
        return None
    flops = sum(counts.step_flops(run.config, _contexts(s)) for s in steps)
    flops += counts.routed_flops(run.config, held)
    return (100.0 * flops
            / (readers.window_s(run) * run.peaks["bf16_flops_per_s"]))


def decode_hbm_roofline_pct(run) -> Optional[float]:
    """Least bytes of a step (weights outside the routed experts, the
    touched experts' weights, the latent cache at the occupied lengths) at
    peak HBM bandwidth, over the step's device time; means over the
    window's steps."""
    steps = readers.steps_in_window(run)
    touched = _delta(run, "touched_experts")
    ms = readers.decode_step_ms(run)
    if not steps or touched is None or ms is None or run.peaks is None:
        return None
    per_step = sum(counts.step_bytes(run.config, _contexts(s), 0)
                   for s in steps) / len(steps)
    per_step += touched / len(steps) * counts.expert_bytes(run.config)
    return 100.0 * per_step / run.peaks["hbm_bytes_per_s"] / (ms / 1e3)


def experts_hbm_roofline_pct(run) -> Optional[float]:
    """The touched experts' weight bytes of a mean layer-step at peak HBM
    bandwidth, over the mean device time of one run of the window's Pallas
    kernels (the held-expert kernel is the step's only one, and runs once
    an expert layer-step).

    Means, not window totals: the profiler keeps only part of the op
    events of a window this long (Moonlight's step runs thousands of
    ops), so a sum of the kept kernel runs undercounts the time. A
    layer-step with no choice landing here skips the kernel and touches
    nothing; counting it among the layer-steps makes the number read low,
    never high."""
    touched = _delta(run, "touched_experts")
    steps = readers.delta(run, "steps")
    if run.trace is None or not touched or steps <= 0 or run.peaks is None:
        return None
    runs = [e.end - e.start for e in run.trace.ops if is_custom_kernel(e)]
    if not runs or sum(runs) <= 0:
        return None
    per_run = touched / (steps * counts.n_moe_layers(run.config))
    return (100.0 * per_run * counts.expert_bytes(run.config)
            / run.peaks["hbm_bytes_per_s"] / (sum(runs) / len(runs) / 1e9))


def tokens_per_held_expert(run) -> Optional[float]:
    """(token, choice) pairs routed to held experts per held expert and
    expert layer, over the window's steps."""
    held = _delta(run, "held_choices")
    steps = readers.delta(run, "steps")
    if held is None or steps <= 0:
        return None
    return held / (steps * counts.n_moe_layers(run.config)
                   * int(run.config["n_routed_experts"]))
