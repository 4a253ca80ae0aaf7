"""Idle gaps put down to the program's own spans (``repro.trace.span``).

Where no runtime event is open, the second half of a gap's label names the
innermost program span open over it, so ``breakdown.idle_gaps`` tells
admission, dispatch and the engine's own step work apart.
"""
import time

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event


def test_idle_gaps_name_the_program_span_open(tmp_path):
    import jax
    from repro.trace import span

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # it slows the process after the session
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with span("serve.admit", request=1, queued_ms=0.5):
            time.sleep(0.01)
        with span("serve.step", step=0, batch=1):
            with span("serve.dispatch"):
                time.sleep(0.01)
            time.sleep(0.01)
    jax.profiler.stop_trace()

    trace = tr.load(str(tmp_path))
    by_name = {e.name: e for e in trace.host if e.name.startswith("repro.")}
    admit, step, dispatch = (by_name[f"repro.serve.{n}"]
                             for n in ("admit", "step", "dispatch"))
    # one-microsecond device operations at the spans' edges split the
    # window into a gap per span
    trace.ops = {"/device:TPU:0": [
        Event("%fusion.1 = f32[8]{0} fusion(%a)", t, t + 1e3)
        for t in (admit.start, admit.end, dispatch.end, step.end)]}
    gaps = dict((label, s) for label, s in tr.reduce(trace).idle_gaps)
    for label, lo, hi in [("- / repro.serve.admit", admit.start, admit.end),
                          ("- / repro.serve.dispatch", admit.end,
                           dispatch.end),
                          ("- / repro.serve.step", dispatch.end, step.end)]:
        assert gaps[label] == pytest.approx((hi - lo - 1e3) / 1e9)
        assert gaps[label] >= 0.009
