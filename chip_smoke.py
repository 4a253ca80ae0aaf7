#!/usr/bin/env python3
"""Bring-up check: drive the kernel-actor and serving paths once on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four layer-stage actors, one per chip

One process holds the chip(s) for the whole run. With no TPU it exits
non-zero at once and never falls back to the CPU. Every phase checks its
results against a reference; the first failed check exits non-zero.

One chip, in order:

* ``m_mult`` — the quickstart's matmul kernel actor, 4096² bf16, against
  an f32 ``jnp.dot`` (max error ≤ 1 % of max |want|).
* WAH index — ``build_wah_index`` over 2²⁴ values of cardinality 64, equal
  word for word to the same build with ``impl="ref"``; three bitmaps
  decoded back to the input positions; the staged ``fuseFillsLiterals``
  pipeline of kernel actors equal to the oracle interleave + compaction.
* Mandelbrot — a 1920×1080 frame (256 iterations) from a kernel actor;
  ≥ 98 % of pixels equal to ``ref.mandelbrot`` (escape counts of boundary
  pixels may differ by a few iterations under another f32 op order).
* Serving — ``repro.launch.serve`` in engine mode, qwen3-1.7b at full
  width, 16 requests × 32 tokens in batches of 8; every request gets its
  32 tokens, all inside the vocabulary. One decode step's logits (batch
  2, after 3 cached tokens) against an f32 forward pass under "highest"
  matmul precision: relative L2 error ≤ 5e-2.

Each Pallas kernel a phase runs must appear in its compiled program as a
``tpu_custom_call``: a fallback to the oracle or to interpret mode fails.

``--chips 4`` runs only the layer-stage pipeline: qwen3-1.7b split into
four ``make_layer_stage_actors`` stages, each on its own chip, streamed by
``PipelineRunner``; logits against ``model.forward`` on one chip
(relative L2 error ≤ 5e-2), and no two stages on one chip.

Earlier lines print wall time, compile time (with persistent-cache hits
and misses), tokens/s and peak device memory. The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from contextlib import contextmanager
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "qwen3-1.7b"
LOGIT_RTOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ----------------------------------------------------------------------------
# compile accounting and phase timing
# ----------------------------------------------------------------------------
class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
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

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self):
        return self.seconds, self.hits, self.misses


@contextmanager
def phase(name: str, log: CompileLog):
    import jax
    c0, h0, m0 = log.snapshot()
    t0 = time.perf_counter()
    print(f"[{name}] start", flush=True)
    yield
    wall = time.perf_counter() - t0
    c1, h1, m1 = log.snapshot()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) / 2**30
             for d in jax.local_devices()]
    print(f"[{name}] ok: wall {wall:.2f}s, backend compile {c1 - c0:.2f}s "
          f"(cache hits {h1 - h0}, misses {m1 - m0}), peak device memory "
          + ", ".join(f"{p:.2f}" for p in peaks) + " GiB", flush=True)


def timed(fn, *args):
    """→ (result, seconds) with the result ready on the device."""
    import jax
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def pallas_kernels(fn, *args) -> set:
    """Names of the Pallas kernels compiled as ``tpu_custom_call`` into
    the program of ``fn(*args)`` (``fn`` is jitted unless it already is)."""
    import jax
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    text = jitted.lower(*args).compile().as_text()
    names = set()
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.findall(r"jit\((pallas_\w+)\)/pallas_call", line)
            names.add(m[-1] if m else "?")
    return names


def expect_kernels(got: set, want: set, where: str) -> None:
    print(f"  {where}: tpu_custom_call for {sorted(got)}")
    check(want <= got, f"{where}: Pallas kernels {sorted(want - got)} did "
                       "not compile to tpu_custom_call")


def rel_l2(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ----------------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------------
def phase_matmul(system, n: int = 4096) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import In, NDRange, Out, dim_vec, kernel
    from repro.kernels import ops

    @kernel(In(jnp.bfloat16), In(jnp.bfloat16),
            Out(jnp.bfloat16, shape=(n, n)),
            nd_range=NDRange(dim_vec(n, n)), name="m_mult")
    def m_mult(a, b):
        return ops.matmul(a, b)

    ka, kb = jax.random.split(jax.random.key(1))
    a = jax.random.normal(ka, (n, n), jnp.bfloat16)
    b = jax.random.normal(kb, (n, n), jnp.bfloat16)
    expect_kernels(pallas_kernels(m_mult.fn, a, b), {"pallas_matmul"},
                   "m_mult")
    worker = system.spawn(m_mult)
    got, t1 = timed(worker.ask, a, b)
    got, t2 = timed(worker.ask, a, b)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32)))
    err = float(np.max(np.abs(np.asarray(got, np.float32) - want))
                / np.max(np.abs(want)))
    print(f"  m_mult {n}x{n} bf16: first ask {t1:.3f}s, second {t2:.3f}s "
          f"({2 * n ** 3 / t2 / 1e12:.1f} TFLOP/s incl. host read-back), "
          f"max err {err:.2e} of max |want|")
    check(got.shape == (n, n), f"m_mult shape {got.shape}")
    check(err <= 1e-2, f"m_mult error {err:.3e} > 1e-2")


def phase_wah(system, n: int = 1 << 24, card: int = 64) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.indexing import (build_wah_index, decode_wah_bitmap,
                                wah_index_pipeline_actors)
    from repro.kernels import ops

    values = jax.random.randint(jax.random.key(2), (n,), 0, card,
                                jnp.int32).astype(jnp.uint32)
    expect_kernels(pallas_kernels(build_wah_index, values, card),
                   {"pallas_radix_pass", "pallas_wah_interleave",
                    "pallas_local_compact"}, "build_wah_index")
    got, t1 = timed(build_wah_index, values, card)
    got, t2 = timed(build_wah_index, values, card)
    want, t_ref = timed(lambda v: build_wah_index(v, card, impl="ref"), values)
    words, n_words, starts, counts = (np.asarray(x) for x in got)
    print(f"  build_wah_index n=2^{n.bit_length() - 1} card {card}: "
          f"{int(n_words)} words; first {t1:.3f}s, second {t2:.3f}s "
          f"({n / t2 / 1e6:.1f} Mvals/s); impl=ref {t_ref:.3f}s")
    for name, g, w in zip(("words", "n_words", "starts", "counts"),
                          (words, n_words, starts, counts), want):
        check(np.array_equal(g, np.asarray(w)),
              f"build_wah_index {name} differs from impl='ref'")
    host_values = np.asarray(values)
    decoded = (0, card // 2, card - 1)
    for v in decoded:
        pos = decode_wah_bitmap(words[:int(n_words)], int(starts[v]),
                                int(counts[v]))
        check(np.array_equal(pos, np.flatnonzero(host_values == v)),
              f"decoded bitmap of value {v} differs from the input")
    print(f"  decoded bitmaps of values {decoded} match the input positions")

    k = n
    kf, kg, kl = jax.random.split(jax.random.key(3), 3)
    flag = jax.random.bernoulli(kf, 0.5, (k,)).astype(jnp.uint32)
    fills = flag * ((jnp.uint32(1) << 31)
                    | jax.random.randint(kg, (k,), 1, 99).astype(jnp.uint32))
    lits = jax.random.randint(kl, (k,), 1, 2 ** 31 - 1).astype(jnp.uint32)
    pipe = wah_index_pipeline_actors(system, k, mode="staged")
    (out, total), t1 = timed(pipe.ask, fills, lits)
    (out, total), t2 = timed(pipe.ask, fills, lits)
    want_out, want_total = ops.stream_compact(
        ops.wah_interleave(fills, lits, impl="ref"), impl="ref")
    print(f"  fuseFillsLiterals staged pipeline k=2^{k.bit_length() - 1}: "
          f"{int(total)} words; first {t1:.3f}s, second {t2:.3f}s")
    check(int(total) == int(want_total), "pipeline word count differs")
    check(np.array_equal(np.asarray(out), np.asarray(want_out)),
          "pipeline words differ from the oracle")


def phase_mandelbrot(system, height: int = 1080, width: int = 1920,
                     max_iter: int = 256) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.core import NDRange, Out, dim_vec, kernel
    from repro.kernels import ops

    view = dict(height=height, width=width, max_iter=max_iter,
                re_min=-2.0, re_max=0.6, im_min=-1.2, im_max=1.2)

    @kernel(Out(jnp.int32, shape=(height, width)),
            nd_range=NDRange(dim_vec(width, height)), name="mandelbrot")
    def frame():
        return ops.mandelbrot(**view)

    expect_kernels(pallas_kernels(frame.fn), {"pallas_mandelbrot"},
                   "mandelbrot")
    worker = system.spawn(frame)
    got, t1 = timed(worker.ask)
    got, t2 = timed(worker.ask)
    want = np.asarray(ops.mandelbrot(impl="ref", **view))
    same = float(np.mean(got == want))
    diff = np.abs(got.astype(np.int64) - want)
    print(f"  mandelbrot {width}x{height}, {max_iter} iterations: first "
          f"{t1:.3f}s, second {t2:.3f}s; {same:.4%} of pixels equal to the "
          f"oracle, largest difference {int(diff.max())} iterations")
    check(got.shape == (height, width), f"mandelbrot shape {got.shape}")
    check(same >= 0.98, f"only {same:.4%} of pixels match the oracle")


def decode_logits_error(model, params, batch: int = 2, steps: int = 3):
    """Relative L2 error of one cached decode step's logits against an f32
    forward pass over the same tokens."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.dist.step import build_serve_step
    from repro.models import transformer

    cfg = model.cfg
    tokens = jax.random.randint(jax.random.key(4), (batch, steps + 1), 0,
                                cfg.vocab_size, jnp.int32)
    step = jax.jit(build_serve_step(model))
    cache = model.init_cache(batch, steps + 1)
    for t in range(steps + 1):
        _, logits, cache = step(params, cache, tokens[:, t:t + 1])
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, t: transformer.forward(p, cfg32, t))(
            params, tokens)
    return rel_l2(logits[:, 0], want[:, -1])


def phase_serve(requests: int = 16, batch: int = 8, steps: int = 32) -> None:
    import numpy as np
    from repro.launch import serve

    out = serve.run(["--arch", ARCH, "--full", "--requests", str(requests),
                     "--batch", str(batch), "--steps", str(steps)])
    cfg, results = out["cfg"], out["results"]
    check(len(results) == requests,
          f"{len(results)} of {requests} requests answered")
    for i, r in enumerate(results):
        toks = np.asarray(r.tokens)
        check(toks.shape == (steps,),
              f"request {i} returned {toks.shape[0]} tokens, not {steps}")
        check(((toks >= 0) & (toks < cfg.vocab_size)).all(),
              f"request {i} has tokens outside the vocabulary")
    print(f"  {requests} requests x {steps} tokens in {out['seconds']:.2f}s "
          f"({requests * steps / out['seconds']:.1f} tokens/s, compile "
          "included)")
    err = decode_logits_error(out["model"], out["params"])
    print(f"  decode-step logits vs f32 forward: relative L2 error "
          f"{err:.3e} (limit {LOGIT_RTOL})")
    check(err <= LOGIT_RTOL, f"decode logits error {err:.3e} > {LOGIT_RTOL}")


def phase_stages(system, n_stages: int, batch: int = 2, seq: int = 64,
                 microbatches: int = 4) -> None:
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.core.memref import DeviceRef
    from repro.dist.pipeline import PipelineRunner, make_layer_stage_actors
    from repro.models import Model

    model = Model(configs.get_config(ARCH))
    params = model.init(jax.random.key(0))
    stages = make_layer_stage_actors(system, model, params, n_stages=n_stages)
    mbs = [jax.random.randint(jax.random.key(10 + i), (batch, seq), 0,
                              model.cfg.vocab_size, jnp.int32)
           for i in range(microbatches)]

    # one microbatch stage by stage: where did each stage's output land?
    x, ran_on = mbs[0], []
    for si, st in enumerate(stages):
        y = st.ask(x)
        arr = y.array if isinstance(y, DeviceRef) else y
        (dev,) = arr.devices()
        ran_on.append(dev)
        print(f"  stage {si}: output on {dev} ({dev.device_kind})")
        x = y
    check(len(set(ran_on)) == n_stages,
          f"stages share devices: {[str(d) for d in ran_on]}")

    runner = PipelineRunner(system, stages, depth=n_stages)
    outs, t1 = timed(runner.run, mbs)
    outs, t2 = timed(runner.run, mbs)
    fwd = jax.jit(model.forward)
    worst = 0.0
    for mb, got in zip(mbs, outs):
        want, _ = fwd(params, {"tokens": mb})
        worst = max(worst, rel_l2(got, want))
    toks = batch * seq * microbatches
    print(f"  PipelineRunner: {microbatches} microbatches of {batch}x{seq} "
          f"through {n_stages} stages: first {t1:.2f}s, second {t2:.2f}s "
          f"({toks / t2:.0f} tokens/s); logits vs one-chip model.forward: "
          f"worst relative L2 error {worst:.3e} (limit {LOGIT_RTOL})")
    check(worst <= LOGIT_RTOL,
          f"staged logits error {worst:.3e} > {LOGIT_RTOL}")


# ----------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel actors and serving on one chip; 4: only "
                         "the four-stage layer pipeline, one stage per chip")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found; run from a checkout "
              "of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev0.platform}); not running "
              "on the CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"devices: {len(devices)} x {dev0.device_kind}; compile cache "
          f"{cache_dir}", flush=True)

    from repro.core import ActorSystem

    log = CompileLog()
    log.install()
    t0 = time.perf_counter()
    try:
        with ActorSystem(name="chip-smoke") as system:
            if args.chips == 1:
                with phase("m_mult", log):
                    phase_matmul(system)
                with phase("wah", log):
                    phase_wah(system)
                with phase("mandelbrot", log):
                    phase_mandelbrot(system)
            else:
                with phase("stages", log):
                    phase_stages(system, n_stages=args.chips)
        if args.chips == 1:
            with phase("serve", log):
                phase_serve()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total wall {time.perf_counter() - t0:.2f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
