"""Serving launcher: a thin CLI over :class:`repro.serve.ServeEngine`.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --requests 32 --batch 8 --steps 64

Each request decodes ``--steps`` greedy tokens against its own
device-resident cache; the engine batches requests (gang-scheduled — the
model cache carries a batch-uniform decode position, so mid-batch joins
are disabled) and reports per-request p50/p95/p99 latency plus the
DeviceRef traffic counters. ``--sync`` keeps the legacy single-process
loop (also the only path for encoder–decoder models, whose cache needs
per-request encoder frames).
"""
from __future__ import annotations

import argparse
import time

__all__ = ["main", "run", "check_cache_capacity"]


def check_cache_capacity(steps: int, capacity: int) -> int:
    """Guard the decode length against the allocated cache.

    A decode of ``steps`` tokens occupies ``steps + 1`` cache slots (the
    prompt token plus one per generated token); a longer decode would
    silently wrap the ring buffer / overwrite live KV entries instead of
    failing loudly. Returns ``capacity`` so call sites can chain it.
    """
    if steps < 0:
        raise ValueError(f"steps must be >= 0, got {steps}")
    if steps + 1 > capacity:
        raise ValueError(
            f"decode of {steps} steps needs {steps + 1} cache slots but "
            f"only {capacity} were allocated; raise the cache capacity or "
            "shorten the decode")
    return capacity


def _run_engine(args, cfg, model, params, serve_step) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ActorSystem, memory_stats
    from repro.serve import ServeEngine

    capacity = args.steps + 1
    check_cache_capacity(args.steps, capacity)

    # The weights are an argument of the jitted step, not a closure: a
    # closed-over array becomes a constant of the compiled program, which
    # at full width copies gigabytes into every compile (and cache entry).
    # Hence jit_step=False below: the engine must not re-jit this closure.
    @jax.jit
    def batched_step(params, cache, tokens):
        nxt, _, cache = serve_step(params, cache, tokens[:, None])
        return nxt[:, 0], cache

    def step_fn(cache, tokens):
        return batched_step(params, cache, tokens)

    def init_fn(prompt):
        return model.init_cache(1, capacity), int(prompt)

    # Per-leaf batch axis, detected by diffing abstract cache shapes for
    # batch sizes 1 and 2 (layer-scanned leaves carry the layer count on
    # axis 0 and batch on axis 1). Leaves with no batch axis — the scalar
    # decode position — are batch-uniform and shared, which gang
    # scheduling keeps aligned.
    s1 = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: model.init_cache(1, capacity)))
    s2 = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: model.init_cache(2, capacity)))
    batch_axes = [next((ax for ax, (a, b) in enumerate(zip(x.shape, y.shape))
                        if a != b), None)
                  for x, y in zip(s1, s2)]

    def combine(leaves, i):
        ax = batch_axes[i]
        return leaves[0] if ax is None else jnp.concatenate(leaves, axis=ax)

    def split(leaf, b, i):
        ax = batch_axes[i]
        if ax is None:
            return leaf
        return jax.lax.slice_in_dim(leaf, b, b + 1, axis=ax)

    with ActorSystem(name="serve") as system:
        engine = ServeEngine(system, step_fn, init_fn,
                             n_workers=args.workers, max_batch=args.batch,
                             allow_join=False, combine=combine, split=split,
                             jit_step=False)
        t0 = time.perf_counter()
        with engine:
            futs = [engine.submit(0, max_new_tokens=args.steps)
                    for _ in range(args.requests)]
            results = [f.result(timeout=600) for f in futs]
        dt = time.perf_counter() - t0
        stats = engine.stats()
    lat = stats["latency"]
    toks = sum(len(r.tokens) for r in results)
    print(f"{cfg.name}: {args.requests} requests × {args.steps} steps "
          f"(batch {args.batch}, {args.workers} workers) in {dt:.2f}s "
          f"({toks / dt:,.0f} tok/s)")
    print(f"latency p50={lat['p50_ms']:.1f}ms p95={lat['p95_ms']:.1f}ms "
          f"p99={lat['p99_ms']:.1f}ms | engine steps={stats['steps']} "
          f"requeues={stats['requeues']}")
    print("memref:", {k: v for k, v in memory_stats().items()
                      if k in ("transfers", "readbacks", "live_refs")})
    print("sample:", np.asarray(results[0].tokens)[:16].tolist())
    return {"results": results, "seconds": dt, "stats": stats}


def _run_paged(args, cfg) -> dict:
    """Paged-mode demo: disaggregated prefill/decode over a PagePool.

    Runs a single-layer greedy attention decoder at the config's model
    dims (token embedding + q/k/v/o projections) whose KV entries live in
    fixed-size pages: prefill workers write each prompt's pages (identical
    prompts share read-sealed pages through the prefix cache), the decode
    loop gathers pages per batch slot. Ends with a page-pressure report
    from ``DeviceManager.memory_stats()``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ActorSystem, memory_stats
    from repro.serve import PagePool, ServeEngine

    d = int(getattr(cfg, "d_model", 64))
    vocab = int(getattr(cfg, "vocab_size", 997) or 997)
    keys = jax.random.split(jax.random.key(0), 5)
    scale = 1.0 / np.sqrt(d)
    emb = jax.random.normal(keys[0], (vocab, d), jnp.float32) * scale
    wq, wk, wv, wo = (jax.random.normal(k, (d, d), jnp.float32) * scale
                      for k in keys[1:])

    def _attend(q, k, v, lengths):
        # q [B, d]; k/v [B, T, d]; positions >= length are masked out
        T = k.shape[1]
        scores = jnp.einsum("bd,btd->bt", q, k) / np.sqrt(d)
        mask = jnp.arange(T)[None, :] < lengths[:, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bt,btd->bd", att, v)

    def prefill_fn(prompt):
        toks = jnp.asarray(np.asarray(prompt, dtype=np.int64) % vocab)
        x = emb[toks]                       # [T, d]
        entries = {"k": x @ wk, "v": x @ wv}
        q = (x[-1] @ wq)[None, :]
        o = _attend(q, entries["k"][None], entries["v"][None],
                    jnp.asarray([toks.shape[0]]))
        logits = (o @ wo) @ emb.T
        return entries, int(jnp.argmax(logits, axis=-1)[0])

    def step_fn(kv, lengths, tokens):
        x = emb[tokens % vocab]             # [B, d]
        entry = {"k": x @ wk, "v": x @ wv}
        # the incoming token's KV joins the context it attends over
        k = kv["k"].at[jnp.arange(x.shape[0]), lengths].set(entry["k"])
        v = kv["v"].at[jnp.arange(x.shape[0]), lengths].set(entry["v"])
        o = _attend(x @ wq, k, v, lengths + 1)
        logits = (o @ wo) @ emb.T
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), entry

    rng = np.random.default_rng(0)
    # mixed workload with repeats: every third request replays prompt 0,
    # so the pool's prefix cache gets exercised
    base_prompts = [rng.integers(0, vocab, size=l).tolist()
                    for l in (24, 6, 48, 12)]
    prompts = [base_prompts[0] if i % 3 == 0
               else base_prompts[i % len(base_prompts)]
               for i in range(args.requests)]

    with ActorSystem(name="serve-paged") as system:
        manager = system.opencl_manager()
        pool = PagePool.for_entries(prefill_fn(base_prompts[1])[0],
                                    page_tokens=16,
                                    max_pages=args.pages)
        engine = ServeEngine(system, step_fn=step_fn, cache_pool=pool,
                             prefill_fn=prefill_fn,
                             prefill_workers=args.prefill_workers,
                             n_workers=args.workers, max_batch=args.batch)
        t0 = time.perf_counter()
        with engine:
            futs = [engine.submit(p, max_new_tokens=args.steps)
                    for p in prompts]
            results = [f.result(timeout=600) for f in futs]
        dt = time.perf_counter() - t0
        stats = engine.stats()
        pressure = manager.memory_stats()
    lat = stats["latency"]
    toks = sum(len(r.tokens) for r in results)
    print(f"{cfg.name} [paged]: {args.requests} requests × {args.steps} "
          f"steps (batch {args.batch}, {args.workers} decode + "
          f"{args.prefill_workers} prefill workers) in {dt:.2f}s "
          f"({toks / dt:,.0f} tok/s)")
    print(f"latency p50={lat['p50_ms']:.1f}ms p95={lat['p95_ms']:.1f}ms "
          f"p99={lat['p99_ms']:.1f}ms | occupancy={stats['occupancy']:.2f} "
          f"prefills={stats['prefills']} prefix_hits={stats['prefix_hits']}")
    ps = stats["pool"]
    print(f"pool: {ps['pages_live']}/{ps['pages_total']} pages live "
          f"(peak {ps['peak_pages']}), shared={ps['pages_shared']}, "
          f"cow={ps['cow']}, fragmentation={ps['fragmentation']:.2f}")
    for name, dev in pressure.items():
        print(f"device {name}: pages_total={dev['pages_total']} "
              f"pages_free={dev['pages_free']} "
              f"pages_shared={dev['pages_shared']} "
              f"fragmentation={dev['fragmentation']:.2f}")
    print("memref:", {k: v for k, v in memory_stats().items()
                      if k in ("transfers", "readbacks", "live_refs")})
    print("sample:", np.asarray(results[0].tokens)[:16].tolist())
    return {"results": results, "seconds": dt, "stats": stats}


def _run_sync(args, cfg, model, params, serve_step) -> dict:
    import jax.numpy as jnp
    import numpy as np

    capacity = args.steps + 1
    check_cache_capacity(args.steps, capacity)
    if cfg.family == "encdec":
        rng = np.random.default_rng(0)
        frames = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.encdec.n_frames, cfg.d_model)),
            jnp.dtype(cfg.compute_dtype))
        cache = model.init_cache(args.batch, capacity, params=params,
                                 frames=frames)
    else:
        cache = model.init_cache(args.batch, capacity)

    toks = jnp.zeros((args.batch, 1), jnp.int32)
    outs = []
    t0 = time.perf_counter()
    for _ in range(args.steps):
        toks, _, cache = serve_step(params, cache, toks)
        outs.append(np.asarray(toks))
    dt = time.perf_counter() - t0
    print(f"{cfg.name}: {args.steps} steps × {args.batch} requests "
          f"in {dt:.2f}s ({args.steps * args.batch / dt:,.0f} tok/s)")
    tokens = np.concatenate(outs, axis=1)
    print("sample:", tokens[0, :16].tolist())
    return {"tokens": tokens, "seconds": dt}


def run(argv=None) -> dict:
    """Parse ``argv``, serve, and return what was served.

    The dict always holds ``cfg``; engine and paged modes add the request
    ``results`` and engine ``stats``, sync mode the ``[batch, steps]``
    ``tokens``; every mode but paged adds ``model`` and ``params``."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=32,
                    help="engine mode: how many requests to serve")
    ap.add_argument("--batch", type=int, default=8,
                    help="max batch size (sync mode: the static batch)")
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--workers", type=int, default=2,
                    help="engine mode: decode worker replicas")
    ap.add_argument("--sync", action="store_true",
                    help="legacy synchronous loop instead of the engine")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV-cache demo: disaggregated prefill/decode "
                         "over a PagePool (single-layer attention at the "
                         "config's dims)")
    ap.add_argument("--prefill-workers", type=int, default=2,
                    help="paged mode: prefill worker replicas")
    ap.add_argument("--pages", type=int, default=512,
                    help="paged mode: PagePool capacity in pages")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from repro import configs
    from repro.dist import step as step_mod
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import Model

    enable_compile_cache()
    cfg = (configs.get_config if args.full else configs.get_smoke_config)(
        args.arch)
    if args.paged:
        return {"cfg": cfg, **_run_paged(args, cfg)}
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    out = {"cfg": cfg, "model": model, "params": params}

    if args.sync or cfg.family == "encdec":
        serve_step = jax.jit(step_mod.build_serve_step(model),
                             donate_argnums=(1,))
        return {**out, **_run_sync(args, cfg, model, params, serve_step)}
    # engine mode: retries must be able to replay a cache, so no donation
    serve_step = step_mod.build_serve_step(model)
    return {**out, **_run_engine(args, cfg, model, params, serve_step)}


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
