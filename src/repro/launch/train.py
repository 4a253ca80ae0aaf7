"""Production training launcher.

Runs any arch's smoke config end to end on the CPU or one chip; ``--full``
takes the published config. The pod-scale plan from ``dryrun_lib.plan_for``
is exercised by ``dryrun.py``.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --steps 100 --batch 8 --seq 64 [--smoke/--full] [--ckpt DIR]
"""
from __future__ import annotations

import argparse
import os
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--full", action="store_true",
                    help="use the full published config instead of the "
                         "smoke one; its train state (params, grads, AdamW "
                         "moments) must fit the devices' memory")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    import jax
    from repro import configs
    from repro.checkpoint import checkpoint as ckpt
    from repro.data import Prefetcher, SyntheticLM
    from repro.dist import step as step_mod
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import Model
    from repro.optim import AdamWConfig, schedule

    enable_compile_cache()
    cfg = (configs.get_config if args.full else configs.get_smoke_config)(
        args.arch)
    model = Model(cfg)
    ocfg = AdamWConfig(lr=args.lr)
    data = SyntheticLM(cfg, batch=args.batch, seq=args.seq, seed=0)
    sched = schedule.warmup_cosine(max(args.steps // 10, 1), args.steps)
    train_step = jax.jit(step_mod.build_train_step(
        model, ocfg, grad_accum=args.grad_accum, lr_schedule=sched))

    start_step = 0
    state = step_mod.init_train_state(model, jax.random.key(0), ocfg)
    if args.ckpt and ckpt.latest_step(args.ckpt) is not None:
        state, manifest = ckpt.restore(args.ckpt, target=state)
        state = jax.tree.map(jax.numpy.asarray, state)
        start_step = manifest["step"]
        print(f"restored step {start_step} from {args.ckpt}")

    pf = Prefetcher(data, depth=2, start_step=start_step)
    t0 = time.perf_counter()
    try:
        for i in range(start_step, args.steps):
            step_idx, batch = pf.next()
            assert step_idx == i
            state, metrics = train_step(
                state, {k: jax.numpy.asarray(v) for k, v in batch.items()})
            if (i + 1) % args.log_every == 0:
                tok_s = ((i + 1 - start_step) * args.batch * args.seq /
                         (time.perf_counter() - t0))
                print(f"step {i + 1:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"tok/s={tok_s:,.0f}", flush=True)
            if args.ckpt and (i + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt, i + 1, state)
    finally:
        pf.close()
    if args.ckpt:
        ckpt.save(args.ckpt, args.steps, state)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
