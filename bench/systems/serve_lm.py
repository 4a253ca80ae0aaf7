"""System under test: a dense decoder LM served by ``repro.serve.ServeEngine``.

The set-up mirrors the engine mode of ``repro.launch.serve``: the jitted
batched step takes the weights as an argument, the per-request cache is
``Model.init_cache(1, capacity)``, and ``combine``/``split`` concatenate
and slice each cache leaf on its batch axis (found by comparing the
abstract caches of batch 1 and 2). Gang scheduling (``allow_join=False``):
the model's cache carries one decode position for the whole batch.

The weights are the benchmark's, made from the seed on the device in one
jitted call, in the dtype the configuration states, and handed to the
engine in the program's parameter layout. The reference reads the same
arrays (``bench/reference/dense_lm.py``).

Traffic keys read here: ``max_batch``, ``cache_capacity``, ``workers``,
``check_requests``; request fields ``prompt`` (a token id) and
``output_tokens``.
"""
from __future__ import annotations

import time
from concurrent.futures import Future
from typing import List

import numpy as np

from bench import load_module
from bench.generator import no_span, rng_for


def model_config(config: dict):
    from repro.configs import ModelConfig

    if config["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden_act {config['hidden_act']!r}")
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        head_dim=int(config["head_dim"]),
        qk_norm=bool(config["qk_norm"]), mlp="swiglu", norm="rmsnorm",
        attn_bias=bool(config["attention_bias"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"])


def make_weights(config: dict, seed: int):
    """All weights, on the device, from the seed, in one jitted call."""
    import jax
    import jax.numpy as jnp

    d = int(config["hidden_size"])
    f = int(config["intermediate_size"])
    v = int(config["vocab_size"])
    n = int(config["num_hidden_layers"])
    h = int(config["num_attention_heads"])
    hkv = int(config["num_key_value_heads"])
    hd = int(config["head_dim"])
    dt = jnp.dtype(config["torch_dtype"])
    shapes = {
        "embed": ((v, d), config["initializer_range"]),
        "final_norm": ((d,), None),
        "attn_norm": ((n, d), None), "mlp_norm": ((n, d), None),
        "q_norm": ((n, hd), None), "k_norm": ((n, hd), None),
        "wq": ((n, d, h * hd), d ** -0.5), "wk": ((n, d, hkv * hd), d ** -0.5),
        "wv": ((n, d, hkv * hd), d ** -0.5),
        "wo": ((n, h * hd, d), (h * hd) ** -0.5),
        "w_gate": ((n, d, f), d ** -0.5), "w_up": ((n, d, f), d ** -0.5),
        "w_down": ((n, f, d), f ** -0.5),
    }
    words = np.random.SeedSequence(seed).generate_state(2)

    @jax.jit
    def make(words):
        # the seed is an argument, so every seed runs the one cached program
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        out = {}
        for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            # norm scales near 1, so that the reference checks they apply
            out[name] = (1.0 + 0.1 * z if std is None else std * z).astype(dt)
        return out

    return make(jnp.asarray(words & 0x7FFFFFFF, jnp.int32))


def program_params(w: dict) -> dict:
    """The same arrays in ``repro.models.transformer``'s layout."""
    block = {"norm1": {"scale": w["attn_norm"]},
             "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                      "wo": w["wo"], "q_norm": {"scale": w["q_norm"]},
                      "k_norm": {"scale": w["k_norm"]}},
             "norm2": {"scale": w["mlp_norm"]},
             "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                     "w_out": w["w_down"]}}
    return {"embed": w["embed"], "groups": [[block]],
            "final_norm": {"scale": w["final_norm"]}}


class System:
    """One engine, warmed up on every shape the cell's traffic uses."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 span=no_span):
        import jax
        import jax.numpy as jnp
        from repro.core import ActorSystem
        from repro.dist.step import build_serve_step
        from repro.models import Model
        from repro.serve import ServeEngine

        self.config, self.traffic, self.seed = config, traffic, seed
        self.sizes = config
        self.capacity = int(traffic["cache_capacity"])
        self.max_batch = int(traffic["max_batch"])
        cfg = model_config(config)
        model = Model(cfg)
        self.weights = make_weights(config, seed)
        params = program_params(self.weights)
        want = jax.eval_shape(model.init, jax.random.key(0))
        got = jax.eval_shape(lambda: params)
        if jax.tree_util.tree_structure(want) != \
                jax.tree_util.tree_structure(got) or any(
                    (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                        jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got))):
            raise ValueError("the program's parameter layout changed; "
                             "update program_params")
        serve_step = build_serve_step(model)

        @jax.jit
        def batched_step(params, cache, tokens):
            nxt, _, cache = serve_step(params, cache, tokens[:, None])
            return nxt[:, 0], cache

        #: (host time, batch, decode position) of every step dispatched
        self.steps: List[tuple] = []
        self._pos = 0
        self._new_gang = False
        capacity = self.capacity

        def step_fn(cache, tokens):
            if self._new_gang:
                self._pos, self._new_gang = 0, False
            self.steps.append((time.monotonic(), int(tokens.shape[0]),
                               self._pos))
            self._pos += 1
            with span("bench.step"):
                return batched_step(params, cache, tokens)

        def init_fn(prompt):
            self._new_gang = True
            return model.init_cache(1, capacity), int(prompt)

        s1 = jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: model.init_cache(1, capacity)))
        s2 = jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: model.init_cache(2, capacity)))
        axes = [next((ax for ax, (a, b) in enumerate(zip(x.shape, y.shape))
                      if a != b), None) for x, y in zip(s1, s2)]

        def combine(leaves, i):
            with span("bench.combine"):
                ax = axes[i]
                return leaves[0] if ax is None else jnp.concatenate(leaves,
                                                                    axis=ax)

        def split(leaf, b, i):
            with span("bench.split"):
                ax = axes[i]
                if ax is None:
                    return leaf
                return jax.lax.slice_in_dim(leaf, b, b + 1, axis=ax)

        self.actors = ActorSystem(name="bench-serve")
        self.engine = ServeEngine(
            self.actors, step_fn, init_fn,
            n_workers=int(traffic.get("workers", 2)),
            max_batch=self.max_batch, allow_join=False, combine=combine,
            split=split, jit_step=False)
        self._warm_up()

    def _warm_up(self) -> None:
        """One gang of ``max_batch`` requests of 1..max_batch tokens: the
        step runs at every batch size from ``max_batch`` down to 1 and the
        split at every index, so nothing compiles in the window."""
        futs = [self.engine.submit(0, max_new_tokens=n)
                for n in range(1, self.max_batch + 1)]
        self.engine.start()
        for f in futs:
            f.result(timeout=1200)
        self.steps.clear()

    # -- driving ---------------------------------------------------------
    def submit(self, req: dict) -> Future:
        out: Future = Future()
        t_sub = time.monotonic()
        fut = self.engine.submit(int(req["prompt"]),
                                 max_new_tokens=int(req["output_tokens"]))

        def done(f):
            try:
                r = f.result()
                out.set_result({"first": t_sub + r.ttft_s,
                                "end": t_sub + r.latency_s,
                                "tokens": [int(t) for t in r.tokens]})
            except BaseException as exc:
                out.set_exception(exc)

        fut.add_done_callback(done)
        return out

    def issue(self, req: dict) -> dict:
        return self.submit(req).result(timeout=600)

    def counters(self) -> dict:
        s = self.engine.stats()
        return {k: s[k] for k in ("steps", "batch_slots", "tokens",
                                  "completed", "failed")} | {
            "host_steps": len(self.steps)}

    # -- after the window --------------------------------------------------
    def release(self) -> None:
        """Stop the engine and free its caches; the weights stay for the
        reference."""
        self.engine.stop()
        self.actors.shutdown()

    def check(self, records: List[dict]) -> List[tuple]:
        """Widest gap by which a served token's reference logit lies below
        the reference's best, over a sample of finished requests drawn
        from the seed, the longest among them."""
        import jax.numpy as jnp

        ref = load_module("reference", self.config["reference"])
        done = [r for r in records if r.get("ok")]
        short = sum(1 for r in done
                    if len(r["tokens"]) != r["req"]["output_tokens"])
        checks = [("short_requests", short, 0)]
        if not done:
            return checks + [("max_logit_gap", float("inf"),
                              self.config["limits"]["max_logit_gap"])]
        sample = sample_requests(done, int(self.traffic["check_requests"]),
                                 self.seed)
        tokens, targets, valid = teacher_forced(sample, self.capacity - 1)
        gaps = ref.logit_gaps(self.weights, self.sizes, jnp.asarray(tokens),
                              jnp.asarray(targets), jnp.asarray(valid))
        gap = float(np.max(np.asarray(gaps)))
        return checks + [("max_logit_gap", gap,
                          self.config["limits"]["max_logit_gap"]),
                         ("checked_tokens", int(valid.sum()), None)]

    def control(self, records: List[dict]) -> List[tuple]:
        """The control: the reference at float8 in the program's place, on
        the same sample, prompts and served tokens. At each position the
        gap is that of the token the float8 forward puts first."""
        import jax.numpy as jnp

        ref = load_module("reference", self.config["reference"])
        done = [r for r in records if r.get("ok")]
        sample = sample_requests(done, int(self.traffic["check_requests"]),
                                 self.seed)
        tokens, targets, valid = teacher_forced(sample, self.capacity - 1)
        gaps = ref.logit_gaps(self.weights, self.sizes, jnp.asarray(tokens),
                              jnp.asarray(targets), jnp.asarray(valid),
                              quant="fp8")
        return [("max_logit_gap", float(np.max(np.asarray(gaps))),
                 self.config["limits"]["max_logit_gap"])]

    def close(self) -> None:
        self.weights = None


def sample_requests(done: List[dict], k: int, seed: int) -> List[dict]:
    longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
    rest = [i for i in range(len(done)) if i != longest]
    pick = rng_for(seed, "check").permutation(rest)[:max(0, k - 1)]
    return [done[longest]] + [done[i] for i in sorted(pick)]


def teacher_forced(sample: List[dict], length: int):
    """Inputs ``[prompt, t1, .., t_{n-1}]`` and targets ``[t1, .., tn]`` of
    each request, padded to ``length``."""
    b = len(sample)
    tokens = np.zeros((b, length), np.int32)
    targets = np.zeros((b, length), np.int32)
    valid = np.zeros((b, length), bool)
    for i, r in enumerate(sample):
        served = r["tokens"]
        seq = [r["req"]["prompt"]] + served[:-1]
        tokens[i, :len(seq)] = seq
        targets[i, :len(served)] = served
        valid[i, :len(served)] = True
    return tokens, targets, valid
