"""System under test: a DeepSeek-V3 decoder (latent attention, a leading
dense layer, sigmoid-routed experts with shared experts), one chip's share
of its experts, served by ``repro.serve.ServeEngine``.

Set up like ``bench/systems/serve_lm.py``: the jitted batched step takes
the weights as an argument, the per-request cache is
``Model.init_cache(1, capacity)`` (the latent cache of every layer),
``combine``/``split`` concatenate and slice each cache leaf on its batch
axis, and the engine gang-schedules (``allow_join=False``). Three
differences:

* the step programs of every batch size are compiled before the warm-up,
  several at a time, and the step calls them directly;
* ``split`` is one program per batch size and leaf, with the request's
  index an argument, so the warm-up compiles no program per index;
* the step adds the program's expert counters (``cache["moe_counts"]``:
  choices routed to held experts, held experts chosen, summed over the
  step's expert layers) into a total on the device; ``counters()`` reads it
  at the window's ends, and no step waits for it.

The weights are the benchmark's, made from the seed on the device in one
jitted call, in the dtype the configuration states, and handed to the
engine in the program's parameter layout. The reference reads the same
arrays (``bench/reference/mla_moe_lm.py``).

Traffic keys read here: ``max_batch``, ``cache_capacity``, ``workers``,
``check_requests``; request fields ``prompt`` (a token id) and
``output_tokens``.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List

import numpy as np

from bench import load_module
from bench.generator import no_span
from bench.systems import serve_lm
from bench.systems.serve_lm import sample_requests, teacher_forced

#: what the configuration file must state for this adapter's equations
FIXED = {"hidden_act": "silu", "q_lora_rank": None, "n_group": 1,
         "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
         "norm_topk_prob": True, "moe_layer_freq": 1, "attention_bias": False,
         "tie_word_embeddings": False, "rope_scaling": None}


def held_range(config: dict) -> tuple:
    lo = int(config["held_experts_from"])
    return lo, lo + int(config["n_routed_experts"])


def model_config(config: dict):
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

    for key, want in FIXED.items():
        if config.get(key) != want:
            raise ValueError(f"unsupported {key} {config.get(key)!r}")
    return ModelConfig(
        name=config["name"], family="moe",
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]), mlp="swiglu", norm="rmsnorm",
        rope_theta=float(config["rope_theta"]), rope_interleave=True,
        norm_eps=float(config["rms_norm_eps"]),
        mla=MLAConfig(kv_lora_rank=int(config["kv_lora_rank"]),
                      qk_nope_head_dim=int(config["qk_nope_head_dim"]),
                      qk_rope_head_dim=int(config["qk_rope_head_dim"]),
                      v_head_dim=int(config["v_head_dim"])),
        moe=MoEConfig(n_experts=int(config["router_experts"]),
                      top_k=int(config["num_experts_per_tok"]),
                      d_expert=int(config["moe_intermediate_size"]),
                      n_shared=int(config["n_shared_experts"]),
                      router="sigmoid",
                      routed_scaling=float(config["routed_scaling_factor"]),
                      first_dense=int(config["first_k_dense_replace"]),
                      held=held_range(config)),
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"])


def weight_shapes(config: dict) -> dict:
    """name → (shape, std; None: a norm scale near 1; "bias": the
    correction bias), stacked over the layers of each group."""
    d = int(config["hidden_size"])
    v = int(config["vocab_size"])
    h = int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, r = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    nd = int(config["first_k_dense_replace"])
    nm = int(config["num_hidden_layers"]) - nd
    f = int(config["intermediate_size"])
    fe = int(config["moe_intermediate_size"])
    fs = fe * int(config["n_shared_experts"])
    ne, e_all = int(config["n_routed_experts"]), int(config["router_experts"])
    out = {"embed": ((v, d), float(config["initializer_range"])),
           "lm_head": ((d, v), d ** -0.5), "final_norm": ((d,), None)}
    for g, n in (("d", nd), ("m", nm)):
        out.update({
            f"{g}_attn_norm": ((n, d), None), f"{g}_mlp_norm": ((n, d), None),
            f"{g}_wq": ((n, d, h * (dn + dr)), d ** -0.5),
            f"{g}_wkv_a": ((n, d, r + dr), d ** -0.5),
            f"{g}_kv_norm": ((n, r), None),
            f"{g}_wkv_b": ((n, r, h * (dn + dv)), r ** -0.5),
            f"{g}_wo": ((n, h * dv, d), (h * dv) ** -0.5)})
    out.update({
        "d_w_gate": ((nd, d, f), d ** -0.5), "d_w_up": ((nd, d, f), d ** -0.5),
        "d_w_down": ((nd, f, d), f ** -0.5),
        "m_router": ((nm, d, e_all), d ** -0.5),
        "m_router_bias": ((nm, e_all), "bias"),
        "m_w_gate": ((nm, ne, d, fe), d ** -0.5),
        "m_w_up": ((nm, ne, d, fe), d ** -0.5),
        "m_w_down": ((nm, ne, fe, d), fe ** -0.5),
        "m_s_gate": ((nm, d, fs), d ** -0.5), "m_s_up": ((nm, d, fs), d ** -0.5),
        "m_s_down": ((nm, fs, d), fs ** -0.5)})
    return out


#: standard deviation of the correction bias (configuration file, assumed)
BIAS_STD = 0.05


def make_weights(config: dict, seed: int):
    """All weights, on the device, from the seed, in one jitted call; the
    layer-stacked ones a layer at a time, so that no float32 copy of a
    whole stack is ever live."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(config["torch_dtype"])
    shapes = weight_shapes(config)
    words = np.random.SeedSequence(seed).generate_state(2)

    def draw(key, shape, std):
        z = jax.random.normal(key, shape, jnp.float32)
        if std == "bias":
            return BIAS_STD * z
        # norm scales near 1, so that the reference checks they apply
        return (1.0 + 0.1 * z if std is None else std * z).astype(dt)

    @jax.jit
    def make(words):
        # the seed is an argument, so every seed runs the one cached program
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        out = {}
        for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name[:2] in ("d_", "m_"):
                out[name] = jax.lax.map(
                    lambda j, k=k, shape=shape, std=std: draw(
                        jax.random.fold_in(k, j), shape[1:], std),
                    jnp.arange(shape[0]))
            else:
                out[name] = draw(k, shape, std)
        return out

    return make(jnp.asarray(words & 0x7FFFFFFF, jnp.int32))


def program_params(w: dict) -> dict:
    """The same arrays in ``repro.models.transformer``'s layout."""
    def block(g: str) -> dict:
        return {"norm1": {"scale": w[f"{g}_attn_norm"]},
                "attn": {"wq": w[f"{g}_wq"], "wkv_a": w[f"{g}_wkv_a"],
                         "kv_norm": {"scale": w[f"{g}_kv_norm"]},
                         "wkv_b": w[f"{g}_wkv_b"], "wo": w[f"{g}_wo"]},
                "norm2": {"scale": w[f"{g}_mlp_norm"]}}

    dense = dict(block("d"), mlp={"w_gate": w["d_w_gate"],
                                  "w_up": w["d_w_up"],
                                  "w_out": w["d_w_down"]})
    moe = dict(block("m"), moe={
        "router": w["m_router"], "router_bias": w["m_router_bias"],
        "w_gate": w["m_w_gate"], "w_up": w["m_w_up"], "w_out": w["m_w_down"],
        "shared": {"w_gate": w["m_s_gate"], "w_up": w["m_s_up"],
                   "w_out": w["m_s_down"]}})
    return {"embed": w["embed"], "groups": [[dense], [moe]],
            "final_norm": {"scale": w["final_norm"]}, "head": w["lm_head"]}


class System(serve_lm.System):
    """One engine, every step program compiled and every shape of the
    cell's traffic warmed up; driving, the warm-up and the release as in
    ``serve_lm.System``."""

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
        want = jax.eval_shape(model.init, jax.random.key(0))
        self.weights = make_weights(config, seed)
        params = program_params(self.weights)
        got = jax.eval_shape(lambda: params)
        if jax.tree_util.tree_structure(want) != \
                jax.tree_util.tree_structure(got) or any(
                    (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                        jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got))):
            raise ValueError("the program's parameter layout changed; "
                             "update program_params")
        serve_step = build_serve_step(model)
        capacity = self.capacity

        @jax.jit
        def batched_step(params, cache, tokens, totals):
            nxt, _, cache = serve_step(params, cache, tokens[:, None])
            return nxt[:, 0], cache, totals + cache["moe_counts"]

        # on the engine's device, committed: so are the step's outputs, as
        # the fresh caches the engine places there, and combining fresh and
        # carried caches runs the same programs
        on_device = jax.sharding.SingleDeviceSharding(jax.devices()[0])

        def lower(b):
            cache = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=on_device),
                jax.eval_shape(lambda: model.init_cache(b, capacity)))
            tokens = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=on_device)
            return b, batched_step.lower(params, cache, tokens,
                                         self._totals).compile()

        #: running totals of the expert counters, on the device
        self._totals = jnp.zeros((2,), jnp.int32)
        with ThreadPoolExecutor(max(1, min(8, os.cpu_count() or 1))) as ex:
            compiled = dict(ex.map(lower, range(self.max_batch, 0, -1)))

        #: (host time, batch, decode position) of every step dispatched
        self.steps: List[tuple] = []
        self._pos = 0
        self._new_gang = False

        def step_fn(cache, tokens):
            if self._new_gang:
                self._pos, self._new_gang = 0, False
            self.steps.append((time.monotonic(), int(tokens.shape[0]),
                               self._pos))
            self._pos += 1
            with span("bench.step"):
                nxt, cache, self._totals = compiled[int(tokens.shape[0])](
                    params, cache, tokens, self._totals)
                return nxt, cache

        def init_fn(prompt):
            self._new_gang = True
            return model.init_cache(1, capacity), int(prompt)

        s1 = jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: model.init_cache(1, capacity)))
        s2 = jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: model.init_cache(2, capacity)))
        axes = [next((ax for ax, (a, b) in enumerate(zip(x.shape, y.shape))
                      if a != b), None) for x, y in zip(s1, s2)]

        # ``leaf[b:b+1]`` on the leaf's batch axis: one program per leaf
        # shape, the index an argument
        take = jax.jit(
            lambda leaf, b, ax: jax.lax.dynamic_slice_in_dim(leaf, b, 1, ax),
            static_argnums=2)

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
                return take(leaf, np.int32(b), ax)

        self.actors = ActorSystem(name="bench-serve")
        self.engine = ServeEngine(
            self.actors, step_fn, init_fn,
            n_workers=int(traffic.get("workers", 2)),
            max_batch=self.max_batch, allow_join=False, combine=combine,
            split=split, jit_step=False)
        self._warm_up()

    def close(self) -> None:
        """Drop the weights and the engine that holds them in its step.
        On a 16 GB chip a second set-up in the same process still runs out
        of device memory: read the control one seed a process."""
        super().close()
        self.engine = self.actors = None

    def counters(self) -> dict:
        held, touched = (int(v) for v in np.asarray(self._totals))
        return super().counters() | {"held_choices": held,
                                     "touched_experts": touched}

    def _sample(self, records: List[dict]):
        """The checked requests, teacher-forced: (tokens, targets, valid)."""
        import jax.numpy as jnp

        done = [r for r in records if r.get("ok")]
        sample = sample_requests(done, int(self.traffic["check_requests"]),
                                 self.seed)
        return [jnp.asarray(a) for a in teacher_forced(sample,
                                                       self.capacity - 1)]

    def check(self, records: List[dict]) -> List[tuple]:
        """Over a sample of finished requests drawn from the seed, the
        longest among them, teacher-forced through the reference: the gaps
        by which a served token's reference logit lies below the
        reference's best (``gap_stats``), each checked where the
        configuration's ``limits`` name it."""
        limits = self.config["limits"]
        done = [r for r in records if r.get("ok")]
        short = sum(1 for r in done
                    if len(r["tokens"]) != r["req"]["output_tokens"])
        checks = [("short_requests", short, 0)]
        if not done:
            return checks + [(n, float("inf"), lim)
                             for n, lim in limits.items()]
        ref = load_module("reference", self.config["reference"])
        tokens, targets, valid = self._sample(records)
        gaps, = ref.logit_gaps(self.weights, self.sizes, tokens, targets,
                               valid)
        return checks + [(n, v, limits.get(n))
                         for n, v in gap_stats(gaps, valid).items()] + [
            ("checked_tokens", int(valid.sum()), None)]

    def control(self, records: List[dict]) -> List[tuple]:
        """The control: the reference at float8 in the program's place, on
        the same sample, prompts and served tokens, against the same
        limits; at each position the gap is that of the token the float8
        forward puts first. Also, unchecked, the witness ``bf16.*``: the
        same with the reference rounded to bfloat16, what that rounding
        alone gives."""
        limits = self.config["limits"]
        ref = load_module("reference", self.config["reference"])
        tokens, targets, valid = self._sample(records)
        _, fp8, bf16 = ref.logit_gaps(self.weights, self.sizes, tokens,
                                      targets, valid, quants=("fp8", "bf16"))
        return [(n, v, limits.get(n))
                for n, v in gap_stats(fp8, valid).items()] + [
            ("bf16." + n, v, None) for n, v in gap_stats(bf16, valid).items()]


def gap_stats(gaps, valid) -> dict:
    """Of the per-position logit gaps at the valid positions: the widest,
    the mean, and the share of positions whose token is not the
    reference's first (gap above 0)."""
    g = np.asarray(gaps, np.float64)[np.asarray(valid, bool)]
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "token_mismatch_share": float(np.mean(g > 0))}
