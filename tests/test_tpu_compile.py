"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The TPU compiler ships with jaxlib's TPU library and compiles for a
``v5e:2x2`` topology that is described, not attached. It refuses what
interpret mode accepts (misaligned block shapes, non-integer iotas, loop
carries it cannot lay out) and programs that do not fit the chip, so these
tests guard the kernels and the decode step that ``chip_smoke.py`` runs,
at its sizes. Nothing executes; results are covered by the interpret-mode
tests in ``test_kernels.py``.

The topology is described inside a module-scoped fixture, never at import,
and every test lowers in this process: only one process at a time may load
the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU program written to the persistent cache cannot be read back
    # without a chip; keep the cache out of these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases(s):
    """kernel name → (jitted fn, abstract args), at chip_smoke's sizes."""
    from repro.kernels.flash_attention import pallas_flash_attention
    from repro.kernels.mandelbrot import pallas_mandelbrot
    from repro.kernels.matmul import pallas_matmul
    from repro.kernels.moe_held import pallas_moe_held_experts
    from repro.kernels.radix_sort import pallas_radix_pass
    from repro.kernels.stream_compact import pallas_local_compact
    from repro.kernels.wah import pallas_wah_interleave

    n = 1 << 24
    u32 = jnp.uint32
    bf16 = jnp.bfloat16
    # qwen3-1.7b heads: 16 query heads over 8 KV heads of width 128
    q = _spec(s, (1, 16, 2048, 128), bf16)
    kv = _spec(s, (1, 8, 2048, 128), bf16)
    # Moonlight-16B-A3B's held experts at batch 64: 16 experts of width
    # 1408 over d_model 2048 in each of 26 stacked layers, 64 x 6 routed
    # rows in 16-row groups
    m, nl, e, d, f = 624, 26, 16, 2048, 1408
    return {
        "pallas_moe_held_experts": (
            jax.jit(functools.partial(pallas_moe_held_experts, tf=f)),
            (_spec(s, (m, d), bf16), _spec(s, (m, 1), jnp.float32),
             _spec(s, (e,), jnp.int32), _spec(s, (e,), jnp.int32),
             _spec(s, (nl, e, d, f), bf16), _spec(s, (nl, e, d, f), bf16),
             _spec(s, (nl, e, f, d), bf16), _spec(s, (), jnp.int32))),
        "pallas_matmul": (
            jax.jit(pallas_matmul),
            (_spec(s, (4096, 4096), bf16), _spec(s, (4096, 4096), bf16))),
        "pallas_flash_attention": (
            jax.jit(lambda q, k, v: pallas_flash_attention(q, k, v,
                                                           causal=True)),
            (q, kv, kv)),
        # no input: a dummy argument on the described chip (kept, though
        # unused) is what places the program there
        "pallas_mandelbrot": (
            jax.jit(lambda _: pallas_mandelbrot(
                height=1080, width=1920, max_iter=256, re_min=-2.0,
                re_max=0.6, im_min=-1.2, im_max=1.2), keep_unused=True),
            (_spec(s, (1,), jnp.float32),)),
        "pallas_radix_pass": (
            jax.jit(pallas_radix_pass), (_spec(s, (n,), u32),)),
        # compaction runs over the interleaved fills+literals: 2n words
        "pallas_local_compact": (
            jax.jit(pallas_local_compact), (_spec(s, (2 * n,), u32),)),
        "pallas_wah_interleave": (
            jax.jit(pallas_wah_interleave),
            (_spec(s, (n,), u32), _spec(s, (n,), u32))),
    }


_KERNELS = ["pallas_moe_held_experts", "pallas_matmul",
            "pallas_flash_attention", "pallas_mandelbrot",
            "pallas_radix_pass", "pallas_local_compact",
            "pallas_wah_interleave"]


@pytest.mark.parametrize("name", _KERNELS)
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_cases(one_chip)[name]
    text = fn.lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, f"{name}: no tpu_custom_call in the compiled program"
    # a kernel given a name (``pallas_call(name=...)``) shows it before
    # ``pallas_call``
    assert any(re.search(rf"jit\({name}\)/(?:\w+/)?pallas_call", c)
               for c in calls)


def test_qwen3_decode_step_fits_one_v5e(one_chip):
    from repro import configs
    from repro.dist.step import build_serve_step
    from repro.models import Model

    model = Model(configs.get_config("qwen3-1.7b"))
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    params = place(model.param_shapes())
    cache = place(jax.eval_shape(lambda: model.init_cache(8, 1024)))
    tokens = _spec(one_chip, (8, 1), jnp.int32)
    compiled = jax.jit(build_serve_step(model)).lower(
        params, cache, tokens).compile()
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used


def test_moonlight_share_decode_step_fits_one_v5e(one_chip, monkeypatch):
    """One chip's share of Moonlight-16B-A3B (16 of 64 experts held, every
    width published) at batch 64 and a 257-position latent cache: the
    served step, with the held-expert kernel compiled for the chip (the
    program takes its TPU branch only where JAX runs on one)."""
    from repro import configs
    from repro.dist.step import build_serve_step
    from repro.kernels import ops
    from repro.models import Model

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    model = Model(configs.get_config("moonlight-16b-a3b"))
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    params = place(model.param_shapes())
    cache = place(jax.eval_shape(lambda: model.init_cache(64, 257)))
    tokens = _spec(one_chip, (64, 1), jnp.int32)
    compiled = jax.jit(build_serve_step(model)).lower(
        params, cache, tokens).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and "moe_held_experts" in kernels[0]
    # the kernel reads the stacked expert weights where they lie: no
    # layer's held experts are copied out for it
    assert not re.search(r"= bf16\[16,(2048,1408|1408,2048)\]", text)
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert 10.3e9 < ma.argument_size_in_bytes and used < HBM_BYTES, used
