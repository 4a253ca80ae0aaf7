"""The Moonlight-16B-A3B cell's yardstick: the counts pinned to the
published shapes and the share the chip holds, and the check that decides
``correct`` failing for the float8 control while the program passes, on the
CPU."""
import json

import pytest

from bench import control as control_script
from bench import counts_mla_moe as counts
from bench import load_json

CELL = "moonlight-16b-a3b.chat-b64"
SIZES = load_json("configs", "moonlight-16b-a3b")
#: every published width, one dense and one expert layer, an 8,192-id
#: slice of the vocabulary: logits of the real scale at a size the CPU
#: holds (at the smoke widths bfloat16 rounding alone reads near the limit)
WIDE = {"num_hidden_layers": 2, "vocab_size": 8192}


def test_parameters_of_the_share_and_of_the_whole_model():
    # 26 x 169,611,776 (latent attention, router, 2 shared and 16 held
    # experts, norms) + 82,973,184 (dense layer 0) + 2 x 163,840 x 2048
    # (embedding, head) + 2048 (final norm)
    assert counts.moe_layer_params(SIZES, 16) == 169_611_776
    assert counts.dense_layer_params(SIZES) == 82_973_184
    assert counts.param_count(SIZES) == 5_163_970_048
    assert counts.param_count(SIZES) * 2 == pytest.approx(10.33e9, rel=1e-3)
    assert counts.param_count(SIZES, uncut=True) == 15_960_108_544
    # the held routed experts are 70 % of the weight bytes a step must read
    assert counts.routed_params(SIZES) / counts.param_count(SIZES) == \
        pytest.approx(0.697, abs=1e-3)


def test_bytes_of_an_expert_and_of_a_cached_token():
    assert counts.expert_bytes(SIZES) == 3 * 2048 * 1408 * 2 == 17_301_504
    # 27 layers x (512 latent + 64 rotary key) x 2 bytes
    assert counts.latent_bytes_per_token(SIZES) == 31_104


def test_step_counts_add_per_request_and_per_choice():
    ctx = [5, 17, 200]
    per_token = 2 * counts.non_expert_matmul_params(SIZES)
    assert counts.step_flops(SIZES, ctx) == sum(
        per_token + counts.attention_flops(SIZES, c) for c in ctx)
    assert counts.attention_flops(SIZES, 1) == 2 * 16 * (2 * 512 + 64) * 27
    assert counts.routed_flops(SIZES, 10) == 10 * 2 * 3 * 2048 * 1408
    assert counts.step_bytes(SIZES, ctx, 7) == (
        counts.non_expert_bytes(SIZES, 3) + 7 * 17_301_504 + 31_104 * 222)
    # outside the routed experts a token multiplies 1.23 G weights
    assert counts.non_expert_matmul_params(SIZES) == 1_229_586_432


#: the limit at two layers, on the statistic the cell checks: the cell's
#: (0.12) is set from readings of all 27 on the chip, where bfloat16 drift
#: and the routing choices it flips pile up with depth (program
#: 0.028-0.056, control 0.27-0.37); at two layers both read far lower
#: (program 0.0002-0.0003, control 0.030-0.064, on the CPU)
SHALLOW_LIMIT = 0.01


def test_program_passes_and_fp8_control_fails(capsys):
    limit = SHALLOW_LIMIT
    traffic = json.loads(json.dumps(load_json("traffic", "chat-b64")["smoke"]))
    traffic["fields"]["prompt"]["high"] = WIDE["vocab_size"]
    rc = control_script.main(
        ["--workload", CELL, "--seconds", "2", "--seeds", "5",
         "--control-seeds", "5"], allow_cpu=True,
        overrides={"config": dict(WIDE, limits={"mean_logit_gap": limit}),
                   "traffic": traffic})
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["failed"] == 0, line
    assert line["program"]["short_requests"] == 0
    assert line["program"]["mean_logit_gap"] <= limit
    assert line["control"]["mean_logit_gap"] > limit
    # every statistic of the gaps, for the program, the control and the
    # bfloat16 witness; the program's tokens are mostly the reference's
    # first, the control's much less so
    for stats in (line["program"], line["control"]):
        assert 0 <= stats["mean_logit_gap"] <= stats["max_logit_gap"]
    assert line["program"]["token_mismatch_share"] < \
        line["control"]["token_mismatch_share"]
    assert line["control"]["bf16.mean_logit_gap"] <= limit
    assert set(SIZES["limits"]) == {"mean_logit_gap"}
    assert limit < SIZES["limits"]["mean_logit_gap"]


def test_every_traced_metric_reads_a_traced_window():
    """The cell's per-layer readers on a window of 100 steps at batch 64
    whose expert kernel ran at exactly the peak for the bytes of the
    experts touched, a third of its runs kept in the trace: each reads a
    finite number, the kernel's share of its roofline 100 %."""
    import math

    from bench import ROOT, harness, load_module
    from bench.peaks import PEAKS
    from bench.trace_reduce import Event, Reduced

    peaks = PEAKS["TPU v5 lite"]
    n, layers = 100, counts.n_moe_layers(SIZES)
    touched, held = n * layers * 16, n * layers * 96
    # one kernel run a layer-step, each reading its 16 experts at the peak;
    # the profiler kept one run in three
    kernel_ns = 1e9 * 16 * counts.expert_bytes(SIZES) \
        / peaks["hbm_bytes_per_s"]
    kernels = [Event('%moe = bf16[624,2048] custom-call(), '
                     'custom_call_target="tpu_custom_call"', 1e6 * i,
                     1e6 * i + kernel_ns) for i in range(0, n * layers, 3)]
    trace = Reduced(window_s=3.0, busy_s=2.4, idle_share=0.2, op_s={},
                    ops=kernels,
                    module_runs={"jit_batched_step": [0.024] * n},
                    idle_gaps=[], spans={}, busy=[])
    zero = {"steps": 0, "batch_slots": 0, "tokens": 0, "completed": 0,
            "failed": 0, "host_steps": 0, "held_choices": 0,
            "touched_experts": 0}
    run = harness.Run(
        cell={"name": CELL}, config=SIZES, traffic=load_json("traffic",
                                                             "chat-b64"),
        records=[], window=(10.0, 13.0), setup_s=1.0,
        setup_compile={"seconds": 2.0, "compiles": 1, "hits": 0, "misses": 1},
        counters_start=zero,
        counters_end=dict(zero, steps=n, batch_slots=64 * n,
                          held_choices=held, touched_experts=touched),
        steps=[(10.0 + 0.03 * i, 64, i) for i in range(n)],
        device_kind="TPU v5 lite", peaks=peaks, trace=trace)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    read = {m["name"]: load_module("metrics", m["name"]).read(run)
            for m in harness.metric_entries(spec, {"name": CELL}, True)}
    assert {"decode.step_ms.moonlight", "decode.mfu.moonlight",
            "decode.hbm_roofline.moonlight",
            "moe.experts_hbm_roofline.moonlight",
            "moe.tokens_per_held_expert.moonlight",
            "device_idle_share.moonlight", "serve.occupancy.moonlight"} \
        <= set(read)
    assert all(v is not None and math.isfinite(v) for v in read.values()), read
    assert read["moe.experts_hbm_roofline.moonlight"] == pytest.approx(100.0)
    assert read["moe.tokens_per_held_expert.moonlight"] == pytest.approx(6.0)
    assert read["decode.step_ms.moonlight"] == pytest.approx(24.0)
    assert read["serve.occupancy.moonlight"] == pytest.approx(100.0)
    assert 0 < read["decode.hbm_roofline.moonlight"] < 100
