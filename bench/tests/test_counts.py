"""The yardstick's arithmetic, pinned to qwen3-1.7b's published shapes."""
import pytest

from bench import counts, load_json
from bench.peaks import peaks

SIZES = load_json("configs", "qwen3-1.7b")


def test_parameter_bytes_are_3_44_gb():
    # 28 x (4*2048*... attention + 3*2048*6144 MLP + norms) + 151936*2048
    assert counts.param_count(SIZES) == 1_720_574_976
    assert counts.param_bytes(SIZES) == pytest.approx(3.44e9, rel=1e-3)


def test_kv_bytes_per_token():
    assert counts.kv_bytes_per_token(SIZES) == 114_688


def test_decode_flops_per_token():
    # 2 x matmul parameters (tied head included) + 4 * c * H * Dh * L
    mm = counts.matmul_params(SIZES)
    assert mm == 28 * 50_331_648 + 151_936 * 2048
    assert counts.decode_token_flops(SIZES, 1) == 2 * mm + 4 * 16 * 128 * 28
    assert counts.decode_token_flops(SIZES, 1) == pytest.approx(3.44e9,
                                                                rel=1e-2)


def test_step_counts_add_per_request():
    ctx = [5, 17, 200]
    assert counts.decode_step_flops(SIZES, ctx) == sum(
        counts.decode_token_flops(SIZES, c) for c in ctx)
    assert counts.decode_step_bytes(SIZES, ctx) == (
        counts.param_bytes(SIZES) + 114_688 * 222)


def test_peaks_by_device_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks("cpu")
