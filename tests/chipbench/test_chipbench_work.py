"""Work counts and peaks."""
import json
from pathlib import Path

import pytest

from chipbench import peaks, work
from chipbench.families import dense_gqa

CONFIGS = Path(__file__).resolve().parents[2] / "chipbench" / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_qwen2_decode_step_by_hand():
    m = model("qwen2-0.5b")
    # one slot holding 1000 tokens
    d, f, L, V = 896, 4864, 24, 151936
    per_layer = d * 14 * 64 + 2 * d * 2 * 64 + 14 * 64 * d + 3 * d * f
    assert per_layer == 14_909_440
    mm = L * per_layer + d * V                    # 493,961,216
    attn = L * 4 * 14 * 64 * 1001                 # QK and PV, 1001 keys
    flops, nbytes = work.decode_step(m, [1000])
    assert flops == 2 * mm + attn
    weights = L * (per_layer * 2 + 2 * d * 4 + (14 + 4) * 64 * 2) \
        + d * V * 2 + d * 4 + d * 2
    kv = 24 * 2 * 2 * 64 * 2 * 1001               # 12,288 B a token
    assert nbytes == weights + kv
    assert dense_gqa.kv_bytes_per_token(m) == 12_288
    # a single decode step is bound by bytes: about 1.2 ms at 819 GB/s
    pk = peaks.peak("TPU v5 lite")
    assert work.bound_s(flops, nbytes, pk) == pytest.approx(
        nbytes / 819e9)


def test_prefill_counts_causal_attention_once():
    m = model("chatglm3-6b")
    T = 1024
    mm = 28 * dense_gqa.layer_matmul_params(m)
    attn = 28 * 4 * 32 * 128 * T * (T + 1) / 2
    assert work.prefill(m, T) == 2 * mm * T + 2 * 4096 * 65024 + attn


@pytest.mark.parametrize("ctx", [[1], [100] * 16, [4000] * 32])
def test_share_is_at_most_100_when_time_equals_the_bound(ctx):
    pk = peaks.peak("TPU v5 lite")
    for name in ("qwen2-0.5b", "chatglm3-6b"):
        b = work.bound_s(*work.decode_step(model(name), ctx), pk)
        assert 100.0 * b / b == pytest.approx(100.0)
        assert 100.0 * b / (b * 1.5) < 100.0


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
    assert "Google Cloud" in peaks.peak("TPU v5 lite")["source"]
