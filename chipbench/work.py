"""Operations and bytes that serving work needs, from the model group
of a configuration file and the live lengths. They count what the
algorithm must do, not what a program happens to do: a program that
wastes less does not lower them.

Decode step over active slots with cache lengths ``ctx`` (before the
step):
  FLOPs = sum over slots of 2 * matmul params (LM head included)
          + layers * 4 * heads * head_dim * (ctx + 1)        (QK and PV)
  bytes = every weight once, the LM head included, but of the embedding
          only the rows gathered
          + the live KV of every active slot (ctx tokens, read)
          + the new token's KV of every active slot (written).
Prefill of one prompt of T tokens (logits for its last token only):
  FLOPs = 2 * matmul params without the head * T + 2 * d * vocab
          + layers * 4 * heads * head_dim * T (T + 1) / 2     (causal)
"""
from __future__ import annotations

BF16 = 2


def layer_matmul_params(m: dict) -> int:
    d, H, KH, hd, f = (m["d_model"], m["heads"], m["kv_heads"],
                       m["head_dim"], m["d_ff"])
    return d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * f


def layer_other_bytes(m: dict) -> int:
    """Norm gains (float32) and q/k/v biases of one layer."""
    d, H, KH, hd = m["d_model"], m["heads"], m["kv_heads"], m["head_dim"]
    bias = (H + 2 * KH) * hd * BF16 if m["qkv_bias"] else 0
    return 2 * d * 4 + bias


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab"]


def kv_bytes_per_token(m: dict) -> int:
    return m["layers"] * 2 * m["kv_heads"] * m["head_dim"] * BF16


def attn_flops(m: dict, keys: int) -> int:
    """QK and PV of one query against ``keys`` keys, all layers."""
    return m["layers"] * 4 * m["heads"] * m["head_dim"] * keys


def decode_step(m: dict, ctx: list) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over slots with lengths ctx."""
    n = len(ctx)
    mm = m["layers"] * layer_matmul_params(m) + head_params(m)
    flops = 2.0 * mm * n + sum(attn_flops(m, c + 1) for c in ctx)
    weights = (m["layers"] * (layer_matmul_params(m) * BF16
                              + layer_other_bytes(m))
               + head_params(m) * BF16 + m["d_model"] * 4
               + n * m["d_model"] * BF16)
    kv = kv_bytes_per_token(m) * (sum(ctx) + n)
    return flops, float(weights + kv)


def prefill(m: dict, T: int) -> float:
    """FLOPs of prefilling one prompt of T tokens."""
    mm = m["layers"] * layer_matmul_params(m)
    return (2.0 * mm * T + 2.0 * head_params(m)
            + attn_flops(m, 1) * T * (T + 1) / 2)


def bound_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
