"""Dense decoder with grouped-query attention: its weight layout, the
program's config for it, and the least work of its serving steps.

Pre-norm blocks of attention (``heads`` query heads reading ``kv_heads``
K/V groups, optional q/k/v biases, RoPE on ``rotary_dim`` of each head)
and a SwiGLU MLP of width ``d_ff``; an embedding, optionally tied to the
output head. qwen2-0.5b and chatglm3-6b are of this family.

Work, under the rule in ``work.py``. Decode step over active slots with
cache lengths ``ctx`` (before the step):
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
# the sizes a configuration file's published_keys has to map
PUBLISHED = ("layers", "d_model", "heads", "kv_heads", "d_ff", "vocab",
             "norm_eps", "tied_embeddings")


def leaf_specs(m: dict) -> dict:
    """path -> (shape, dtype, law, scale, stacked) for model group ``m``.

    Laws: ``normal`` (N(0,1) times scale), ``norm`` (1 + 0.1 N, a norm's
    gain), ``bias`` (0.02 N). Matrices are scaled by fan-in ** -0.5.
    Stacked leaves carry the layer count as their first dimension.
    """
    L, d, H, KH = m["layers"], m["d_model"], m["heads"], m["kv_heads"]
    hd, f, Vp = m["head_dim"], m["d_ff"], m["vocab_padded"]
    bf, f32 = "bfloat16", "float32"
    s = {
        "embed": ((Vp, d), bf, "normal", 0.02, False),
        "final_norm/scale": ((d,), f32, "norm", 1.0, False),
        "layers/ln1/scale": ((L, d), f32, "norm", 1.0, True),
        "layers/ln2/scale": ((L, d), f32, "norm", 1.0, True),
        "layers/attn/wq": ((L, d, H, hd), bf, "normal", d ** -0.5, True),
        "layers/attn/wk": ((L, d, KH, hd), bf, "normal", d ** -0.5, True),
        "layers/attn/wv": ((L, d, KH, hd), bf, "normal", d ** -0.5, True),
        "layers/attn/wo": ((L, H, hd, d), bf, "normal", (H * hd) ** -0.5,
                           True),
        "layers/mlp/wi_gate": ((L, d, f), bf, "normal", d ** -0.5, True),
        "layers/mlp/wi_up": ((L, d, f), bf, "normal", d ** -0.5, True),
        "layers/mlp/wo": ((L, f, d), bf, "normal", f ** -0.5, True),
    }
    if m["qkv_bias"]:
        s["layers/attn/bq"] = ((L, H, hd), bf, "bias", 1.0, True)
        s["layers/attn/bk"] = ((L, KH, hd), bf, "bias", 1.0, True)
        s["layers/attn/bv"] = ((L, KH, hd), bf, "bias", 1.0, True)
    if not m["tied_embeddings"]:
        s["lm_head"] = ((d, Vp), bf, "normal", d ** -0.5, False)
    return s


def program_config(name: str, m: dict, base):
    """The program's ``ModelConfig`` for model group ``m``; ``base`` is
    the program's ``repro.configs.base``."""
    if m["rotary_dim"] == m["head_dim"]:
        rope = "full"
    elif 2 * m["rotary_dim"] == m["head_dim"]:
        rope = "2d"
    else:
        raise ValueError(f"{name}: rotary_dim {m['rotary_dim']} is "
                         f"neither head_dim nor half of it")
    return base.ModelConfig(
        name=name, family="dense", n_layers=m["layers"],
        d_model=m["d_model"], n_heads=m["heads"], n_kv_heads=m["kv_heads"],
        d_ff=m["d_ff"], vocab_size=m["vocab"], head_dim=m["head_dim"],
        qkv_bias=m["qkv_bias"], rope=rope, rope_theta=m["rope_theta"],
        tie_embeddings=m["tied_embeddings"],
        vocab_pad_multiple=m.get("vocab_pad_multiple", 256))


def check_published(f: dict) -> None:
    """Raise where model group ``f["model"]`` departs from the published
    configuration ``f`` beyond the keys that ``f["published_keys"]``
    maps: the map has to name every size of the family, and a head size
    that the published file does not state is its width over its heads,
    as the published code reads it."""
    m, keys = f["model"], f["published_keys"]
    missing = set(PUBLISHED) - set(keys)
    if missing:
        raise ValueError(f"{f['name']}: published_keys maps no "
                         f"{sorted(missing)}")
    if "head_dim" not in keys and m["head_dim"] * m["heads"] != m["d_model"]:
        raise ValueError(f"{f['name']}: head_dim {m['head_dim']} is not "
                         f"d_model / heads, and the published file states "
                         f"none")


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
