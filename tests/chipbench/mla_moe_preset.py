"""A test's architecture family: latent attention (MLA) with routed and
shared experts after leading dense layers, laid out as the program's
``moe`` models (deepseek-v3) hold their weights. The tests copy it into
``families/`` of a copy of the benchmark to show that a new architecture
goes in as files alone.

Work, under the rule in ``work.py``. Per token, every matrix except the
routed experts' counts once, and of those the ``experts_per_token`` it is
routed to. A decode step attends in the latent space (queries absorbed
into ``wuk``, outputs through ``wuv``): per cached key and head,
2 (kv_lora + rope) FLOPs for QK and 2 kv_lora for PV. A prefill expands
the keys and values first: per query-key pair and head,
2 (nope + rope) + 2 v. The bytes are every weight once but the routed
experts, of which a step may need no more than ``experts_per_token`` a
layer (every token routed alike), the gathered embedding rows, and the
latent cache (kv_lora + rope per token and layer) read and written; the
multi-token-prediction block is not served and not counted.
"""
from __future__ import annotations

BF16 = 2
# the sizes a configuration file's published_keys has to map
PUBLISHED = ("layers", "dense_layers", "d_model", "heads", "d_ff", "vocab",
             "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "experts",
             "experts_per_token", "expert_d_ff", "shared_experts",
             "norm_eps", "tied_embeddings")


def _layer_specs(m, prefix, n, moe):
    d, H = m["d_model"], m["heads"]
    qr, kr = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    bf, f32 = "bfloat16", "float32"
    lead = (n,) if n else ()
    st = bool(n)
    s = {
        "ln1/scale": ((d,), f32, "norm", 1.0),
        "ln2/scale": ((d,), f32, "norm", 1.0),
        "attn/wdq": ((d, qr), bf, "normal", d ** -0.5),
        "attn/q_norm/scale": ((qr,), f32, "norm", 1.0),
        "attn/wuq": ((qr, H, nope + rope), bf, "normal", qr ** -0.5),
        "attn/wdkv": ((d, kr), bf, "normal", d ** -0.5),
        "attn/kv_norm/scale": ((kr,), f32, "norm", 1.0),
        "attn/wkr": ((d, rope), bf, "normal", d ** -0.5),
        "attn/wuk": ((kr, H, nope), bf, "normal", kr ** -0.5),
        "attn/wuv": ((kr, H, v), bf, "normal", kr ** -0.5),
        "attn/wo": ((H, v, d), bf, "normal", (H * v) ** -0.5),
    }
    if moe:
        E, fe = m["experts"], m["expert_d_ff"]
        fs = fe * m["shared_experts"]
        s.update({
            "moe/router": ((d, E), bf, "normal", d ** -0.5),
            "moe/wi_gate": ((E, d, fe), bf, "normal", d ** -0.5),
            "moe/wi_up": ((E, d, fe), bf, "normal", d ** -0.5),
            "moe/wo": ((E, fe, d), bf, "normal", fe ** -0.5),
            "moe/shared_wi_gate": ((d, fs), bf, "normal", d ** -0.5),
            "moe/shared_wi_up": ((d, fs), bf, "normal", d ** -0.5),
            "moe/shared_wo": ((fs, d), bf, "normal", fs ** -0.5),
        })
    else:
        f = m["d_ff"]
        s.update({
            "mlp/wi_gate": ((d, f), bf, "normal", d ** -0.5),
            "mlp/wi_up": ((d, f), bf, "normal", d ** -0.5),
            "mlp/wo": ((f, d), bf, "normal", f ** -0.5),
        })
    return {f"{prefix}/{k}": (lead + shape, dt, law, sc, st)
            for k, (shape, dt, law, sc) in s.items()}


def leaf_specs(m: dict) -> dict:
    d, Vp, nd = m["d_model"], m["vocab_padded"], m["dense_layers"]
    s = {
        "embed": ((Vp, d), "bfloat16", "normal", 0.02, False),
        "final_norm/scale": ((d,), "float32", "norm", 1.0, False),
    }
    if nd:
        s.update(_layer_specs(m, "dense_layers", nd, moe=False))
    s.update(_layer_specs(m, "layers", m["layers"] - nd, moe=True))
    if not m["tied_embeddings"]:
        s["lm_head"] = ((d, Vp), "bfloat16", "normal", d ** -0.5, False)
    if m["mtp"]:
        s.update(_layer_specs(m, "mtp/block", 0, moe=False))
        s["mtp/norm_e/scale"] = ((d,), "float32", "norm", 1.0, False)
        s["mtp/norm_h/scale"] = ((d,), "float32", "norm", 1.0, False)
        s["mtp/proj"] = ((2 * d, d), "bfloat16", "normal",
                         (2 * d) ** -0.5, False)
    return s


def program_config(name: str, m: dict, base):
    return base.ModelConfig(
        name=name, family="moe", n_layers=m["layers"],
        d_model=m["d_model"], n_heads=m["heads"], n_kv_heads=m["heads"],
        d_ff=m["d_ff"], vocab_size=m["vocab"], rope_theta=m["rope_theta"],
        tie_embeddings=m["tied_embeddings"], mtp=m["mtp"],
        moe=base.MoEConfig(
            n_routed_experts=m["experts"], top_k=m["experts_per_token"],
            d_ff_expert=m["expert_d_ff"],
            n_shared_experts=m["shared_experts"],
            first_dense_layers=m["dense_layers"]),
        mla=base.MLAConfig(
            q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
            qk_nope_head_dim=m["qk_nope_head_dim"],
            qk_rope_head_dim=m["qk_rope_head_dim"],
            v_head_dim=m["v_head_dim"]),
        vocab_pad_multiple=m.get("vocab_pad_multiple", 256))


def check_published(f: dict) -> None:
    """Raise where the map of published keys leaves a size out, or the
    multi-token-prediction block departs from the published count."""
    m, keys = f["model"], f["published_keys"]
    missing = set(PUBLISHED) - set(keys)
    if missing:
        raise ValueError(f"{f['name']}: published_keys maps no "
                         f"{sorted(missing)}")
    if m["mtp"] != bool(f["num_nextn_predict_layers"]):
        raise ValueError(f"{f['name']}: mtp {m['mtp']} against "
                         f"{f['num_nextn_predict_layers']} published "
                         f"prediction layers")


def _attn_params(m):
    d, H = m["d_model"], m["heads"]
    qr, kr = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    return (d * qr + qr * H * (nope + rope) + d * kr + d * rope
            + kr * H * (nope + v) + H * v * d)


def _ffn_params(m, moe, experts):
    d = m["d_model"]
    if not moe:
        return 3 * d * m["d_ff"]
    return d * m["experts"] + 3 * d * m["expert_d_ff"] * (
        experts + m["shared_experts"])


def _matmul_params(m, experts):
    """Matrix parameters of every served layer, ``experts`` routed
    experts in each MoE layer."""
    nd, nm = m["dense_layers"], m["layers"] - m["dense_layers"]
    a = _attn_params(m)
    return (nd * (a + _ffn_params(m, False, 0))
            + nm * (a + _ffn_params(m, True, experts)))


def _other_bytes(m):
    """Norm gains (float32) of every served layer and the final norm."""
    per = 2 * m["d_model"] + m["q_lora_rank"] + m["kv_lora_rank"]
    return 4 * (m["layers"] * per + m["d_model"])


def latent_bytes_per_token(m: dict) -> int:
    return m["layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * BF16


def decode_step(m: dict, ctx: list) -> tuple[float, float]:
    n, k, H = len(ctx), m["experts_per_token"], m["heads"]
    head = m["d_model"] * m["vocab"]
    per_key = m["layers"] * H * (
        2 * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
        + 2 * m["kv_lora_rank"])
    flops = (2.0 * (_matmul_params(m, k) + head) * n
             + per_key * sum(c + 1 for c in ctx))
    nbytes = ((_matmul_params(m, k) + head) * BF16 + _other_bytes(m)
              + n * m["d_model"] * BF16
              + latent_bytes_per_token(m) * (sum(ctx) + n))
    return flops, float(nbytes)


def prefill(m: dict, T: int) -> float:
    per_pair = m["layers"] * m["heads"] * (
        2 * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
        + 2 * m["v_head_dim"])
    return (2.0 * _matmul_params(m, m["experts_per_token"]) * T
            + 2.0 * m["d_model"] * m["vocab"] + per_pair * T * (T + 1) / 2)
