"""Core transformer layers: norms, RoPE, GQA/MQA/MLA attention, MLP.

Attention uses a *chunked online-softmax* implementation for train/prefill
(``chunked_attention``) — the pure-XLA expression of the paper's streaming
principle: KV is consumed in pages with O(page) local state instead of
materializing the T×T score matrix. On TPU the Pallas ``flash_attention``
kernel replaces it; the XLA path is the portable oracle and the dry-run
lowering path.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import PSpec
from repro.models import tuning as TU
from repro.sharding.context import shard

NEG_INF = -1e30


# ---------------------------------------------------------------- norms
def norm_pspec(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    p = {"scale": PSpec((d,), ("embed_act",), "ones", dtype="float32")}
    if cfg.norm == "layernorm":
        p["bias"] = PSpec((d,), ("embed_act",), "zeros", dtype="float32")
    return p


def apply_norm(p, x, cfg: ModelConfig, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------- rope
def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, cfg: ModelConfig, dim: Optional[int] = None):
    """x: (..., T, H, D) or (..., H, D) w/ scalar positions; rotates pairs.

    cfg.rope == "full": rotate all of head_dim; "2d" (chatglm): rotate the
    first half only; "none": identity.
    """
    if cfg.rope == "none":
        return x
    d = x.shape[-1]
    rot = d if cfg.rope == "full" else d // 2
    if dim is not None:
        rot = dim
    freqs = rope_freqs(rot, cfg.rope_theta)                    # (rot/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., rot/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    # broadcast across the head axis: positions are (..., T) while x is
    # (..., T, H, D) -> insert the H axis.
    cos, sin = cos[..., None, :], sin[..., None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([y.astype(x.dtype), x[..., rot:]], axis=-1)


# ----------------------------------------------------- chunked attention
def chunked_attention(q, k, v, *, causal: bool, q_offset=0,
                      q_chunk: int = 0, kv_chunk: int = 0,
                      kv_len=None):
    """Online-softmax attention, O(chunk) memory — streaming KV pages.

    q: (B, Tq, H, D); k, v: (B, Tk, KH, Dk/Dv) with H = KH * G (GQA).
    kv_len: optional (B,) valid KV length (for prefill into padded caches).
    Returns (B, Tq, H, Dv).
    """
    B, Tq, H, D = q.shape
    _, Tk, KH, Dv = v.shape
    G = H // KH
    t = TU.get()
    q_chunk = min(q_chunk or t.q_chunk, Tq)
    kv_chunk = min(kv_chunk or t.kv_chunk, Tk)
    nq, nk = -(-Tq // q_chunk), -(-Tk // kv_chunk)
    pad_q = nq * q_chunk - Tq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(D)
    qc = q.reshape(B, nq, q_chunk, KH, G, D)

    def q_block(args):
        qb, qi = args                                  # (B,qc,KH,G,D)
        qb = shard(qb, ("batch", "seq_q", "kv_heads", "heads", None))
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_block(carry, ki):
            acc, m, l = carry
            ks = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 1)
            kv_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, ks,
                           preferred_element_type=jnp.float32) * scale
            s = shard(s, ("batch", "kv_heads", "heads", "seq_q", None))
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= q_pos[:, None] >= kv_pos[None, :]
            mask = jnp.broadcast_to(mask, (B, 1, 1, q_chunk, kv_chunk))
            if kv_len is not None:
                mask &= (kv_pos[None, :] < kv_len[:, None]
                         )[:, None, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vs.dtype), vs,
                preferred_element_type=jnp.float32)
            acc = shard(acc, ("batch", "kv_heads", "heads", "seq_q", None))
            return (acc, m_new, l_new), None

        acc0 = shard(jnp.zeros((B, KH, G, q_chunk, Dv), jnp.float32),
                     ("batch", "kv_heads", "heads", "seq_q", None))
        m0 = jnp.full((B, KH, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_block, (acc0, m0, l0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4)            # (B,qc,KH,G,Dv)

    out = jax.lax.map(q_block, (qc.transpose(1, 0, 2, 3, 4, 5),
                                jnp.arange(nq)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_chunk, H, Dv)
    return out[:, :Tq].astype(v.dtype)


def softmax_with_new(s, valid, s_new):
    """Softmax over the valid cached keys and one new key.

    s: (..., S) f32 scores against the cache, valid broadcastable to s;
    s_new: (...) f32 score against the current token's own key. Returns
    (p (..., S), p_new (...)): ``jax.nn.softmax`` over the S + 1 keys."""
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.maximum(s.max(-1), s_new)
    e = jnp.exp(s - m[..., None])
    e_new = jnp.exp(s_new - m)
    total = e.sum(-1) + e_new
    return e / total[..., None], e_new / total


def decode_attention(q, k_cache, v_cache, cache_len, k_scale=None,
                     v_scale=None, new=None):
    """Single-token attention against a (padded) cache.

    q: (B, H, D); caches: (B, S, KH, D); cache_len: () or (B,) int32.
    k_scale/v_scale: (B, S, KH) dequant scales for INT8 caches.
    new: the current token's own entries, attended as one more key after
    the ``cache_len`` cached ones: {"k","v": (B, KH, D)}, with
    "k_scale","v_scale" (B, KH) beside INT8 caches.
    """
    B, H, D = q.shape
    _, S, KH, Dv = v_cache.shape
    G = H // KH
    qg = q.reshape(B, KH, G, D)

    def mxu(a):
        return a.astype(jnp.bfloat16) if a.dtype == jnp.int8 else a

    s = jnp.einsum("bhgd,bkhd->bhgk", qg, mxu(k_cache),
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    if k_scale is not None:
        s = s * k_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None]
    s = shard(s, ("batch", "kv_heads", "heads", "cache_seq"))
    pos = jnp.arange(S)
    cl = jnp.asarray(cache_len)
    if cl.ndim == 0:
        cl = jnp.broadcast_to(cl, (B,))
    valid = (pos[None, :] < cl[:, None])[:, None, None, :]
    if new is None:
        p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
    else:
        s_new = jnp.einsum("bhgd,bhd->bhg", qg, mxu(new["k"]),
                           preferred_element_type=jnp.float32) / math.sqrt(D)
        if k_scale is not None:
            s_new = s_new * new["k_scale"].astype(jnp.float32)[:, :, None]
        p, p_new = softmax_with_new(s, valid, s_new)
    if v_scale is not None:
        pv = p * v_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None]
        out = jnp.einsum("bhgk,bkhd->bhgd", pv.astype(jnp.bfloat16),
                         v_cache.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        if new is not None:
            pv_new = p_new * new["v_scale"].astype(jnp.float32)[:, :, None]
            out = out + jnp.einsum(
                "bhg,bhd->bhgd", pv_new.astype(jnp.bfloat16),
                new["v"].astype(jnp.bfloat16),
                preferred_element_type=jnp.float32)
        return out.reshape(B, H, Dv).astype(jnp.bfloat16)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    if new is not None:
        out = out + jnp.einsum("bhg,bhd->bhgd", p_new.astype(v_cache.dtype),
                               new["v"], preferred_element_type=jnp.float32)
    return out.reshape(B, H, Dv).astype(v_cache.dtype)


# ---------------------------------------------------------------- MLP
def mlp_pspecs(cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    p = {"wo": PSpec((f, d), ("mlp", "embed"))}
    if cfg.glu:
        p["wi_gate"] = PSpec((d, f), ("embed", "mlp"))
        p["wi_up"] = PSpec((d, f), ("embed", "mlp"))
    else:
        p["wi"] = PSpec((d, f), ("embed", "mlp"))
        p["bi"] = PSpec((f,), ("mlp",), "zeros")
        p["bo"] = PSpec((d,), ("embed_act",), "zeros")
    return p


def apply_act(x, cfg: ModelConfig):
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


@jax.named_scope("mlp")
def apply_mlp(p, x, cfg: ModelConfig):
    if cfg.glu:
        h = apply_act(x @ p["wi_gate"], cfg) * (x @ p["wi_up"])
        return h @ p["wo"]
    h = apply_act(x @ p["wi"] + p["bi"], cfg)
    return h @ p["wo"] + p["bo"]


# ------------------------------------------------------- GQA attention
def attention_pspecs(cfg: ModelConfig):
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": PSpec((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": PSpec((d, KH, hd), ("embed", "kv_heads", "head_dim")),
        "wv": PSpec((d, KH, hd), ("embed", "kv_heads", "head_dim")),
        "wo": PSpec((H, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = PSpec((H, hd), ("heads", "head_dim"), "zeros")
        p["bk"] = PSpec((KH, hd), ("kv_heads", "head_dim"), "zeros")
        p["bv"] = PSpec((KH, hd), ("kv_heads", "head_dim"), "zeros")
    return p


def qkv_proj(p, x, cfg: ModelConfig):
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


@jax.named_scope("attention")
def attention_train(p, x, cfg: ModelConfig, positions, causal=True,
                    kv=None):
    """Full-sequence attention (train / prefill). kv: optional external
    (k, v) for cross-attention (whisper decoder)."""
    q, k, v = (qkv_proj(p, x, cfg) if kv is None
               else (jnp.einsum("btd,dhk->bthk", x, p["wq"]) +
                     (p["bq"] if cfg.qkv_bias else 0), *kv))
    if kv is None:
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)
    out = chunked_attention(q, k, v, causal=causal)
    return jnp.einsum("bthk,hkd->btd", out, p["wo"]), (k, v)


@jax.named_scope("attention")
def attention_decode(p, x, cfg: ModelConfig, cache):
    """x: (B, d) one token. cache: {"k","v": (B,S,KH,hd), "len": (B,)}.

    Per-sequence lengths: slot b attends to its ``len[b]`` cached
    positions and to its own new K/V as one more key, so continuous
    batching serves mixed-progress sequences in one step. The cache is
    only read. Returns (out (B, d), new): the token's entries in the
    cache's dtype, {"k","v": (B,KH,hd)} (and "k_scale","v_scale" (B,KH)
    for INT8 caches), for the caller to write at ``[b, len[b]]``."""
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    pos = cache["len"]                                    # (B,)
    q = apply_rope(q[:, None], pos[:, None], cfg)[:, 0]
    k = apply_rope(k[:, None], pos[:, None], cfg)[:, 0]
    if "k_scale" in cache:
        # INT8 paged KV: per-(token, kv-head) scales — halves the decode
        # bandwidth wall (the paper's INT8 streaming, applied to the KV)
        ks = jnp.max(jnp.abs(k), -1) / 127.0 + 1e-8
        vs = jnp.max(jnp.abs(v), -1) / 127.0 + 1e-8
        new = {"k": jnp.round(k / ks[..., None]).astype(jnp.int8),
               "v": jnp.round(v / vs[..., None]).astype(jnp.int8),
               "k_scale": ks.astype(jnp.float16),
               "v_scale": vs.astype(jnp.float16)}
        out = decode_attention(q, cache["k"], cache["v"], pos,
                               k_scale=cache["k_scale"],
                               v_scale=cache["v_scale"], new=new)
    else:
        new = {"k": k.astype(cache["k"].dtype),
               "v": v.astype(cache["v"].dtype)}
        out = decode_attention(q, cache["k"], cache["v"], pos, new=new)
    return jnp.einsum("bhk,hkd->bd", out, p["wo"]), new


# --------------------------------------------------------------- MLA
def mla_pspecs(cfg: ModelConfig):
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wdq": PSpec((d, m.q_lora_rank), ("embed", "lora")),
        "q_norm": norm_pspec(cfg, m.q_lora_rank),
        "wuq": PSpec((m.q_lora_rank, H, qk), ("lora", "heads", "head_dim")),
        "wdkv": PSpec((d, m.kv_lora_rank), ("embed", "lora")),
        "kv_norm": norm_pspec(cfg, m.kv_lora_rank),
        "wkr": PSpec((d, m.qk_rope_head_dim), ("embed", "head_dim")),
        "wuk": PSpec((m.kv_lora_rank, H, m.qk_nope_head_dim),
                     ("lora", "heads", "head_dim")),
        "wuv": PSpec((m.kv_lora_rank, H, m.v_head_dim),
                     ("lora", "heads", "head_dim")),
        "wo": PSpec((H, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


@jax.named_scope("attention")
def mla_train(p, x, cfg: ModelConfig, positions):
    m = cfg.mla
    nope, rope_d = m.qk_nope_head_dim, m.qk_rope_head_dim
    cq = apply_norm(p["q_norm"], x @ p["wdq"], cfg)
    q = jnp.einsum("btr,rhk->bthk", cq, p["wuq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg, dim=rope_d)
    ckv = apply_norm(p["kv_norm"], x @ p["wdkv"], cfg)
    k_rope = apply_rope((x @ p["wkr"])[:, :, None, :], positions, cfg,
                        dim=rope_d)                      # (B,T,1,rope)
    k_nope = jnp.einsum("btr,rhk->bthk", ckv, p["wuk"])
    v = jnp.einsum("btr,rhk->bthk", ckv, p["wuv"])
    H = cfg.n_heads
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_rope.shape[:2] + (H, rope_d))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = chunked_attention(q, k, v, causal=True)
    return jnp.einsum("bthk,hkd->btd", out, p["wo"]), (ckv, k_rope[:, :, 0])


@jax.named_scope("attention")
def mla_decode(p, x, cfg: ModelConfig, cache):
    """Absorbed-matrix MLA decode against the *compressed* latent cache.

    cache: {"ckv": (B,S,r), "kr": (B,S,rope), "len": (B,)}, only read:
    slot b attends to its ``len[b]`` cached latents and its own new one.
    score = q_nope·W_uk·ckv + q_rope·k_rope  (W_uk absorbed into q).
    Returns (out (B, d), new {"ckv": (B,r), "kr": (B,rope)}).
    """
    m = cfg.mla
    nope, rope_d = m.qk_nope_head_dim, m.qk_rope_head_dim
    pos = cache["len"]                                        # (B,)
    cq = apply_norm(p["q_norm"], x @ p["wdq"], cfg)
    q = jnp.einsum("br,rhk->bhk", cq, p["wuq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope[:, None], pos[:, None], cfg,
                        dim=rope_d)[:, 0]
    ckv_t = apply_norm(p["kv_norm"], x @ p["wdkv"], cfg)       # (B,r)
    kr_t = apply_rope((x @ p["wkr"])[:, None, None, :],
                      pos[:, None], cfg, dim=rope_d)[:, 0, 0]
    ckv, kr = cache["ckv"], cache["kr"]
    new = {"ckv": ckv_t.astype(ckv.dtype), "kr": kr_t.astype(kr.dtype)}
    # absorb W_uk:   q_lat (B,H,r)
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, p["wuk"])
    norm = math.sqrt(nope + rope_d)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhk,bsk->bhs", q_rope, kr,
                      preferred_element_type=jnp.float32)) / norm
    s_new = (jnp.einsum("bhr,br->bh", q_lat, new["ckv"],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhk,bk->bh", q_rope, new["kr"],
                          preferred_element_type=jnp.float32)) / norm
    valid = jnp.arange(ckv.shape[1])[None, :] < pos[:, None]
    pattn, p_new = softmax_with_new(s, valid[:, None, :], s_new)
    o_lat = (jnp.einsum("bhs,bsr->bhr", pattn.astype(ckv.dtype), ckv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bh,br->bhr", p_new.astype(ckv.dtype), new["ckv"],
                          preferred_element_type=jnp.float32)
             ).astype(ckv.dtype)
    out = jnp.einsum("bhr,rhk->bhk", o_lat, p["wuv"])
    return jnp.einsum("bhk,hkd->bd", out, p["wo"]), new
