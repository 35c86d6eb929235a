"""Prefill + single-token decode paths with scanned, stacked caches.

Cache layout (leading L = layer-stack dim, scanned):
  dense/vlm : {"layers": {"k","v": (L,B,S,KH,hd)}, "len": ()}
  mla       : {"layers": {"ckv": (L,B,S,r), "kr": (L,B,S,rope)}, "len": ()}
  moe       : dense caches + optional "dense_layers" stack (deepseek)
  hybrid    : {"layers": mamba-state, "shared": {"k","v": (I,B,S,KH,hd)},
               "len": ()} — I = number of shared-attention invocations
  ssm       : {"layers": rwkv-state, "len": ()}
  audio     : {"layers": self {"k","v"}, "cross": {"k","v": (L,B,Te,KH,hd)},
               "len": ()}
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import transformer as T
from repro.sharding.context import shard


# =====================================================================
# cache init + logical axes
# =====================================================================
def _attn_cache_zeros(cfg, n_layers, batch, seq, dtype=jnp.bfloat16):
    from repro.models import tuning as TU
    if cfg.mla:
        m = cfg.mla
        return {"ckv": jnp.zeros((n_layers, batch, seq, m.kv_lora_rank), dtype),
                "kr": jnp.zeros((n_layers, batch, seq, m.qk_rope_head_dim),
                                dtype)}
    KH, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if TU.get().kv_cache_quant:
        return {"k": jnp.zeros((n_layers, batch, seq, KH, hd), jnp.int8),
                "v": jnp.zeros((n_layers, batch, seq, KH, hd), jnp.int8),
                "k_scale": jnp.zeros((n_layers, batch, seq, KH),
                                     jnp.float16),
                "v_scale": jnp.zeros((n_layers, batch, seq, KH),
                                     jnp.float16)}
    return {"k": jnp.zeros((n_layers, batch, seq, KH, hd), dtype),
            "v": jnp.zeros((n_layers, batch, seq, KH, hd), dtype)}


def _attn_cache_axes(cfg):
    from repro.models import tuning as TU
    if cfg.mla:
        return {"ckv": ("layers", "cache_batch", "cache_seq", "lora"),
                "kr": ("layers", "cache_batch", "cache_seq", "head_dim")}
    ax = ("layers", "cache_batch", "cache_seq", "kv_heads", "head_dim")
    out = {"k": ax, "v": ax}
    if TU.get().kv_cache_quant:
        sax = ("layers", "cache_batch", "cache_seq", "kv_heads")
        out["k_scale"] = sax
        out["v_scale"] = sax
    return out


def n_shared_invocations(cfg: ModelConfig) -> int:
    return -(-cfg.n_layers // cfg.ssm.attn_every)


def init_cache(cfg: ModelConfig, batch: int, seq: int, enc_seq: int = 0,
               dtype=jnp.bfloat16):
    fam = cfg.family
    cache: dict = {"len": jnp.zeros((batch,), jnp.int32)}
    if fam in ("dense", "vlm"):
        cache["layers"] = _attn_cache_zeros(cfg, cfg.n_layers, batch, seq, dtype)
    elif fam == "moe":
        nd = cfg.moe.first_dense_layers
        if nd:
            cache["dense_layers"] = _attn_cache_zeros(cfg, nd, batch, seq, dtype)
        cache["layers"] = _attn_cache_zeros(cfg, cfg.n_layers - nd, batch,
                                            seq, dtype)
    elif fam == "hybrid":
        st = SSM.init_mamba_state(cfg, batch, dtype)
        cache["layers"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), st)
        I = n_shared_invocations(cfg)
        KH, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        cache["shared"] = {
            "k": jnp.zeros((I, batch, seq, KH, hd), dtype),
            "v": jnp.zeros((I, batch, seq, KH, hd), dtype)}
    elif fam == "ssm":
        st = SSM.init_rwkv_state(cfg, batch, dtype)
        cache["layers"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), st)
    elif fam == "audio":
        cache["layers"] = _attn_cache_zeros(cfg, cfg.n_layers, batch, seq, dtype)
        KH, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        cache["cross"] = {
            "k": jnp.zeros((cfg.n_layers, batch, enc_seq, KH, hd), dtype),
            "v": jnp.zeros((cfg.n_layers, batch, enc_seq, KH, hd), dtype)}
    else:
        raise ValueError(fam)
    return cache


def cache_axes(cfg: ModelConfig):
    fam = cfg.family
    ax: dict = {"len": ("cache_batch",)}
    if fam in ("dense", "vlm"):
        ax["layers"] = _attn_cache_axes(cfg)
    elif fam == "moe":
        if cfg.moe.first_dense_layers:
            ax["dense_layers"] = _attn_cache_axes(cfg)
        ax["layers"] = _attn_cache_axes(cfg)
    elif fam == "hybrid":
        ax["layers"] = jax.tree.map(lambda a: ("layers",) + a,
                                    SSM.mamba_state_axes(cfg),
                                    is_leaf=lambda x: isinstance(x, tuple))
        a = ("layers", "cache_batch", "cache_seq", "kv_heads", "head_dim")
        ax["shared"] = {"k": a, "v": a}
    elif fam == "ssm":
        ax["layers"] = jax.tree.map(lambda a: ("layers",) + a,
                                    SSM.rwkv_state_axes(cfg),
                                    is_leaf=lambda x: isinstance(x, tuple))
    elif fam == "audio":
        ax["layers"] = _attn_cache_axes(cfg)
        a = ("layers", "cache_batch", "cache_seq", "kv_heads", "head_dim")
        ax["cross"] = {"k": a, "v": a}
    return ax


# =====================================================================
# decode bodies
# =====================================================================
def write_token(stack, new, pos, layer=None):
    """Write each slot's new cache entries at ``[layer, b, pos[b]]`` of
    the stacked ``(L,B,S,...)`` cache leaves: one dynamic-update-slice
    per slot and leaf, in place when the cache is donated. ``new``
    leaves are ``(L,B,...)`` for every layer (``layer=None``), or
    ``(B,...)`` for the one layer ``layer``. A position past the end is
    clamped to the last one; only a free slot's length gets there."""
    def put(c, n):
        if layer is not None:
            n = n[None]
        for b in range(pos.shape[0]):
            start = (0 if layer is None else layer, b, pos[b])
            c = jax.lax.dynamic_update_slice(
                c, n[:, b, None, None], start + (0,) * (c.ndim - 3))
        return c
    with jax.named_scope("attention"), jax.named_scope("kv_write"):
        return jax.tree.map(put, stack, new)


def _attn_decode_layer(lp, x, cfg, cache_slice, pos):
    """Single-token attention for one layer. cache_slice: this layer's
    k/v (B,S,KH,hd) (or MLA latents), only read. Returns (x', new): the
    token's own entries, (B,KH,hd) per leaf."""
    x = shard(x, ("batch", "embed_act"))
    h = L.apply_norm(lp["ln1"], x, cfg)
    attend = L.mla_decode if cfg.mla else L.attention_decode
    a, new = attend(lp["attn"], h, cfg, {**cache_slice, "len": pos})
    return x + a, new


def _dense_decode_layer(lp, x, cfg, cache_slice, pos):
    x, new = _attn_decode_layer(lp, x, cfg, cache_slice, pos)
    h = L.apply_norm(lp["ln2"], x, cfg)
    return x + L.apply_mlp(lp["mlp"], h, cfg), new


def _moe_decode_layer(lp, x, cfg, cache_slice, pos):
    x, new = _attn_decode_layer(lp, x, cfg, cache_slice, pos)
    h = L.apply_norm(lp["ln2"], x, cfg)
    # lossless capacity (C = n tokens) at decode: no token dropping
    y, _ = MOE.apply_moe(lp["moe"], h[:, None], cfg, capacity=x.shape[0])
    return x + y[:, 0], new


def _decode_stack(layer_fn, x, cfg, layer_params, stack, pos):
    """Scan ``layer_fn`` over a stacked attention cache. The scan reads
    each layer's cache and emits only the new token's entries, which
    ``write_token`` then writes into the stack once, after the scan.
    Returns (x, the updated stack)."""
    def body(x, xs):
        lp, cs = xs
        return layer_fn(lp, x, cfg, cs, pos)
    x, new = jax.lax.scan(body, x, (layer_params, stack))
    return x, write_token(stack, new, pos)


def decode_step(params, cfg: ModelConfig, cache, tokens):
    """tokens: (B,) int32 (or (B,d) embeddings for pure-embed families).
    Returns (new_cache, logits (B, V)).

    A stacked attention cache is read where attention needs it and
    written once, at each slot's new position: jit with the cache
    donated and that write happens in place."""
    fam = cfg.family
    x = params["embed"][tokens].astype(jnp.bfloat16)
    pos = cache["len"]
    new_cache = {"len": pos + 1}

    if fam in ("dense", "vlm"):
        x, new_cache["layers"] = _decode_stack(
            _dense_decode_layer, x, cfg, params["layers"], cache["layers"],
            pos)
    elif fam == "moe":
        if cfg.moe.first_dense_layers:
            x, new_cache["dense_layers"] = _decode_stack(
                _dense_decode_layer, x, cfg, params["dense_layers"],
                cache["dense_layers"], pos)
        x, new_cache["layers"] = _decode_stack(
            _moe_decode_layer, x, cfg, params["layers"], cache["layers"],
            pos)
    elif fam == "hybrid":
        shared = params["shared_attn"]
        every = cfg.ssm.attn_every
        sk, sv = cache["shared"]["k"], cache["shared"]["v"]

        def body(carry, xs):
            x, idx, inv, sk, sv = carry
            lp, st = xs

            def with_attn(op):
                x, sk, sv, inv = op
                h = L.apply_norm(shared["ln"], x, cfg)
                a, new = L.attention_decode(
                    shared["attn"], h, cfg,
                    {"k": sk[inv], "v": sv[inv], "len": pos})
                kv = write_token({"k": sk, "v": sv}, new, pos, inv)
                return x + a, kv["k"], kv["v"], inv + 1

            x, sk, sv, inv = jax.lax.cond(
                idx % every == 0, with_attn, lambda op: op, (x, sk, sv, inv))
            h = L.apply_norm(lp["ln1"], x, cfg)
            m, new_st = SSM.mamba2_step(lp["mamba"], h, cfg, st)
            x = x + m
            h = L.apply_norm(lp["ln2"], x, cfg)
            x = x + L.apply_mlp(lp["mlp"], h, cfg)
            return (x, idx + 1, inv, sk, sv), new_st

        (x, _, _, sk, sv), new_states = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                   sk, sv), (params["layers"], cache["layers"]))
        new_cache["layers"] = new_states
        new_cache["shared"] = {"k": sk, "v": sv}
    elif fam == "ssm":
        def body(x, xs):
            lp, st = xs
            h = L.apply_norm(lp["ln1"], x, cfg)
            t, tstate = SSM.rwkv_time_mix_step(lp["time"], h, cfg, st["time"])
            x = x + t
            h = L.apply_norm(lp["ln2"], x, cfg)
            c, cshift = SSM.rwkv_channel_mix(lp["channel"], h,
                                             st["channel_shift"])
            return x + c, {"time": tstate, "channel_shift": cshift}
        x, new = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
        new_cache["layers"] = new
    elif fam == "audio":
        x = x + params["pos_embed"][pos].astype(x.dtype)   # (B,d) gather
        Te = cache["cross"]["k"].shape[2]

        def body(x, xs):
            lp, cs, xk, xv = xs
            y, new = _attn_decode_layer(lp, x, cfg, cs, pos)
            h = L.apply_norm(lp["ln_x"], y, cfg)
            q = jnp.einsum("bd,dhk->bhk", h, lp["xattn"]["wq"])
            a = L.decode_attention(q, xk, xv, Te)
            y = y + jnp.einsum("bhk,hkd->bd", a, lp["xattn"]["wo"])
            h = L.apply_norm(lp["ln2"], y, cfg)
            return y + L.apply_mlp(lp["mlp"], h, cfg), new

        x, new = jax.lax.scan(body, x, (params["layers"], cache["layers"],
                                        cache["cross"]["k"],
                                        cache["cross"]["v"]))
        new_cache["layers"] = write_token(cache["layers"], new, pos)
        new_cache["cross"] = cache["cross"]
    else:
        raise ValueError(fam)

    x = L.apply_norm(params["final_norm"], x[:, None], cfg)[:, 0]
    logits = T.lm_head(params, cfg, x)
    return new_cache, logits


# =====================================================================
# prefill: full-sequence forward that also fills the cache
# =====================================================================
def _attn_prefill_layer(lp, x, cfg, positions):
    h = L.apply_norm(lp["ln1"], x, cfg)
    if cfg.mla:
        a, (ckv, kr) = L.mla_train(lp["attn"], h, cfg, positions)
        return x + a, {"ckv": ckv, "kr": kr}
    a, (k, v) = L.attention_train(lp["attn"], h, cfg, positions)
    return x + a, {"k": k, "v": v}


def _pad_cache_seq(kv_tree, seq_total):
    """Pad per-layer (L,B,T,...) KV stacks up to the cache length S,
    quantizing to the INT8 paged layout when tuned."""
    from repro.models import tuning as TU
    def pad(a):
        pad_amt = seq_total - a.shape[2]
        cfgs = [(0, 0)] * a.ndim
        cfgs[2] = (0, pad_amt)
        return jnp.pad(a, cfgs)
    kv_tree = jax.tree.map(pad, kv_tree)
    if TU.get().kv_cache_quant and "k" in kv_tree:
        out = {}
        for name in ("k", "v"):
            a = kv_tree[name]
            sc = jnp.max(jnp.abs(a), -1) / 127.0 + 1e-8
            out[name] = jnp.round(a / sc[..., None]).astype(jnp.int8)
            out[name + "_scale"] = sc.astype(jnp.float16)
        return out
    return kv_tree


def prefill(params, cfg: ModelConfig, batch, cache_seq: int,
            remat: str = "full"):
    """Process the prompt, return (cache, last-token logits (B,V))."""
    fam = cfg.family
    if cfg.embedding_inputs and "embeddings" in batch:
        x = batch["embeddings"].astype(jnp.bfloat16)
    elif fam == "audio":
        x = None
    else:
        x = T.embed_tokens(params, cfg, batch["tokens"])

    if fam in ("dense", "vlm", "moe"):
        B, Tq, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(Tq), (B, Tq))
        cache: dict = {"len": jnp.full((B,), Tq, jnp.int32)}

        def run_stack(x, stack_params, is_moe):
            def body(x, lp):
                x, kv = _attn_prefill_layer(lp, x, cfg, positions)
                h = L.apply_norm(lp["ln2"], x, cfg)
                if is_moe:
                    # serving path: LOSSLESS routing (no capacity drops),
                    # consistent with the lossless decode step
                    y, _ = MOE.apply_moe(lp["moe"], h, cfg,
                                         capacity=B * Tq)
                else:
                    y = L.apply_mlp(lp["mlp"], h, cfg)
                return x + y, kv
            return jax.lax.scan(T._remat(body, remat), x, stack_params)

        if fam == "moe" and cfg.moe.first_dense_layers:
            x, kvd = run_stack(x, params["dense_layers"], False)
            cache["dense_layers"] = _pad_cache_seq(kvd, cache_seq)
        x, kv = run_stack(x, params["layers"], fam == "moe")
        cache["layers"] = _pad_cache_seq(kv, cache_seq)
    elif fam in ("ssm", "hybrid"):
        # recurrent prefill: run the train forward, but KEEP final states
        B, Tq, _ = x.shape
        cache = init_cache(cfg, B, cache_seq)
        cache["len"] = jnp.full((B,), Tq, jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(Tq), (B, Tq))
        if fam == "ssm":
            st0 = SSM.init_rwkv_state(cfg, B, x.dtype)
            def body(x, xs):
                lp = xs
                y, st = T.rwkv_layer_fwd(lp, x, cfg, st0)
                return y, st
            x, states = jax.lax.scan(T._remat(body, remat), x,
                                     params["layers"])
            cache["layers"] = states
        else:
            shared = params["shared_attn"]
            every = cfg.ssm.attn_every
            st0 = SSM.init_mamba_state(cfg, B)
            sk, sv = cache["shared"]["k"], cache["shared"]["v"]

            def body(carry, lp):
                # shared KV caches live in the carry: only the I invocation
                # layers write (avoids materializing 81 layers of KV).
                x, idx, inv, sk, sv = carry

                def with_attn(op):
                    x, sk, sv, inv = op
                    h = L.apply_norm(shared["ln"], x, cfg)
                    a, (k, v) = L.attention_train(shared["attn"], h, cfg,
                                                  positions)
                    k = jnp.pad(k, ((0, 0), (0, cache_seq - Tq),
                                    (0, 0), (0, 0))).astype(sk.dtype)
                    v = jnp.pad(v, ((0, 0), (0, cache_seq - Tq),
                                    (0, 0), (0, 0))).astype(sv.dtype)
                    sk = jax.lax.dynamic_update_index_in_dim(sk, k, inv, 0)
                    sv = jax.lax.dynamic_update_index_in_dim(sv, v, inv, 0)
                    return x + a, sk, sv, inv + 1

                x, sk, sv, inv = jax.lax.cond(
                    idx % every == 0, with_attn, lambda op: op,
                    (x, sk, sv, inv))
                y, st = T.mamba_layer_fwd(lp, x, cfg, st0)
                return (y, idx + 1, inv, sk, sv), st

            (x, _, _, sk, sv), states = jax.lax.scan(
                T._remat(body, remat),
                (x, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                 sk, sv), params["layers"])
            cache["layers"] = states
            cache["shared"] = {"k": sk, "v": sv}
    elif fam == "audio":
        frames = batch["audio_frames"].astype(jnp.bfloat16)
        B, Te, _ = frames.shape
        pos_e = jnp.broadcast_to(jnp.arange(Te), (B, Te))
        enc_body = T._remat(lambda x, lp: (
            T.dense_layer_fwd_nocausal(lp, x, cfg, pos_e), None), remat)
        enc, _ = jax.lax.scan(enc_body, frames, params["enc_layers"])
        enc = L.apply_norm(params["enc_norm"], enc, cfg)

        tokens = batch["tokens"]
        Bd, Td = tokens.shape
        x = T.embed_tokens(params, cfg, tokens)
        x = x + params["pos_embed"][:Td].astype(x.dtype)
        pos_d = jnp.broadcast_to(jnp.arange(Td), (Bd, Td))

        def dec_body(x, lp):
            x, kv = _attn_prefill_layer(lp, x, cfg, pos_d)
            h = L.apply_norm(lp["ln_x"], x, cfg)
            kx = jnp.einsum("btd,dhk->bthk", enc, lp["xattn"]["wk"])
            vx = jnp.einsum("btd,dhk->bthk", enc, lp["xattn"]["wv"])
            a, _ = L.attention_train(lp["xattn"], h, cfg, pos_d,
                                     causal=False, kv=(kx, vx))
            x = x + a
            h = L.apply_norm(lp["ln2"], x, cfg)
            return x + L.apply_mlp(lp["mlp"], h, cfg), (kv, kx, vx)

        x, (kv, kxs, vxs) = jax.lax.scan(T._remat(dec_body, remat), x,
                                         params["layers"])
        cache = {"len": jnp.full((Bd,), Td, jnp.int32),
                 "layers": _pad_cache_seq(kv, cache_seq),
                 "cross": {"k": kxs, "v": vxs}}
    else:
        raise ValueError(fam)

    x = L.apply_norm(params["final_norm"], x[:, -1:], cfg)[:, 0]
    logits = T.lm_head(params, cfg, x)
    return cache, logits
