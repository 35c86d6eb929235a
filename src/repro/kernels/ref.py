"""Pure-jnp oracles for every kernel (the correctness ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gemm_ref(a, b, out_dtype=None):
    out_dtype = out_dtype or a.dtype
    acc = jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer) else jnp.float32
    return jnp.dot(a, b, preferred_element_type=acc).astype(out_dtype)


def flash_ref(q, k, v, causal=True):
    """q: (BH, Tq, D); k, v: (BH, Tk, D)."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    if causal:
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def gqa_flash_ref(q, k, v, causal=True):
    """q: (B, Tq, H, D); k, v: (B, Tk, KH, D) — the layout of
    ``ops.flash_attention``: q head kh*G + g reads KV head kh.  Folds the
    heads into ``flash_ref``'s batch axis."""
    B, Tq, H, D = q.shape
    Tk, KH = k.shape[1], k.shape[2]
    G = H // KH
    qf = q.reshape(B, Tq, KH, G, D).transpose(0, 2, 3, 1, 4).reshape(-1, Tq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(-1, Tk, D), G, axis=0)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(-1, Tk, D), G, axis=0)
    return flash_ref(qf, kf, vf, causal).reshape(B, KH, G, Tq, D) \
        .transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, D)


def paged_ref(q, k_pages, v_pages, table, lens):
    """Gather pages into contiguous caches, then masked attention."""
    B, H, D = q.shape
    P, page, KH, _ = k_pages.shape
    max_pages = table.shape[1]
    G = H // KH
    k = k_pages[table].reshape(B, max_pages * page, KH, D)
    v = v_pages[table].reshape(B, max_pages * page, KH, D)
    qg = q.reshape(B, KH, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k.astype(jnp.float32)
                   ) / math.sqrt(D)
    valid = jnp.arange(max_pages * page)[None] < lens[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)
