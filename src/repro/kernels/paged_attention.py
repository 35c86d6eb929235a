"""Paged decode attention — the serving-side embodiment of the paper's
SMMU/page-table design: the KV cache lives in fixed-size pages, a
per-sequence page table provides the indirection, and the kernel walks
the table exactly like the SMMU translates 4 KB-aligned DMA bursts.

The page table rides in scalar-prefetch memory (SMEM) so the index_map
can "translate" page ids BEFORE the DMA of each K/V page is issued —
one translation per page, just like one TLB lookup per 4 KB tile in the
paper (§3.3).

Shapes:
  q:        (B, H, D)          one decode token per sequence
  k_pages:  (P, page, KH, D)   global page pool (P pages)
  v_pages:  (P, page, KH, D)
  table:    (B, max_pages)     page ids per sequence (int32)
  lens:     (B,)               current KV length per sequence
Output: (B, H, D).

Grid: (B, max_pages) — pages innermost; online softmax in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page: int, max_pages: int,
                  scale: float, n_kv: int, head_dim: int):
    """One (sequence, page) grid step.  q/o blocks are (1, KH, G, D);
    K/V blocks are (1, page, KH*D) with KV head h in lanes
    [h*D, (h+1)*D).  Scratch is per KV head: acc (KH, G, D), running max
    and denominator (KH, G, 1) — every value stays 2-D per head, so
    Mosaic never has to re-lay a vector out across a reshape."""
    b, pi = pl.program_id(0), pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = lens_ref[b]
    n_pages_used = (seq_len + page - 1) // page

    @pl.when(pi < n_pages_used)
    def _step():
        for h in range(n_kv):
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, h].astype(jnp.float32)              # (G, D)
            k = k_ref[0, :, lanes].astype(jnp.float32)       # (page, D)
            v = v_ref[0, :, lanes].astype(jnp.float32)       # (page, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, page)
            pos = pi * page + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(pos < seq_len, s, NEG_INF)
            m_prev = m_ref[h]                                # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(pi == max_pages - 1)
    def _flush():
        for h in range(n_kv):
            o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
                           ).astype(o_ref.dtype)


def paged_attention_raw(q, k_pages, v_pages, table, lens, *,
                        interpret: bool = False):
    B, H, D = q.shape
    P, page, KH, _ = k_pages.shape
    _, max_pages = table.shape
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    # GQA folding happens here, outside the kernel: both reshapes only
    # regroup trailing dims, so they are free (no HBM copy of the pool)
    qg = q.reshape(B, KH, G, D)
    kf = k_pages.reshape(P, page, KH * D)
    vf = v_pages.reshape(P, page, KH * D)
    kernel = functools.partial(_paged_kernel, page=page,
                               max_pages=max_pages, scale=scale, n_kv=KH,
                               head_dim=D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # (table, lens) land in SMEM
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, KH, G, D),
                         lambda b, pi, table, lens: (b, 0, 0, 0)),
            # the SMMU moment: translate page id -> pool slot in index_map
            pl.BlockSpec((1, page, KH * D),
                         lambda b, pi, table, lens: (table[b, pi], 0, 0)),
            pl.BlockSpec((1, page, KH * D),
                         lambda b, pi, table, lens: (table[b, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KH, G, D),
                               lambda b, pi, table, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KH, G, D), jnp.float32),
            pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.VMEM((KH, G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        interpret=interpret,
    )(table, lens, qg, kf, vf)
    return out.reshape(B, H, D)
