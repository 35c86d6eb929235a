"""Jit'd public wrappers around the Pallas kernels: padding to block
multiples, block-size selection via the paper's overlap bound
(core.overlap) and GQA head folding.  Every wrapper compiles the kernel
for the TPU unless the caller passes ``interpret=True`` (the Pallas
interpreter, which is how the CPU tests run them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.overlap import choose_gemm_blocks
from repro.kernels.flash_attention import flash_attention_raw
from repro.kernels.paged_attention import paged_attention_raw
from repro.kernels.streaming_gemm import streaming_gemm_raw


def _round_up(x, m):
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def streaming_gemm(a, b, bm: int | None = None, bn: int | None = None,
                   bk: int | None = None,
                   interpret: bool = False):
    """Paged streaming GEMM with automatic padding to block multiples.

    Block sizes default to the unified page-aligned overlap-bound
    chooser (``core.overlap.choose_gemm_blocks``); pass explicit
    bm/bn/bk to override."""
    M, K = a.shape
    _, N = b.shape
    if bm is None or bn is None or bk is None:
        cm, cn, ck = choose_gemm_blocks(M, N, K, a.dtype)
        bm, bn, bk = bm or cm, bn or cn, bk or ck
    bm, bn, bk = min(bm, _round_up(M, 8)), min(bn, _round_up(N, 128)), \
        min(bk, _round_up(K, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    ap = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    bp = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    out = streaming_gemm_raw(ap, bp, bm=bm, bn=bn, bk=bk,
                             interpret=interpret)
    return out[:M, :N]


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 256,
                    bk: int = 512, interpret: bool = False):
    """q: (B, Tq, H, D); k, v: (B, Tk, KH, D) — GQA folded internally."""
    B, Tq, H, D = q.shape
    _, Tk, KH, _ = k.shape
    G = H // KH
    # fold batch × kv-head × group -> BH; repeat kv per group
    qf = q.reshape(B, Tq, KH, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KH * G, Tq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(B * KH, Tk, D), G,
                    axis=0)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(B * KH, Tk, D), G,
                    axis=0)
    bq_, bk_ = min(bq, Tq), min(bk, Tk)
    Tqp, Tkp = _round_up(Tq, bq_), _round_up(Tk, bk_)
    qf = jnp.pad(qf, ((0, 0), (0, Tqp - Tq), (0, 0)))
    kf = jnp.pad(kf, ((0, 0), (0, Tkp - Tk), (0, 0)))
    vf = jnp.pad(vf, ((0, 0), (0, Tkp - Tk), (0, 0)))
    # padded KV rows must not contribute: they are masked by causal for
    # qpos < Tk; for non-causal, mask via a huge negative on padded keys
    if not causal and Tkp != Tk:
        raise NotImplementedError("pad-free Tk required for non-causal")
    out = flash_attention_raw(qf, kf, vf, bq=bq_, bk=bk_, causal=causal,
                              interpret=interpret)
    out = out[:, :Tq].reshape(B, KH, G, Tq, D).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Tq, H, D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, table, lens,
                    interpret: bool = False):
    return paged_attention_raw(q, k_pages, v_pages, table, lens,
                               interpret=interpret)
