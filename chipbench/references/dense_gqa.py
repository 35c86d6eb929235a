"""Plain reference of a dense decoder with grouped-query attention.

Pre-norm RMSNorm blocks, RoPE on the first ``rotary_dim`` dimensions of
each head (interleaved pairs), causal softmax attention where head h
reads kv head h // (heads / kv_heads), and a SwiGLU MLP. The whole
forward pass in float32 at ``highest`` matmul precision, no cache, no
batching tricks, one layer at a time, with weights made again from the
seed by ``chipbench.weights``. It imports nothing of the program.

``control=True`` computes the same forward with every matrix product's
operands rounded to float8 (e4m3, one scale per row of the activations
and per output column of the weights): the next precision below the
bfloat16 that the configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

Q_BLOCK = 256
ROW_BLOCK = 256
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, control):
    """x (..., k) @ w (k, n) in float32; float8 operands for control."""
    if control:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, rot, theta):
    """x (B, T, H, D): rotate pairs (0,1), (2,3), ... of the first rot."""
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                             / rot))
    ang = pos[:, :, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([y.reshape(x[..., :rot].shape), x[..., rot:]],
                           -1)


def _layer(m, control, w, x):
    B, T, d = x.shape
    H, KH, hd = m["heads"], m["kv_heads"], m["head_dim"]
    G = H // KH
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.float32), (B, T))
    h = _rms(x, w["ln1"], m["norm_eps"])
    q = _mm(h, w["wq"].reshape(d, H * hd), control).reshape(B, T, H, hd)
    k = _mm(h, w["wk"].reshape(d, KH * hd), control).reshape(B, T, KH, hd)
    v = _mm(h, w["wv"].reshape(d, KH * hd), control).reshape(B, T, KH, hd)
    if m["qkv_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q, pos, m["rotary_dim"], m["rope_theta"])
    k = _rope(k, pos, m["rotary_dim"], m["rope_theta"])
    qg = q.reshape(B, T // Q_BLOCK, Q_BLOCK, KH, G, hd)

    def block(i):
        qb = qg[:, i]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k,
                       precision="highest") / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision="highest")

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK))   # (nb,B,qb,KH,G,hd)
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, H * hd)
    x = x + _mm(o, w["wo"].reshape(H * hd, d), control)
    h = _rms(x, w["ln2"], m["norm_eps"])
    f = jax.nn.silu(_mm(h, w["wi_gate"], control)) * _mm(h, w["wi_up"],
                                                          control)
    return x + _mm(f, w["wo_mlp"], control)


_LAYER_LEAVES = {"ln1": "layers/ln1/scale", "ln2": "layers/ln2/scale",
                 "wq": "layers/attn/wq", "wk": "layers/attn/wk",
                 "wv": "layers/attn/wv", "wo": "layers/attn/wo",
                 "bq": "layers/attn/bq", "bk": "layers/attn/bk",
                 "bv": "layers/attn/bv", "wi_gate": "layers/mlp/wi_gate",
                 "wi_up": "layers/mlp/wi_up", "wo_mlp": "layers/mlp/wo"}


def _frozen(m):
    return tuple(sorted(m.items()))


@functools.lru_cache(maxsize=None)
def _fns(mf):
    m = dict(mf)
    specs = weights.leaf_specs(m)
    names = {k: p for k, p in _LAYER_LEAVES.items() if p in specs}

    @jax.jit
    def layer_weights(root, i):
        return {k: weights.draw_leaf(root, p, specs[p], layer=i)
                for k, p in names.items()}

    @jax.jit
    def embed(root, tokens):
        e = weights.draw_leaf(root, "embed", specs["embed"])
        return e[tokens].astype(jnp.float32)

    @jax.jit
    def head(root):
        """The (d, vocab) output matrix in float32."""
        if m["tied_embeddings"]:
            e = weights.draw_leaf(root, "embed", specs["embed"])
            return e[:m["vocab"]].astype(jnp.float32).T
        h = weights.draw_leaf(root, "lm_head", specs["lm_head"])
        return h[:, :m["vocab"]].astype(jnp.float32)

    @jax.jit
    def final_norm(root, x):
        g = weights.draw_leaf(root, "final_norm/scale",
                              specs["final_norm/scale"])
        return _rms(x, g.astype(jnp.float32), m["norm_eps"])

    layers = {c: jax.jit(functools.partial(_layer, m, c))
              for c in (False, True)}

    return layer_weights, embed, head, final_norm, layers


def final_hidden(m: dict, seed: int, tokens: np.ndarray, control=False):
    """(B, T) token ids -> (B, T, d) final normed hidden states, f32.
    T must be a multiple of Q_BLOCK; padding at the end is harmless
    because attention is causal."""
    layer_weights, embed, _, final_norm, layers = _fns(_frozen(m))
    root = weights.root_key(seed)
    x = embed(root, jnp.asarray(tokens))
    for i in range(m["layers"]):
        x = layers[control](layer_weights(root, i), x)
    return final_norm(root, x)


@functools.lru_cache(maxsize=None)
def _gap_fns(mf):
    @jax.jit
    def gap_of(hid_ref, hmat, toks):
        lg = jnp.matmul(hid_ref, hmat, precision="highest")
        return jnp.max(lg, -1) - jnp.take_along_axis(
            lg, toks[:, None], -1)[:, 0]

    @jax.jit
    def pick(hid, hmat):
        return jnp.argmax(_mm(hid, hmat, True), -1).astype(jnp.int32)

    return gap_of, pick


def gaps(m: dict, seed: int, prompts: list, outputs: list,
         control: bool = False):
    """For each request (prompt ids, served ids): per served token, the
    gap by which the reference's logit of that token lies below the
    reference's best. With ``control`` also, per position, the gap of
    the token that the float8 forward puts first; returns both lists."""
    _, _, head, _, _ = _fns(_frozen(m))
    gap_of, pick = _gap_fns(_frozen(m))
    lens = [len(p) + len(o) - 1 for p, o in zip(prompts, outputs)]
    T = -(-max(lens) // 1024) * 1024
    toks = np.zeros((len(prompts), T), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        seq = np.concatenate([p, o])[:-1]
        toks[i, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        hid = final_hidden(m, seed, toks)
        hid_c = final_hidden(m, seed, toks, control=True) if control \
            else None
        hmat = head(weights.root_key(seed))
        served, ctl = [], []
        for i, (p, o) in enumerate(zip(prompts, outputs)):
            n, start = len(o), len(p) - 1
            gs, gc = [], []
            for b in range(0, n, ROW_BLOCK):
                k = min(ROW_BLOCK, n - b)
                rows = np.minimum(np.arange(start + b, start + b + ROW_BLOCK),
                                  T - 1)
                t = np.zeros(ROW_BLOCK, np.int32)
                t[:k] = o[b:b + k]
                h = hid[i, rows]
                gs.append(np.asarray(gap_of(h, hmat, jnp.asarray(t)))[:k])
                if control:
                    c = pick(hid_c[i, rows], hmat)
                    gc.append(np.asarray(gap_of(h, hmat, c))[:k])
            served.append(np.concatenate(gs))
            if control:
                ctl.append(np.concatenate(gc))
    return (served, ctl) if control else served
