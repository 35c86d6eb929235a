"""Seeded random weights of a dense GQA decoder, made by the benchmark.

The program under test is handed these weights; the reference makes
the same numbers again, one layer at a time, from the same seed. So
the reference takes nothing that the program has made.

Every leaf is drawn from its own key, ``fold_in(root, crc32(path))``,
and every layer of a stacked leaf from ``fold_in(leaf_key, layer)``.
The layout (paths, shapes, dtypes) is the one the program's parameter
tree has; ``program.make_params`` checks that they agree.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int):
    """A JAX key from any whole number, however large."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


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


def _draw(key, shape, dtype, law, scale):
    z = jax.random.normal(key, shape, jnp.float32)
    if law == "norm":
        z = 1.0 + 0.1 * z
    elif law == "bias":
        z = 0.02 * z
    else:
        z = z * scale
    return z.astype(dtype)


def _leaf_key(root, path: str):
    return jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw_leaf(root, path: str, spec, layer=None):
    """One leaf, or one layer of a stacked leaf when ``layer`` is given."""
    shape, dtype, law, scale, stacked = spec
    k = _leaf_key(root, path)
    if layer is None and not stacked:
        return _draw(k, shape, dtype, law, scale)
    if layer is not None:
        return _draw(jax.random.fold_in(k, layer), shape[1:], dtype, law,
                     scale)
    return jax.lax.map(
        lambda i: _draw(jax.random.fold_in(k, i), shape[1:], dtype, law,
                        scale), jnp.arange(shape[0]))


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = v
    return out


def make_all(m: dict, seed: int) -> dict:
    """Every leaf on the device, in the type it is served in, from one
    jitted call. Returns the nested tree."""
    specs = leaf_specs(m)

    @jax.jit
    def build(root):
        return nest({p: draw_leaf(root, p, s) for p, s in specs.items()})

    return build(root_key(seed))
