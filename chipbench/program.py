"""The one place the benchmark touches the program under test.

It builds the program's ``ModelConfig`` from a configuration file's
``model`` group, hands the program the benchmark's own weights, and
makes the serving engine. Everything else in the benchmark works on
plain data.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax

from chipbench import weights

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from repro.configs.base import ModelConfig  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402,F401

# The program's RMSNorm epsilon is fixed in code (models/layers.py).
PROGRAM_NORM_EPS = 1e-5


def model_config(name: str, m: dict) -> ModelConfig:
    """The program's config for model group ``m``. Raises where the
    program cannot run what the group states."""
    if m["dtype"] != "bfloat16":
        raise ValueError(f"{name}: the program serves bfloat16 only")
    if m["norm_eps"] != PROGRAM_NORM_EPS:
        raise ValueError(f"{name}: the program's RMSNorm epsilon is "
                         f"{PROGRAM_NORM_EPS}, the config states "
                         f"{m['norm_eps']}")
    if m["rotary_dim"] == m["head_dim"]:
        rope = "full"
    elif 2 * m["rotary_dim"] == m["head_dim"]:
        rope = "2d"
    else:
        raise ValueError(f"{name}: rotary_dim {m['rotary_dim']} is "
                         f"neither head_dim nor half of it")
    cfg = ModelConfig(
        name=name, family="dense", n_layers=m["layers"],
        d_model=m["d_model"], n_heads=m["heads"], n_kv_heads=m["kv_heads"],
        d_ff=m["d_ff"], vocab_size=m["vocab"], head_dim=m["head_dim"],
        qkv_bias=m["qkv_bias"], rope=rope, rope_theta=m["rope_theta"],
        tie_embeddings=m["tied_embeddings"],
        vocab_pad_multiple=m.get("vocab_pad_multiple", 256))
    if cfg.padded_vocab != m["vocab_padded"]:
        raise ValueError(f"{name}: the program pads the vocabulary to "
                         f"{cfg.padded_vocab}, not {m['vocab_padded']}")
    return cfg


def make_params(cfg: ModelConfig, m: dict, seed: int):
    """The benchmark's weights, checked against the program's layout."""
    want = jax.eval_shape(Model(cfg, remat="none").init,
                          jax.random.PRNGKey(0))
    got = weights.make_all(m, seed)
    w_leaves = jax.tree_util.tree_leaves_with_path(want)
    g_struct = jax.tree.structure(got)
    if jax.tree.structure(want) != g_struct:
        raise ValueError(f"parameter layout differs: program "
                         f"{jax.tree.structure(want)}, benchmark {g_struct}")
    for (path, w), g in zip(w_leaves, jax.tree.leaves(got)):
        if w.shape != g.shape or w.dtype != g.dtype:
            raise ValueError(f"leaf {jax.tree_util.keystr(path)}: program "
                             f"{w.shape} {w.dtype}, benchmark "
                             f"{g.shape} {g.dtype}")
    return got


def make_engine(cfg: ModelConfig, params, *, slots: int, max_seq: int):
    return ServingEngine(cfg, params, slots=slots, max_seq=max_seq)
