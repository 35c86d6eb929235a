"""The one place the benchmark touches the program under test.

It builds the program's ``ModelConfig`` from a configuration file's
``model`` group, through the group's family (``families/<family>.py``,
which is handed ``repro.configs.base`` and imports nothing of the
program), hands the program the benchmark's own weights, and makes the
serving engine. Everything else in the benchmark works on
plain data.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax

from chipbench import spec, weights

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from repro.configs import base as program_base  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402,F401

# The program's RMSNorm epsilon is fixed in code (models/layers.py).
PROGRAM_NORM_EPS = 1e-5


def model_config(name: str, m: dict) -> ModelConfig:
    """The program's config for model group ``m``, as its family builds
    it. Raises where the program cannot run what the group states."""
    if m["dtype"] != "bfloat16":
        raise ValueError(f"{name}: the program serves bfloat16 only")
    if m["norm_eps"] != PROGRAM_NORM_EPS:
        raise ValueError(f"{name}: the program's RMSNorm epsilon is "
                         f"{PROGRAM_NORM_EPS}, the config states "
                         f"{m['norm_eps']}")
    cfg = spec.family_of(m).program_config(name, m, program_base)
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
