"""Ahead-of-time compiles for a described TPU v5e chip at qwen2-0.5b
widths: the three Pallas kernels and the full-width decode step.

Nothing runs; the TPU compiler accepts or refuses each program, which
catches the Mosaic layout and VMEM errors that interpret mode cannot.
The topology is described inside a fixture (never at import), and the
persistent compilation cache is off around these compiles, because an
entry written for a described chip cannot be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.model import Model

B, H, KH, D, PAGE, MAX_PAGES = 8, 14, 2, 64, 8, 256   # 8 slots x 2048
SEQ = PAGE * MAX_PAGES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,k,n", [(256, 896, 4864),    # MLP up-proj
                                   (8, 4864, 896)])     # decode down-proj
def test_streaming_gemm_compiles(one_chip, m, k, n):
    compiled = ops.streaming_gemm.lower(
        _spec(one_chip, (m, k)), _spec(one_chip, (k, n))).compile()
    _assert_kernel(compiled)


def test_flash_attention_compiles(one_chip):
    q = _spec(one_chip, (1, 512, H, D))
    kv = _spec(one_chip, (1, 512, KH, D))
    compiled = ops.flash_attention.lower(q, kv, kv, causal=True).compile()
    _assert_kernel(compiled)


def test_paged_attention_compiles(one_chip):
    pool = _spec(one_chip, (B * MAX_PAGES, PAGE, KH, D))
    compiled = ops.paged_attention.lower(
        _spec(one_chip, (B, H, D)), pool, pool,
        _spec(one_chip, (B, MAX_PAGES), jnp.int32),
        _spec(one_chip, (B,), jnp.int32)).compile()
    _assert_kernel(compiled)


def _qwen2_decode(one_chip, **jit_kw):
    model = Model(get_config("qwen2-0.5b"), remat="none")
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = place(model.abstract_params())
    cache = place(jax.eval_shape(lambda: model.init_cache(B, SEQ)))
    return jax.jit(model.decode_step, **jit_kw).lower(
        params, cache, _spec(one_chip, (B,), jnp.int32)).compile()


def test_qwen2_decode_step_compiles(one_chip):
    mem = _qwen2_decode(one_chip).memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_qwen2_decode_step_copies_no_cache_when_donated(one_chip):
    """With the cache donated, attention reads each layer's K/V as it
    lies and the new token is written in place: no copy in the program
    has the cache's sequence length, and no temporary is cache-sized."""
    compiled = _qwen2_decode(one_chip, donate_argnums=(1,))
    copies = re.findall(r"\[([\d,]+)\]\{[^}]*\} copy\(", compiled.as_text())
    assert copies and not [c for c in copies
                           if str(SEQ) in c.split(",")]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < mem.alias_size_in_bytes / 100
