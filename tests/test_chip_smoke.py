"""CPU checks of ``chip_smoke.py`` and the compile cache: the smoke
refuses to run without a TPU, its phases run at reduced size without
forking, its four-device training comparison holds on virtual devices
and fails when the sharded run drops half its batch, and the compile
cache directory is fixed."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_reduced
from repro.launch import compile_cache
from repro.launch.serve import serve

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_raises_on_cpu(smoke):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        smoke.require_tpu()


def test_smoke_script_fails_without_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(SMOKE)], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    cc.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path,
                                   restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_serve_rejects_short_max_seq():
    with pytest.raises(ValueError, match="cannot hold a 512-token prompt"):
        serve(get_reduced("qwen2-0.5b"), slots=2, max_seq=96, requests=1,
              new_tokens=4, seed=0)


def test_smoke_phases_fork_nothing(smoke, monkeypatch):
    """Serving and the logits comparison at reduced size: the run
    drains and no process is forked or spawned."""
    def refuse(*a, **k):
        raise AssertionError("the smoke must start no process")
    monkeypatch.setattr(os, "fork", refuse)
    monkeypatch.setattr(subprocess, "Popen", refuse)
    from repro.core import scenario
    monkeypatch.setattr(scenario, "_pool_executor", refuse)
    cfg = get_reduced(smoke.ARCH)
    params = smoke.phase_serving(cfg, 0)
    smoke.phase_logits(cfg, params, 0)


def _four_device_run(body: str) -> str:
    """Run ``body`` after ``import chip_smoke as S`` in a fresh process
    with four virtual CPU devices; return its stdout."""
    script = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke as S\n"
        "from repro.configs import get_reduced\n"
        "cfg = get_reduced(S.ARCH)\n" + body)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_sharded_training_matches_one_device():
    """The --chips 4 comparison at reduced size on four virtual CPU
    devices: parameters spread over all four, losses agree."""
    out = _four_device_run(
        "S.compare_sharded_training(cfg, 0, batch=8, seq=64, steps=3)\n"
        "print(__import__('json').dumps({'done': True}))\n")
    assert json.loads(out.strip().splitlines()[-1]) == {"done": True}
    assert "parameter bytes per device: 0: " in out


def test_sharded_training_catches_dropped_half_batch():
    """A planted data-axis fault: the 2x2 run's loss sees only the first
    half of each batch (data shard 0).  The comparison must raise."""
    out = _four_device_run(
        "import json\n"
        "from repro.models import model as M\n"
        "full_loss, full_run = M.Model.loss, S.train_losses\n"
        "def half_loss(self, p, batch):\n"
        "    return full_loss(self, p, {k: v[:v.shape[0] // 2]\n"
        "                               for k, v in batch.items()})\n"
        "def faulty_run(cfg, mesh, seed, **kw):\n"
        "    if mesh.devices.size > 1:\n"
        "        M.Model.loss = half_loss\n"
        "    try:\n"
        "        return full_run(cfg, mesh, seed, **kw)\n"
        "    finally:\n"
        "        M.Model.loss = full_loss\n"
        "S.train_losses = faulty_run\n"
        "try:\n"
        "    S.compare_sharded_training(cfg, 0, batch=8, seq=64, steps=3)\n"
        "except AssertionError as e:\n"
        "    print(json.dumps({'caught': str(e)}))\n")
    last = json.loads(out.strip().splitlines()[-1])
    assert "exceeds" in last["caught"], out
