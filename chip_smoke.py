"""Smoke run of the main path on TPU chips.

    python chip_smoke.py             # one chip: serving, logits, kernels
    python chip_smoke.py --chips 4   # 2x2 mesh training against one chip

One chip: qwen2-0.5b at its published width with seeded random weights
serves 16 requests through ``launch.serve.serve`` (``Model`` +
``ServingEngine``, 8 slots, max_seq 2048); the prefill logits of one
128-token prompt on the chip are compared with the same jitted prefill on
the host CPU; and the three Pallas kernels run compiled (never
interpreted) at qwen2-0.5b shapes against ``kernels/ref.py`` on the chip.

``--chips 4``: three training steps of qwen2-0.5b on a (data=2, model=2)
mesh and the same steps on a one-chip mesh; the loss sequences must agree
and the parameters must be spread over all four chips.

The lines before the last are smoke readings, not benchmark metrics.
The last line of stdout, printed only when every phase passed, is one
JSON object naming the device.  Any failure raises and exits non-zero.
Without a TPU the script fails before it runs anything.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import PROMPT_LENS, serve  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.runtime.train_loop import Trainer, TrainerConfig  # noqa: E402

ARCH = "qwen2-0.5b"
SEED = 0
SLOTS, MAX_SEQ, REQUESTS, NEW_TOKENS = 8, 2048, 16, 32
# Tolerances for bf16 (8-bit mantissa, one rounding = 2^-8 ~ 3.9e-3):
# - chip vs host prefill logits, relative L2 error: the two backends
#   round bf16 activations at different points in each of 24 layers;
LOGITS_TOL = 5e-2
# - kernel vs reference, max |error| over max |reference|: f32
#   accumulation in both, so a few bf16 roundings of the output apart;
KERNEL_TOL = 2e-2
# - 2x2 mesh vs one chip, per-step |loss difference| over the loss:
#   the same f32 loss with sharded reductions summed in another order
#   reads 1.6e-5 on four v5e chips and 1.8e-5 at reduced size on the
#   CPU; dropping half of the sharded run's batch reads 2.1e-2 on the
#   chips and 2.1e-3 on the CPU.
LOSS_TOL = 2e-4
# --chips 4 training shape: batch 8 splits over data=2, and with full
# remat the one-chip run needs about 9 GB of the chip's 16 GB.
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 3

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def require_tpu() -> jax.Device:
    """The first device JAX reports, which must be a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke.py needs a TPU, but JAX's first device is "
            f"{dev.platform!r}; there is no CPU fallback")
    return dev


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, summed from
    JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def phase(self, name: str, fn, *args):
        s0, h0, t0 = self.seconds, self.cache_hits, time.perf_counter()
        out = fn(*args)
        print(f"[smoke] phase {name}: wall {time.perf_counter() - t0:.2f} s,"
              f" compile {self.seconds - s0:.2f} s,"
              f" compile-cache hits {self.cache_hits - h0}", flush=True)
        return out


def _rel_max_err(out, want) -> float:
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(out - want)) / np.max(np.abs(want)))


def _check(name: str, err: float, tol: float):
    print(f"[smoke] {name}: error {err:.3e} (tolerance {tol:.0e})",
          flush=True)
    if not err <= tol:
        raise AssertionError(f"{name}: error {err:.3e} exceeds {tol:.0e}")


# ------------------------------------------------------------ one chip
def phase_serving(cfg: ModelConfig, seed: int):
    eng, reqs, st = serve(cfg, slots=SLOTS, max_seq=MAX_SEQ,
                          requests=REQUESTS, new_tokens=NEW_TOKENS,
                          seed=seed)
    answered = sum(len(r.output) == NEW_TOKENS for r in reqs)
    print(f"[smoke] serving: {answered}/{REQUESTS} requests answered, "
          f"{st.tokens_out} tokens out, {st.prefills} prefills, "
          f"{st.decode_steps} decode steps, drained={st.drained}",
          flush=True)
    if not st.drained or answered != REQUESTS or \
            st.tokens_out != REQUESTS * NEW_TOKENS:
        raise AssertionError("serving run did not answer every request")
    return eng.params


def phase_logits(cfg: ModelConfig, params, seed: int):
    """Chip prefill vs the same jitted prefill on the host CPU backend,
    with the same parameters copied there."""
    model = Model(cfg, remat="none")
    prefill = jax.jit(lambda p, b: model.prefill(p, b, MAX_SEQ))
    tokens = np.random.default_rng(seed + 1).integers(
        1, cfg.vocab_size - 1, (1, PROMPT_LENS[0])).astype(np.int32)
    _, chip = prefill(params, {"tokens": jnp.asarray(tokens)})
    cpu = jax.devices("cpu")[0]
    _, host = prefill(jax.device_put(params, cpu),
                      {"tokens": jax.device_put(tokens, cpu)})
    chip = np.asarray(chip[0, :cfg.vocab_size], np.float32)
    host = np.asarray(host[0, :cfg.vocab_size], np.float32)
    err = float(np.linalg.norm(chip - host) / np.linalg.norm(host))
    print(f"[smoke] logits: top-1 chip {int(chip.argmax())}, "
          f"host {int(host.argmax())}", flush=True)
    _check("prefill logits chip vs host CPU (relative L2)", err, LOGITS_TOL)


def phase_kernels(cfg: ModelConfig, seed: int):
    """Each Pallas kernel compiled for the chip at qwen2-0.5b shapes."""
    bf16 = jnp.bfloat16
    H, KH, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    a = jax.random.normal(ks[0], (256, cfg.d_model), bf16)
    b = jax.random.normal(ks[1], (cfg.d_model, cfg.d_ff), bf16)
    _check("streaming_gemm (256x896)x(896x4864) bf16",
           _rel_max_err(ops.streaming_gemm(a, b), ref.gemm_ref(a, b)),
           KERNEL_TOL)

    T = PROMPT_LENS[1]
    q = jax.random.normal(ks[2], (1, T, H, D), bf16)
    k = jax.random.normal(ks[3], (1, T, KH, D), bf16)
    v = jax.random.normal(ks[4], (1, T, KH, D), bf16)
    _check(f"flash_attention causal 1x{T} H={H} KH={KH} D={D} bf16",
           _rel_max_err(ops.flash_attention(q, k, v, causal=True),
                        ref.gqa_flash_ref(q, k, v, causal=True)),
           KERNEL_TOL)

    page, max_pages = 8, 64
    n_pages = SLOTS * max_pages + 8
    q = jax.random.normal(ks[5], (SLOTS, H, D), bf16)
    kp = jax.random.normal(ks[6], (n_pages, page, KH, D), bf16)
    vp = jax.random.normal(ks[7], (n_pages, page, KH, D), bf16)
    table = jax.random.permutation(jax.random.PRNGKey(seed + 1), n_pages)[
        :SLOTS * max_pages].reshape(SLOTS, max_pages).astype(jnp.int32)
    lens = jnp.asarray(np.random.default_rng(seed).integers(
        1, page * max_pages + 1, SLOTS), jnp.int32)
    _check(f"paged_attention B={SLOTS} H={H} KH={KH} D={D} page={page} "
           f"pages/seq={max_pages} bf16",
           _rel_max_err(ops.paged_attention(q, kp, vp, table, lens),
                        ref.paged_ref(q, kp, vp, table, lens)),
           KERNEL_TOL)


# --------------------------------------------------------- four chips
def train_losses(cfg: ModelConfig, mesh, seed: int, *, batch: int,
                 seq: int, steps: int, check_spread: bool = False):
    """Losses of ``steps`` training steps on ``mesh``, from a fresh
    checkpoint directory so nothing is restored.  With
    ``check_spread`` the initial state is first checked to hold
    parameter bytes on every device of the mesh, and to hold fewer than
    all of them on any one device."""
    run = RunConfig(model=cfg, shape=ShapeConfig("train", "train", seq,
                                                 batch))
    with tempfile.TemporaryDirectory() as ckpt_dir:
        tr = Trainer(run, mesh, TrainerConfig(
            ckpt_dir=ckpt_dir, ckpt_every=steps + 1, seed=seed,
            lr_base=3e-4, lr_warmup=1, lr_total=100))
        if check_spread:
            check_param_spread(tr.init_state()["params"],
                               list(mesh.devices.flat))
        return tr.train(steps)["losses"]


def check_param_spread(params, devices):
    held = {d: 0 for d in devices}
    total = 0
    for x in jax.tree.leaves(params):
        total += x.nbytes
        for s in x.addressable_shards:
            held[s.device] += s.data.nbytes
    print("[smoke] parameter bytes per device: "
          + ", ".join(f"{d.id}: {n}" for d, n in held.items())
          + f" (of {total})", flush=True)
    if min(held.values()) == 0 or max(held.values()) >= total:
        raise AssertionError("parameters are not spread over every device")


def compare_sharded_training(cfg: ModelConfig, seed: int, *,
                             batch: int, seq: int, steps: int):
    """The same training steps on a (data=2, model=2) mesh and on a
    one-device mesh: parameters must spread over the four devices and
    the losses must agree within ``LOSS_TOL``."""
    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devices)}")
    print(f"[smoke] training {cfg.name}: batch {batch}, seq {seq}, "
          f"{steps} steps", flush=True)
    sharded = train_losses(cfg, make_host_mesh(data=2, model=2), seed,
                           batch=batch, seq=seq, steps=steps,
                           check_spread=True)
    single = train_losses(cfg, make_host_mesh(data=1, model=1), seed,
                          batch=batch, seq=seq, steps=steps)
    print(f"[smoke] losses 2x2 mesh: {sharded}", flush=True)
    print(f"[smoke] losses one chip: {single}", flush=True)
    if len(sharded) != steps or len(single) != steps:
        raise AssertionError("a training run stopped early")
    err = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
    _check("loss 2x2 mesh vs one chip (max relative)", err, LOSS_TOL)


def phase_sharded_training(cfg: ModelConfig, seed: int):
    compare_sharded_training(cfg, seed, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                             steps=TRAIN_STEPS)
    peaks = [d.memory_stats()["peak_bytes_in_use"]
             for d in jax.devices()[:4]]
    print(f"[smoke] peak bytes in use per device: {peaks}", flush=True)
    if min(peaks) == 0:
        raise AssertionError("a device of the 2x2 mesh held no bytes")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    dev = require_tpu()
    cache_dir = enable_compile_cache()
    n_cached = len(list(Path(cache_dir).glob("*")))
    print("[smoke] readings below are smoke checks, not benchmark metrics",
          flush=True)
    print(f"[smoke] device: {dev.platform} {dev.device_kind} x"
          f"{len(jax.devices())}; compile cache {cache_dir} "
          f"({n_cached} entries)", flush=True)
    meter = CompileMeter()
    cfg = get_config(ARCH)
    if args.chips == 4:
        meter.phase("sharded-training", phase_sharded_training, cfg, SEED)
    else:
        params = meter.phase("serving", phase_serving, cfg, SEED)
        meter.phase("logits", phase_logits, cfg, params, SEED)
        meter.phase("kernels", phase_kernels, cfg, SEED)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
