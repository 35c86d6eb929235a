"""Shared data of the chip benchmark's tests: a reduced configuration,
its mixes and cells, written as new files into a copy of the benchmark's
directory."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# qwen2-0.5b's reduced preset (configs/qwen2_0_5b.reduced()) as a model
# group: 2 layers, width 64, 4 heads over 2 KV groups.
TINY_MODEL = {
    "layers": 2, "d_model": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
    "d_ff": 128, "vocab": 256, "vocab_padded": 256, "vocab_pad_multiple": 16,
    "rope_theta": 10000.0, "rotary_dim": 16, "norm_eps": 1e-5,
    "qkv_bias": True, "tied_embeddings": True, "dtype": "bfloat16"}

TINY_MIXES = {
    "tiny-open": {"loop": "open", "arrivals": "poisson", "pool": 256,
                  "pool_seed": 0,
                  "prompt": {"median": 24, "sigma": 0.6, "min": 8,
                             "max": 64, "buckets": [16, 32, 64]},
                  "output": {"median": 12, "sigma": 0.6, "min": 4,
                             "max": 40}},
    "tiny-closed": {"loop": "closed", "pool": 64, "pool_seed": 0,
                    "prompt": {"median": 24, "sigma": 0.6, "min": 8,
                               "max": 64, "buckets": [16, 32, 64]},
                    "output": {"median": 12, "sigma": 0.6, "min": 4,
                               "max": 40}},
}
# program reads <= 0.003 at this size on the CPU, the float8 control
# 0.03-0.05 (seeds 1, 2, 3, 2**33)
CHECK = {"requests": 3, "min_tokens": 20, "logit_gap": 0.01}
TINY_CELLS = {
    "tiny.open": {"slots": 4, "max_seq": 128, "rate_rps": 20.0,
                  "lead_s": 0.5, "check": CHECK},
    "tiny.closed": {"slots": 4, "max_seq": 128, "clients": 4,
                    "check": CHECK},
}


def _write(path: Path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_tiny_bench(tmp_path: Path):
    """(root, base): a checkout-like root with a BENCHMARK.json naming the
    tiny cells, and a copy of the benchmark's directory holding their
    files."""
    base = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", base,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    _write(base / "configs" / "tiny.json",
           {"name": "tiny", "model": TINY_MODEL, "reference": "dense_gqa"})
    for name, mix in TINY_MIXES.items():
        _write(base / "traffic" / f"{name}.json", mix)
    for name, cell in TINY_CELLS.items():
        _write(base / "cells" / f"{name}.json", cell)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny-open",
         "chips": 1, "why": "test"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny-closed",
         "chips": 1, "why": "test"}]
    _write(tmp_path / "BENCHMARK.json", bench)
    return tmp_path, base
