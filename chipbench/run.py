"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout and the cell's
files under ``chipbench/``. Needs a TPU: without one, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
The last line of stdout is one JSON object; the last lines of stderr
give each number compared beside its limit.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache sits at a fixed path inside the
# checkout, whatever the environment says, so that nothing is shared
# with another checkout and the path (part of the key) never moves.
CACHE_DIR = ROOT / ".jax_cache"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="also write the extracted trace events here")
    args = ap.parse_args()

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import harness
    try:
        result, lines = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds,
            bool(args.trace), t_proc=T_PROC, save_trace=args.save_trace)
    except harness.NoChip as e:
        print(f"[chipbench] {e}", file=sys.stderr)
        return 2
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
