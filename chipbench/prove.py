"""Readings that set and test a cell's limits, and the knee sweep.

    python3 chipbench/prove.py --workload <name> --seeds 1,2,3 \
        --seconds 30 [--control] [--rates 2,3,4]

For each seed, in one process: the program serves the cell's traffic
for a window, then the reference reads the widest logit gap of the
served tokens (the number ``correct`` compares) and, with
``--control``, the widest gap of the tokens that the float8 control
puts first at the same positions. With ``--rates`` the first seed's
open-loop traffic is offered at each rate in turn instead (the knee
sweep). One JSON line per window. The benchmark's own runs never run
this.
"""
import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="")
    args = ap.parse_args()

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT))
    import jax
    import numpy as np
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import harness, loop
    from chipbench.compile_meter import CompileMeter

    c = harness.load_cell(ROOT, args.workload, False)
    harness.require_chips(c.wl["chips"])
    meter = CompileMeter()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r]
    runs = [(seeds[0], r) for r in rates] or [(s, None) for s in seeds]
    for seed, rate in runs:
        t0 = time.perf_counter()
        engine = harness.set_up(c, seed)
        t1 = time.perf_counter()
        rec = harness.serve(c, engine, seed, args.seconds, meter, rate=rate)
        out = {"workload": args.workload, "seed": seed, "rate_rps": rate,
               "setup_s": t1 - t0,
               "due": len(rec.due_in_window()),
               "tokens_per_s": loop.tokens_in_window(rec) / rec.seconds,
               "ttft_p50_ms": float(np.percentile(loop.ttft_s(rec), 50)) * 1e3,
               "ttft_p90_ms": float(np.percentile(loop.ttft_s(rec), 90)) * 1e3,
               "itl_p99_ms": float(np.percentile(loop.itl_s(rec), 99)) * 1e3,
               "unanswered_at_close": sum(
                   1 for s in rec.due_in_window()
                   if not s.stamps or s.stamps[0] > rec.t_close),
               "queue_at_close": len(engine.queue),
               "compiles_in_window": rec.compiles_in_window,
               "peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
                   "peak_bytes_in_use", 0)}
        chosen = harness.finished_sample(c, rec, seed)
        del engine
        gc.collect()
        if rate is None and chosen:
            prompts = [np.asarray(s.req.prompt) for s in chosen]
            outputs = [np.asarray(s.req.output, np.int32) for s in chosen]
            t2 = time.perf_counter()
            got = c.ref.gaps(c.m, seed, prompts, outputs,
                             control=args.control)
            served, ctl = got if args.control else (got, None)
            out["reference_s"] = time.perf_counter() - t2
            out["tokens_checked"] = int(sum(len(o) for o in outputs))
            out["program_gap"] = max(float(g.max()) for g in served)
            out["program_gaps"] = [float(g.max()) for g in served]
            if ctl is not None:
                out["control_gap"] = max(float(g.max()) for g in ctl)
                out["control_gaps"] = [float(g.max()) for g in ctl]
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
