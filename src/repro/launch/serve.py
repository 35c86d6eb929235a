"""Serving launcher: continuous batching over synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b

``serve()`` is the whole run; ``chip_smoke.py`` calls it too.  Prompt
lengths are drawn from ``PROMPT_LENS``, so prefill compiles once per
length.  ``--reduced`` runs on a CPU.
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serving.engine import Request, ServingEngine

PROMPT_LENS = (128, 512)


def serve(cfg: ModelConfig, *, slots: int, max_seq: int, requests: int,
          new_tokens: int, seed: int):
    """Serve ``requests`` synthetic requests with seeded random weights
    until the queue drains.  Returns ``(engine, requests, stats)``."""
    if max_seq < max(PROMPT_LENS) + new_tokens:
        raise ValueError(
            f"max_seq {max_seq} cannot hold a {max(PROMPT_LENS)}-token "
            f"prompt and {new_tokens} new tokens")
    params = Model(cfg, remat="none").init(jax.random.PRNGKey(seed))
    eng = ServingEngine(cfg, params, slots=slots, max_seq=max_seq)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(requests):
        r = Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size - 1,
                                        int(rng.choice(PROMPT_LENS))
                                        ).astype(np.int32),
                    max_new_tokens=new_tokens)
        reqs.append(r)
        eng.submit(r)
    return eng, reqs, eng.run_until_drained()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    _, reqs, st = serve(cfg, slots=args.slots, max_seq=args.max_seq,
                        requests=args.requests, new_tokens=args.new_tokens,
                        seed=args.seed)
    ttft = [r.first_token_s - r.submitted_s for r in reqs]
    print(f"[{cfg.name}] {st.tokens_out} tokens "
          f"@ {st.tokens_per_s:.1f} tok/s; "
          f"TTFT p50={np.percentile(ttft, 50)*1e3:.0f}ms; "
          f"prefills={st.prefills} decode_steps={st.decode_steps}")


if __name__ == "__main__":
    main()
