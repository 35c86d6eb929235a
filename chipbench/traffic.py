"""Traffic from a mix's data file and the run's seed.

A mix fixes a pool of request sizes (and, in an open loop, of gaps
between arrivals), drawn once from the mix's own ``pool_seed``. The
run's seed only orders that pool and draws the prompt token ids, so
that the spread between seeds is not a spread in work.

``arrival_times`` is a copy of ``serving/engine.arrival_times``, kept
here so that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def arrival_times(kind: str, n: int, qps: float, seed: int = 0, *,
                  burst_factor: float = 4.0, burst_len: float = 16.0,
                  period_s: float = 60.0, depth: float = 0.8
                  ) -> np.ndarray:
    """Seeded open-loop arrival process: ``n`` absolute arrival times
    at a mean offered rate of ``qps`` requests/second.

    - ``poisson``: i.i.d. exponential gaps.
    - ``bursty``: exponential gaps scaled by alternating quiet/hot runs
      of geometric length ``burst_len``; hot gaps shrink by
      ``burst_factor``, quiet gaps stretch to keep the mean rate.
    - ``diurnal``: gaps modulated by ``1 + depth*sin(2*pi*t/period_s)``.
    """
    if qps <= 0:
        raise ValueError(f"qps must be > 0, got {qps}")
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / qps, size=n)
    if kind == "poisson":
        pass
    elif kind == "bursty":
        lo = 1.0 / burst_factor
        hi = 2.0 - lo
        scale = np.empty(n)
        i, hot = 0, False
        while i < n:
            run = int(rng.geometric(1.0 / burst_len))
            scale[i:i + run] = lo if hot else hi
            i += run
            hot = not hot
        gaps *= scale
    elif kind == "diurnal":
        t = np.cumsum(gaps)
        rate = np.maximum(
            1.0 + depth * np.sin(2.0 * np.pi * t / period_s), 1e-3)
        gaps = gaps / rate
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return np.cumsum(gaps)


def lognormal_lengths(rng, n: int, spec: dict) -> np.ndarray:
    """Lengths from lognormal(median, sigma), clipped to [min, max] and,
    where the spec has ``buckets``, rounded up to the next bucket."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)
    if "buckets" in spec:
        b = np.asarray(sorted(spec["buckets"]))
        if x.max() > b[-1]:
            raise ValueError(f"max {spec['max']} above the last bucket")
        x = b[np.searchsorted(b, x)]
    return x


@dataclasses.dataclass
class Plan:
    """What a run sends: per request its prompt length, output length
    and (open loop) its due time from the start of traffic."""
    prompt_lens: np.ndarray
    output_lens: np.ndarray
    offsets_s: np.ndarray | None
    loop: str
    clients: int = 0


def pool(mix: dict):
    """The mix's fixed pool: prompt lengths, output lengths and unit
    gaps (a rate of 1 request/s), independent of the run's seed."""
    rng = np.random.default_rng(mix["pool_seed"])
    n = mix["pool"]
    prompts = lognormal_lengths(rng, n, mix["prompt"])
    outputs = lognormal_lengths(rng, n, mix["output"])
    gaps = None
    if mix["loop"] == "open":
        gaps = np.diff(arrival_times(mix["arrivals"], n, 1.0,
                                     seed=mix["pool_seed"] + 1,
                                     **mix.get("arrival_args", {})),
                       prepend=0.0)
    return prompts, outputs, gaps


def plan(mix: dict, cell: dict, seed: int, seconds: float) -> Plan:
    """The pool in the order the seed gives.

    Closed loop: the pool shuffled; clients take requests in that order.
    Open loop: the first n requests of the pool, n those that arrive by
    the window's close at the cell's rate, rotated to start at a point
    the seed gives. Every seed then offers the same requests and the
    same gaps, each gap with its request, in another order, and the
    last arrival comes at the same time.
    """
    prompts, outputs, gaps = pool(mix)
    rng = np.random.default_rng([int(seed), 1])
    if mix["loop"] == "open":
        t = np.cumsum(gaps) / cell["rate_rps"]
        n = int(np.searchsorted(t, cell.get("lead_s", 0.0) + seconds,
                                side="right"))
        if n >= len(gaps):
            raise ValueError(f"the mix's pool of {len(gaps)} requests "
                             f"does not last {seconds} s at "
                             f"{cell['rate_rps']} req/s")
        idx = np.roll(np.arange(n), -int(rng.integers(n)))
        return Plan(prompts[idx], outputs[idx],
                    np.cumsum(gaps[idx]) / cell["rate_rps"], "open")
    order = rng.permutation(len(prompts))
    return Plan(prompts[order], outputs[order], None, "closed",
                clients=cell["clients"])


def prompt_tokens(seed: int, n: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``n`` of the run with ``seed``."""
    rng = np.random.default_rng([int(seed), 2, n])
    return rng.integers(0, vocab, length).astype(np.int32)
