"""The serving engine's recorded plan traces, pinned record for record.

Three runs at reduced size, each folded into one digest: every record's
kind, step index, slots, uids, arrival event, token count, plan name and
the pages its events stream, with the seconds one accesys replay prices
for its plan. A change to how
the engine admits, steps, retires, preempts or maps pages moves the
digest of the run that reaches it.

- ``closed``: the jitted model path with a shadow page table, built as
  the ``serve`` scenario builds it (closed loop, run until drained);
- ``preempt``: an open-loop, plan-only run with chunked prefill, lifo
  preemption and a pool seizure from ``serving.faults``;
- ``prefix``: an open-loop, plan-only run over a cached shared prefix.
"""
import hashlib

import jax
import numpy as np
import pytest

from repro.accesys.pipeline import replay_trace
from repro.configs import get_reduced
from repro.core.scenario import Scenario, system_for
from repro.models.model import Model
from repro.serving import faults
from repro.serving.engine import Request, ServingEngine, arrival_times

ARCH = "qwen2_0_5b"


def _requests(n, seed, prompt=(4, 30), new=(2, 12)):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(1, 250, size=int(
                        rng.integers(*prompt))).astype(np.int32),
                    max_new_tokens=int(rng.integers(*new)))
            for i in range(n)]


def _closed():
    cfg = get_reduced(ARCH)
    params = Model(cfg, remat="none").init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=3, max_seq=48,
                        record_plans=True)
    for r in _requests(8, seed=1, new=(3, 24)):
        eng.submit(r)
    eng.run_until_drained()
    assert eng.stats.drained
    return eng


def _preempt():
    eng = ServingEngine(get_reduced(ARCH), plan_only=True, slots=3,
                        max_seq=64, kv_page_tokens=8, kv_pool_pages=13)
    reqs = faults.adversarial_requests(16, seed=1, max_seq=64)
    eng.run_open_loop(reqs, faults.storm_arrivals(16, 400.0, seed=1),
                      prefill_chunk_tokens=8, est_step_s=1e-4,
                      est_prefill_s_per_token=1e-5, preempt="lifo",
                      faults=faults.PoolShrinkFault(6, 3, 60))
    assert eng.stats.drained and eng.stats.preemptions > 0
    return eng


def _prefix():
    eng = ServingEngine(get_reduced(ARCH), plan_only=True, slots=3,
                        max_seq=64, kv_page_tokens=8, prefix_tokens=16,
                        prefix_caching=True)
    eng.run_open_loop(_requests(8, seed=3, new=(1, 10)),
                      arrival_times("poisson", 8, 300.0, seed=3),
                      prefill_chunk_tokens=16)
    assert eng.stats.drained
    return eng


def _digest(trace) -> str:
    _, per = replay_trace(system_for(Scenario(model="serve", mode="DC")),
                          [r.plan for r in trace])
    rows = [(r.kind, r.step_idx, r.slots, r.uids, r.arrival_event,
             r.n_tokens, r.plan.name,
             [getattr(e, "page", None) for e in r.plan.events],
             repr(float(s)))
            for r, s in zip(trace, per)]
    return hashlib.sha256(repr(rows).encode()).hexdigest()[:16]


@pytest.mark.parametrize("run,n_records,digest", [
    (_closed, 36, "77ba704a49aec7a7"),
    (_preempt, 119, "b6f6a6b4cf3f088b"),
    (_prefix, 31, "e4945daef9de295e"),
], ids=["closed", "preempt", "prefix"])
def test_recorded_trace_is_pinned(run, n_records, digest):
    eng = run()
    assert (len(eng.trace), _digest(eng.trace)) == (n_records, digest)
