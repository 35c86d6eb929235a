"""The harness loop on the CPU with qwen2-0.5b's reduced preset: token
stamps, censoring of requests without a first token, and the window's
accounting."""
import numpy as np
import pytest

from chipbench import harness, loop, program, traffic, weights
from repro.configs import get_reduced

from chipbench_fixtures import TINY_MODEL


def tiny_engine(slots=4, max_seq=128):
    cfg = get_reduced("qwen2-0.5b")
    assert cfg.d_model == TINY_MODEL["d_model"]
    params = weights.make_all(TINY_MODEL, 3)
    eng = program.make_engine(cfg, params, slots=slots, max_seq=max_seq)
    harness.warm_up(eng, [16, 32], TINY_MODEL["vocab"])
    return eng


def tiny_plan(loop_kind, n=40):
    rng = np.random.default_rng(0)
    prompts = rng.choice([16, 32], n)
    outputs = rng.integers(4, 12, n)
    if loop_kind == "open":
        return traffic.Plan(prompts, outputs, np.arange(n) * 0.02, "open")
    return traffic.Plan(prompts, outputs, None, "closed", clients=4)


def drive(eng, plan, seconds, lead_s=0.0):
    def make(n):
        i = n % len(plan.prompt_lens)
        return program.Request(uid=n, prompt=traffic.prompt_tokens(
            1, n, int(plan.prompt_lens[i]), 256),
            max_new_tokens=int(plan.output_lens[i]))
    return loop.drive(eng, plan, make, seconds=seconds, lead_s=lead_s)


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_stamps_and_window(kind):
    eng = tiny_engine()
    rec = drive(eng, tiny_plan(kind, 30), 0.6, lead_s=0.1)
    assert rec.t_close == pytest.approx(rec.t_open + 0.6)
    assert rec.t_end >= rec.t_close
    for s in rec.sent:
        # one stamp per token the engine delivered, never decreasing
        assert len(s.stamps) == len(s.req.output)
        assert s.stamps == sorted(s.stamps)
        if s.done is not None:
            assert len(s.req.output) == s.req.max_new_tokens
            assert s.done == s.stamps[-1]
        assert s.submit >= s.due
    # the stamps are the steps' ends; each step's counts add up
    ends = {st.t1 for st in rec.steps}
    assert all(t in ends for s in rec.sent for t in s.stamps)
    n_tok = sum(len(s.stamps) for s in rec.sent)
    n_pre = sum(len(st.prefill_lens) for st in rec.steps)
    n_dec = sum(len(st.decode_ctx) for st in rec.steps)
    assert n_tok == n_pre + n_dec
    # the engine's counters are read between steps, the stamps at each
    # step's end: they part by at most the step that spans the open
    counted = rec.stats_close[2] - rec.stats_open[2]
    assert loop.tokens_in_window(rec) > 0
    assert abs(counted - loop.tokens_in_window(rec)) <= 2 * rec.slots
    assert len(loop.itl_s(rec)) > 0 and loop.itl_s(rec).min() >= 0
    if kind == "closed":
        # the window opened once every client's first request held a slot
        assert all(s.stamps and s.stamps[0] <= rec.t_open
                   for s in rec.sent[:4])
        assert all(s.client >= 0 for s in rec.sent)
    else:
        assert rec.t_open == pytest.approx(rec.t_traffic + 0.1)


def test_censoring_of_requests_without_a_first_token():
    rec = loop.Record(slots=1, t_traffic=0.0, t_open=10.0, t_close=20.0,
                      t_end=20.1, sent=[], steps=[], no_work=[],
                      stats_open=(0, 0, 0), stats_close=(0, 0, 0))
    before = loop.Sent(0, None, 8, due=5.0, stamps=[6.0, 11.0, 12.0])
    served = loop.Sent(1, None, 8, due=12.0, submit=12.5,
                       stamps=[13.0, 13.5, 20.05])
    late = loop.Sent(2, None, 8, due=18.0, submit=18.2, stamps=[20.08])
    never = loop.Sent(3, None, 8, due=19.0, submit=19.1)
    rec.sent = [before, served, late, never]
    # only requests due in the window count; the late and the unanswered
    # count with close - due
    assert loop.ttft_s(rec).tolist() == pytest.approx([1.0, 2.0, 1.0])
    assert loop.submit_lag_s(rec).tolist() == pytest.approx([0.5, 0.2, 0.1])
    # gaps with both tokens in the window: 11->12 and 13->13.5
    assert sorted(loop.itl_s(rec).tolist()) == pytest.approx([0.5, 1.0])
    # tokens stamped in (open, close]
    assert loop.tokens_in_window(rec) == 4
