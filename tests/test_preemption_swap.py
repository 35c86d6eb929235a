"""Preemption, KV swap-to-host, and graceful degradation under
memory pressure.

The contract under test, layer by layer:

* ``core.plan.swap_plan`` — page-aligned DMA plans on the dedicated
  swap lane, one stable ``(uid, page)`` namespace per KV pool so an
  out/in round trip re-touches identical page keys;
* ``PageTable.swap_out / validate / seize_pages`` — device pages are
  released exactly when the swap plan is emitted and the free/owned/
  prefix/seized partitions never overlap or leak;
* ``ServingEngine(preempt=...)`` — eviction moves work, never loses
  or repeats it: every prompt token prefilled exactly once and every
  token decoded exactly once across any number of preemptions, the
  pool drains to empty, and per-step invariants hold under seeded
  fault injection (burst storms, adversarial mixes, mid-run pool
  shrinkage);
* swap-bearing traces price BITWISE identically streamed vs
  monolithic at any chunk size, and ``sim_report`` splits each
  request's latency into additive components whose sums reproduce
  TTFT and end-to-end time exactly.
"""
import dataclasses
import hashlib
import math

import numpy as np
import pytest

from repro.accesys.pipeline import replay_trace, replay_trace_streamed
from repro.core import plan as plan_ir
from repro.core.scenario import MODES, Scenario, system_for
from repro.serving import faults, invariants
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import PagedCacheConfig, PageTable
from repro.serving.sim_report import simulate_serving_trace


def _cfgs():
    return [system_for(Scenario(model="serve", mode=m)) for m in MODES]


def _engine(**kw):
    from repro.configs import get_reduced
    kw.setdefault("slots", 3)
    kw.setdefault("max_seq", 64)
    kw.setdefault("kv_page_tokens", 8)
    return ServingEngine(get_reduced("qwen2_0_5b"), plan_only=True,
                         **kw)


def _req(uid, n_prompt, max_new=4):
    return Request(uid=uid,
                   prompt=np.arange(1, n_prompt + 1, dtype=np.int32),
                   max_new_tokens=max_new)


def _overload(seed, policy, **kw):
    eng, reqs = faults.overload_run(seed, preempt=policy, **kw)
    assert eng.stats.drained
    return eng, reqs


# ================================================= swap_plan builder
class TestSwapPlan:
    def test_events_namespace_lane_and_kinds(self):
        for direction, kind in (("out", plan_ir.EventKind.DMA_OUT),
                                ("in", plan_ir.EventKind.DMA_IN)):
            p = plan_ir.swap_plan(3, 8, 2, 16, 2, direction=direction,
                                  tag=42, n_layers=2)
            # n_layers * (K + V) pools, one event per page each
            assert len(p.tensors) == 4
            assert len(p.events) == 3 * 4
            assert set(p.tensors) == {"L0.k.swap", "L0.v.swap",
                                      "L1.k.swap", "L1.v.swap"}
            for ev in p.events:
                assert ev.kind is kind
                assert ev.lane == plan_ir.SWAP_LANE
                assert ev.op == f"swap_{direction}"
                assert ev.nbytes == p.page_bytes
                ns, key = ev.page
                assert ns.endswith(".swap") and key[0] == 42
            pages = {ev.page for ev in p.events}
            assert len(pages) == len(p.events)   # no duplicate keys

    def test_out_in_round_trip_touches_identical_pages(self):
        out = plan_ir.swap_plan(2, 8, 2, 16, 2, direction="out", tag=7)
        back = plan_ir.swap_plan(2, 8, 2, 16, 2, direction="in", tag=7)
        assert {e.page for e in out.events} == \
            {e.page for e in back.events}
        assert plan_ir.trace_footprint([out, back]) == len(out.events)

    def test_page_bytes_and_footprint(self):
        p = plan_ir.swap_plan(5, 8, 2, 16, 2, direction="out", tag=0)
        assert p.page_bytes == 8 * 2 * 16 * 2
        assert sum(e.nbytes for e in p.events) == 2 * 5 * p.page_bytes
        assert plan_ir.trace_footprint([p]) == 10   # 5 pages x K,V

    def test_rejects_bad_direction_and_empty(self):
        with pytest.raises(ValueError, match="direction"):
            plan_ir.swap_plan(1, 8, 2, 16, 2, direction="up", tag=0)
        with pytest.raises(ValueError, match=">= 1 page"):
            plan_ir.swap_plan(0, 8, 2, 16, 2, direction="out", tag=0)

    def test_replays_standalone(self):
        p = plan_ir.swap_plan(4, 8, 2, 16, 2, direction="out", tag=1,
                              n_layers=2)
        res, per = replay_trace(_cfgs()[0], [p])
        assert res.total_s > 0 and per.shape == (1,)


# ======================================= PageTable swap + accounting
def _table(n_pages=12, page_tokens=8, max_seqs=3):
    return PageTable(PagedCacheConfig(
        n_pages=n_pages, page_tokens=page_tokens, n_kv_heads=2,
        head_dim=16, max_pages_per_seq=8, dtype="float16"),
        max_seqs=max_seqs)


class TestPageTableSwap:
    def test_written_own_pages_excludes_shared_and_unwritten(self):
        t = _table()
        t.alloc_seq(0, 20)             # 3 pages held, 0 shared
        assert t.written_own_pages(0, 0) == 0
        assert t.written_own_pages(0, 9) == 2
        assert t.written_own_pages(0, 20) == 3
        assert t.written_own_pages(0, 999) == 3   # capped at held

    def test_swap_out_frees_pages_and_emits_matching_plan(self):
        t = _table()
        t.alloc_seq(0, 20)
        before = t.pages_in_use
        plan, n = t.swap_out(0, 17, tag=5, n_layers=2)
        assert n == 3 and before == 3
        assert t.pages_in_use == 0
        # 3 pages x 2 layers x (K, V)
        assert len(plan.events) == 3 * 4
        assert all(e.kind is plan_ir.EventKind.DMA_OUT
                   for e in plan.events)
        t.validate()

    def test_swap_out_nothing_written_returns_no_plan(self):
        t = _table()
        t.alloc_seq(0, 8)
        plan, n = t.swap_out(0, 0, tag=1)
        assert plan is None and n == 0
        assert t.pages_in_use == 0
        t.validate()

    def test_seize_restore_round_trip(self):
        t = _table()
        assert t.seize_pages(5) == 5
        t.validate()
        assert t.pages_in_use == 5
        t.alloc_seq(0, 40)             # 5 pages from the 7 left
        t.validate()
        assert t.restore_pages() == 5
        t.validate()
        assert t.pages_in_use == 5     # only the slot's own pages

    def test_seize_is_clamped_to_free(self):
        t = _table()
        t.alloc_seq(0, 40)             # 5 of 12 pages
        assert t.seize_pages(99) == 7
        t.validate()

    def test_validate_catches_double_free(self):
        t = _table()
        t.alloc_seq(0, 16)
        t._free.append(int(t.tables[0, 0]))    # corrupt: page in both
        with pytest.raises(AssertionError):
            t.validate()

    def test_validate_catches_leak(self):
        t = _table()
        t.alloc_seq(0, 16)
        t._free.pop()                  # corrupt: page vanishes
        with pytest.raises(AssertionError):
            t.validate()


# ================================================ engine preemption
class TestEnginePreemption:
    @pytest.mark.parametrize("policy", ["lifo", "longest"])
    def test_conservation_across_preemptions(self, policy):
        eng, reqs = _overload(0, policy)
        assert eng.stats.preemptions > 0
        assert eng.stats.swapped_pages > 0
        invariants.check_drained(eng.sched)
        tally = invariants.check_trace_conservation(
            eng.trace, reqs, max_seq=eng.max_seq)
        # every swap_out round-tripped, page counts matched
        assert any(v["swap_outs"] for v in tally.values())
        for v in tally.values():
            assert v["swap_outs"] == v["swap_ins"]
            assert v["swap_out_pages"] == v["swap_in_pages"]

    def test_preempts_only_running_request(self):
        # one monster holds nearly the whole pool; a second request
        # cannot reserve its worst case until the monster is evicted
        eng = _engine(slots=2, max_seq=64, kv_pool_pages=9)
        reqs = [_req(0, 40, max_new=8), _req(1, 24, max_new=8)]
        eng.run_open_loop(reqs, np.array([0.0, 0.0]),
                          prefill_chunk_tokens=8, est_step_s=1e-4,
                          est_prefill_s_per_token=1e-5,
                          preempt="lifo", debug_invariants=True)
        assert eng.stats.drained and eng.n_finished == 2
        assert eng.stats.preemptions >= 1
        first = next(i for i, r in enumerate(eng.trace)
                     if r.kind == "swap_out")
        assert eng.trace[first].uids == (0,)
        # everything before that eviction belongs to uid 0: it was
        # the ONLY running request when it was preempted
        assert all(r.uids == (0,) for r in eng.trace[:first])
        invariants.check_trace_conservation(eng.trace, reqs,
                                            max_seq=eng.max_seq)

    def test_chunk_boundary_preemption_mid_prefill(self):
        # small chunks + lifo + a spare slot: the newest runner is
        # evicted BETWEEN prefill chunks (admission-triggered
        # preemption needs a free slot) and resumes where it stopped
        eng = _engine(slots=3, max_seq=64, kv_pool_pages=9)
        reqs = [_req(0, 40, max_new=2), _req(1, 20, max_new=2),
                _req(2, 20, max_new=2)]
        eng.run_open_loop(reqs, np.zeros(3), prefill_chunk_tokens=8,
                          est_step_s=1e-4,
                          est_prefill_s_per_token=1e-5,
                          preempt="lifo", debug_invariants=True)
        assert eng.stats.drained
        per_uid: dict = {}
        mid_prefill = set()
        for rec in eng.trace:
            if rec.kind == "prefill":
                per_uid.setdefault(rec.uids[0], []).append(
                    rec.n_tokens)
            elif rec.kind == "swap_out":
                uid = rec.uids[0]
                done = sum(per_uid.get(uid, []))
                if 0 < done < len(reqs[uid].prompt):
                    mid_prefill.add(uid)
        assert mid_prefill               # someone was evicted mid-prefill
        for uid, chunks in per_uid.items():
            assert sum(chunks) == len(reqs[uid].prompt), \
                (uid, chunks)
        invariants.check_trace_conservation(eng.trace, reqs,
                                            max_seq=eng.max_seq)

    def test_swap_in_racing_retire(self):
        # requests preempted mid-decode with few tokens left must
        # resume and retire immediately without double-producing
        found = False
        for seed in range(6):
            eng, reqs = _overload(seed, "lifo", n_requests=40)
            tally = invariants.check_trace_conservation(
                eng.trace, reqs, max_seq=eng.max_seq)
            # a uid whose LAST swap_in is followed by at most one of
            # its decode records: resume raced straight into retire
            for uid, v in tally.items():
                if not v["swap_ins"]:
                    continue
                last_in = max(i for i, r in enumerate(eng.trace)
                              if r.kind == "swap_in"
                              and r.uids[0] == uid)
                after = sum(1 for r in eng.trace[last_in + 1:]
                            if r.kind == "decode" and uid in r.uids)
                if after <= 1:
                    found = True
        assert found

    @pytest.mark.parametrize("policy,digest", [
        ("lifo", "69fffc60d8428c2f"), ("longest", "dbfe9b6a4a0c2447")])
    def test_plan_only_trace_and_lengths_are_pinned(self, policy, digest):
        """A seeded overload run with preemption gives the records and
        the per-step host lengths (``sched.lens``) pinned here, so changes to
        the jitted path's bookkeeping leave the plan-only path as it
        was."""
        @dataclasses.dataclass
        class LensLog:
            seen: list

            def on_step(self, eng, step):
                self.seen.append(eng.sched.lens.tolist())

        eng = _engine(slots=3, max_seq=64, kv_pool_pages=13)
        reqs = faults.adversarial_requests(60, seed=0, max_seq=64)
        arr = faults.storm_arrivals(60, 400.0, seed=0)
        log = LensLog([])
        eng.run_open_loop(reqs, arr, prefill_chunk_tokens=8,
                          est_step_s=1e-4, est_prefill_s_per_token=1e-5,
                          preempt=policy, debug_invariants=True,
                          faults=faults.FaultSchedule([
                              faults.PoolShrinkFault(10, 3, 200), log]))
        assert eng.stats.drained and eng.stats.preemptions > 0
        recs = [(r.kind, r.step_idx, r.slots, r.uids, r.arrival_event,
                 r.n_tokens) for r in eng.trace]
        assert hashlib.sha256(repr((recs, log.seen, eng.sched.lens.tolist()))
                              .encode()).hexdigest()[:16] == digest

    def test_decode_growth_failure_swaps_out_and_pages_are_pinned(self):
        """A pool seizure while slots decode makes a slot's page growth
        fail in a decode step; under lifo it is swapped out, not a
        crash.  A plan step grows and retires slot by slot, so a page a
        retiring slot frees is there for the next slot's growth.  The
        records, the pages their plans stream and the seconds one
        replay prices for each are pinned here."""
        eng = _engine(slots=3, max_seq=64, kv_pool_pages=16)
        reqs = faults.adversarial_requests(12, seed=6, max_seq=64)
        eng.run_open_loop(reqs, faults.storm_arrivals(12, 400.0, seed=6),
                          prefill_chunk_tokens=8, est_step_s=1e-4,
                          est_prefill_s_per_token=1e-5, preempt="lifo",
                          debug_invariants=True,
                          faults=faults.PoolShrinkFault(20, 16, 50))
        assert eng.stats.drained and eng.stats.preemptions > 0
        invariants.check_trace_conservation(eng.trace, reqs,
                                            max_seq=eng.max_seq)
        _, per = replay_trace(system_for(Scenario(model="serve",
                                                  mode="DC")),
                              [r.plan for r in eng.trace])
        rows = [(r.kind, r.step_idx, r.slots, r.uids, r.arrival_event,
                 r.n_tokens, r.plan.name,
                 [getattr(e, "page", None) for e in r.plan.events],
                 repr(float(t))) for r, t in zip(eng.trace, per)]
        assert (len(rows), hashlib.sha256(repr(rows).encode())
                .hexdigest()[:16]) == (76, "95ea418e97a4ab1d")

    def test_policy_validation(self):
        eng = _engine()
        with pytest.raises(ValueError, match="preempt"):
            eng.run_open_loop([_req(0, 8)], np.zeros(1),
                              preempt="fifo")
        with pytest.raises(ValueError, match="stall_budget"):
            eng.run_open_loop([_req(0, 8)], np.zeros(1),
                              preempt="lifo", stall_budget_s=-1.0)

    def test_no_preemption_without_policy(self):
        # same pressured pool and storm, no policy armed: the engine
        # defers instead of evicting and still drains cleanly
        eng = _engine(slots=3, max_seq=64, kv_pool_pages=13)
        reqs = faults.adversarial_requests(30, seed=0, max_seq=64)
        arr = faults.storm_arrivals(30, 400.0, seed=0)
        eng.run_open_loop(reqs, arr, prefill_chunk_tokens=8,
                          est_step_s=1e-4,
                          est_prefill_s_per_token=1e-5,
                          debug_invariants=True)
        assert eng.stats.drained
        assert eng.stats.preemptions == 0
        assert not any(r.kind.startswith("swap") for r in eng.trace)
        assert eng.deferred_admissions > 0
        invariants.check_trace_conservation(eng.trace, reqs,
                                            max_seq=eng.max_seq)


# ======================================== non-drained exit surfacing
class TestDrainedFlag:
    def test_truncated_open_loop_reports_not_drained(self):
        eng = _engine()
        reqs = [_req(i, 16, max_new=8) for i in range(8)]
        eng.run_open_loop(reqs, np.zeros(8), prefill_chunk_tokens=8,
                          max_steps=3)
        assert not eng.stats.drained
        assert eng.unfinished_uids()

    def test_full_open_loop_reports_drained(self):
        eng = _engine()
        reqs = [_req(i, 16, max_new=4) for i in range(4)]
        eng.run_open_loop(reqs, np.zeros(4), prefill_chunk_tokens=8)
        assert eng.stats.drained
        assert not eng.unfinished_uids()


# =========================================== fault-injection harness
class TestFaultInjection:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("policy", ["lifo", "longest"])
    def test_overload_properties(self, seed, policy):
        """Bounded queue, full drain, per-step invariants (checked
        inside the run), trace conservation — per seed and policy."""
        eng, reqs = _overload(seed, policy, n_requests=48)
        assert eng.n_finished == len(reqs)
        invariants.check_drained(eng.sched)
        invariants.check_trace_conservation(eng.trace, reqs,
                                            max_seq=eng.max_seq)

    def test_storm_arrivals_shape(self):
        arr = faults.storm_arrivals(100, 50.0, seed=3, storms=4)
        assert arr.shape == (100,) and np.all(np.diff(arr) >= 0)
        # zero-width spikes: repeated identical instants
        _, counts = np.unique(arr, return_counts=True)
        assert counts.max() >= 100 * 0.5 / 4
        assert np.array_equal(arr,
                              faults.storm_arrivals(100, 50.0, seed=3,
                                                    storms=4))

    def test_adversarial_mix_fits_budget(self):
        reqs = faults.adversarial_requests(64, seed=1, max_seq=64,
                                           max_new_hi=8)
        assert {r.uid for r in reqs} == set(range(64))
        assert all(len(r.prompt) + r.max_new_tokens <= 64
                   for r in reqs)
        big = sum(len(r.prompt) >= 42 for r in reqs)
        assert 0 < big < 64            # a mix, not a monoculture

    def test_pool_shrink_fault_seizes_and_restores(self):
        eng = _engine(kv_pool_pages=10)
        f = faults.PoolShrinkFault(at_step=0, n_pages=4,
                                   restore_step=2)
        f.on_step(eng, 0)
        assert f.seized == 4 and eng.sched.table.pages_in_use == 4
        f.on_step(eng, 1)
        assert eng.sched.table.pages_in_use == 4
        f.on_step(eng, 2)
        assert f.restored and eng.sched.table.pages_in_use == 0

    def test_smoke_main_exits_clean(self):
        assert faults.main(["--seeds", "0", "--requests", "24"]) == 0


# ================================= bitwise streamed parity with swap
class TestSwapTraceParity:
    def test_streamed_matches_monolithic_all_modes(self):
        eng, _ = _overload(1, "lifo", n_requests=40)
        assert eng.stats.preemptions > 0
        plans = [r.plan for r in eng.trace]
        cfgs = _cfgs()
        mono = [replay_trace(c, plans) for c in cfgs]
        for chunk in (1, 311, 10**9):
            res, pers = replay_trace_streamed(cfgs, plans,
                                              chunk_events=chunk)
            for (mr, mp), r, p in zip(mono, res, pers):
                for f in dataclasses.fields(mr):
                    assert getattr(mr, f.name) == getattr(r, f.name), \
                        (chunk, f.name)
                assert np.array_equal(mp, p), chunk


# ================================================ latency attribution
class TestSwapAttribution:
    def _report(self, seed=0, policy="lifo"):
        eng, reqs = _overload(seed, policy)
        cfg = system_for(Scenario(model="serve", mode="DC"))
        return eng, simulate_serving_trace(cfg, eng.trace)

    def test_components_sum_exactly(self):
        eng, rep = self._report()
        assert any(r.n_preempt for r in rep.requests)
        for r in rep.requests:
            if not math.isnan(r.ttft_s):
                assert abs(r.queue_s + r.prefill_s + r.swap_pre_s
                           - r.ttft_s) < 1e-12
                assert r.queue_s >= -1e-12
                assert r.prefill_s > 0 and r.swap_pre_s >= 0
            if not math.isnan(r.e2e_s):
                total = r.queue_s + r.prefill_s + r.swap_pre_s + \
                    r.decode_s + r.swap_post_s + r.stall_s
                assert abs(total - r.e2e_s) < 1e-12
                assert r.stall_s >= -1e-12 and r.swap_post_s >= 0

    def test_swap_time_conserved_and_attributed(self):
        eng, rep = self._report()
        rec_swap = sum(d for d, rec in zip(rep.per_event_s, eng.trace)
                       if rec.kind.startswith("swap"))
        attr = sum(r.swap_s for r in rep.requests
                   if not math.isnan(r.swap_s))
        assert rec_swap > 0
        assert abs(rec_swap - attr) < 1e-12
        for r in rep.requests:
            if math.isnan(r.swap_s):
                continue
            assert (r.swap_s > 0) == (r.n_preempt > 0), r

    def test_percentiles_carry_swap_and_queue_tails(self):
        _, rep = self._report()
        pct = rep.percentiles()
        assert pct["n_preempted"] > 0
        assert pct["preemptions"] >= pct["n_preempted"]
        assert pct["swap_s_total"] > 0
        for key in ("swap_p50_us", "swap_p99_us", "queue_p50_us",
                    "queue_p99_us"):
            assert not math.isnan(pct[key])
        assert pct["swap_p99_us"] >= pct["swap_p50_us"] >= 0


# ====================================================== load sweep
class TestPreemptionSweep:
    def test_sweep_prices_past_the_knee_with_swap(self):
        from repro.core.scenario import sweep_load
        res = sweep_load(n_requests=40, preempt="lifo",
                         modes=("DC",), slots=3, max_seq=64,
                         prompt_lo=8, prompt_hi=24,
                         prefill_chunk_tokens=8)
        assert res.preempt == "lifo"
        assert res.kv_pool_pages is not None
        assert res.kv_pool_pages < 3 * (64 // 8)   # pressured
        k = res.knee_qps["DC"]
        assert k is not None
        past = [pt for pt in res.curve("DC") if pt.qps > k]
        assert past                    # >=1 priced point past the knee
        assert any(pt.percentiles["preemptions"] > 0 for pt in past)
        assert all(pt.drained for pt in res.curve("DC"))
        assert "preempt" in res.to_json()
