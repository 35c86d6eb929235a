"""Request-centric serving simulation: GQA / multi-layer decode plans,
prefill plans from the same PageTable pages, batched trace replay with
per-plan attribution, simulated TTFT/TPOT percentiles, deferred
admission under pool pressure, and PageTable churn invariants.

These are the PR's acceptance criteria: KV bytes stay accounted per
KV head under q-head fan-out, prefill streams exactly the pages the
page table names, one batched compiled replay equals the sequential
event replay plan-for-plan, simulated latency folds back onto
individual requests, and the engine defers (never crashes) when the
shadow pool fills.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import plan as P
from repro.core import streaming
from repro.core.modes import MemoryMode
from repro.serving.kv_cache import PagedCacheConfig, PagedKVCache, \
    PageTable


def _dma_bytes(plan, pools=None):
    return sum(ev.nbytes for ev in plan.events
               if ev.kind is P.EventKind.DMA_IN and
               (pools is None or ev.page[0] in pools))


# ------------------------------------------------------------------ GQA
def test_gqa_decode_kv_bytes_per_kv_head_and_compute_fanout():
    """n_q_heads > n_kv_heads must NOT change KV page traffic (pages
    are fetched once, bytes per KV head) while SA passes scale with the
    q-head fan-out."""
    tables, lens = [[3, 7, 1], [5, 2]], [20, 12]
    mha = P.decode_step_plan(tables, lens, 8, 2, 16, 2)
    gqa = P.decode_step_plan(tables, lens, 8, 2, 16, 2, n_q_heads=8)
    for pl in (mha, gqa):
        pl.validate()
        assert _dma_bytes(pl) == 2 * 5 * pl.page_bytes
    n_sa = lambda pl: sum(1 for e in pl.events
                          if e.kind is P.EventKind.COMPUTE
                          and e.unit == "sa")
    assert n_sa(gqa) == 4 * n_sa(mha)          # group = 8 // 2
    assert gqa.macs == 4 * mha.macs
    # score / output drains scale with the query heads too
    out_bytes = lambda pl: sum(e.nbytes for e in pl.events
                               if e.kind is P.EventKind.DMA_OUT)
    assert out_bytes(gqa) == 4 * out_bytes(mha)


def test_gqa_decode_matches_grouped_reference():
    """Functional execution of a GQA decode plan == per-q-head paged
    attention with kv head h // group."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    HQ, KH, hd, group = 4, 2, 16, 2
    ccfg = PagedCacheConfig(n_pages=32, page_tokens=8, n_kv_heads=KH,
                            head_dim=hd, max_pages_per_seq=4,
                            dtype="float32")
    cache = PagedKVCache(ccfg, max_seqs=3)
    mk = lambda t: jnp.asarray(rng.standard_normal((t, KH, hd)),
                               jnp.float32)
    for slot, ln in enumerate((20, 9, 17)):
        assert cache.alloc_seq(slot, ln)
        cache.write_prompt(slot, mk(ln), mk(ln))
    plan = cache.decode_step_plan([0, 1, 2], n_q_heads=HQ)
    plan.validate()
    q = rng.standard_normal((3, HQ * hd)).astype(np.float32)
    kd, vd = cache.page_dicts([0, 1, 2])
    outs, store = streaming.execute_plan(plan, {"q": q}, MemoryMode.DM,
                                         paged={"k": kd, "v": vd})
    out = outs["decode_out"].reshape(3, HQ, hd)
    for b, s in enumerate([0, 1, 2]):
        L = int(cache.lens[s])
        tbl = cache.tables[s, :int(cache.held[s])]
        K = np.concatenate([np.asarray(cache.k_pages[p])
                            for p in tbl])[:L]
        V = np.concatenate([np.asarray(cache.v_pages[p])
                            for p in tbl])[:L]
        qb = q[b].reshape(HQ, hd)
        for h in range(HQ):
            kvh = h // group
            sc = (qb[h] @ K[:, kvh].T) * hd ** -0.5
            pr = np.exp(sc - sc.max())
            pr /= pr.sum()
            np.testing.assert_allclose(out[b, h], pr @ V[:, kvh],
                                       rtol=1e-4, atol=1e-5)
    # each page fetched once despite the fan-out
    assert store.stats.lookups == 2 * sum(int(cache.held[s])
                                          for s in [0, 1, 2])


# ---------------------------------------------------------- multi-layer
def test_multi_layer_decode_per_layer_page_namespaces():
    tables, lens = [[3, 7], [5]], [12, 6]
    one = P.decode_step_plan(tables, lens, 8, 2, 16, 2, n_q_heads=4)
    three = P.decode_step_plan(tables, lens, 8, 2, 16, 2, n_q_heads=4,
                               n_layers=3)
    three.validate()
    assert len(three.events) == 3 * len(one.events)
    assert three.macs == 3 * one.macs
    assert _dma_bytes(three) == 3 * _dma_bytes(one)
    pools = {e.page[0] for e in three.events
             if e.kind is P.EventKind.DMA_IN}
    assert pools == {f"L{i}.{t}" for i in range(3) for t in ("k", "v")}
    # same physical page ids per layer, distinct SMMU namespaces
    for i in range(3):
        ids = {e.page[1] for e in three.events
               if e.kind is P.EventKind.DMA_IN
               and e.page[0] == f"L{i}.k"}
        assert ids == {3, 7, 5}


def test_decode_step_schedule_footprint_counts_layers():
    tables, lens = [[3, 7], [5]], [12, 6]
    sched = P.decode_step_schedule(tables, lens, 8, 2, 16, 2, 4,
                                   n_q_heads=4)
    sched.validate()
    one = P.decode_step_plan(tables, lens, 8, 2, 16, 2, n_q_heads=4)
    assert sched.footprint_pages == 4 * one.footprint_pages
    assert sched.exact_events == 4 * len(one.events)


# -------------------------------------------------------------- prefill
def _held_table():
    pt = PageTable(PagedCacheConfig(
        n_pages=16, page_tokens=8, n_kv_heads=2, head_dim=16,
        max_pages_per_seq=4, dtype="float16"), max_seqs=2)
    assert pt.alloc_seq(0, 20)
    pt.note_tokens(0, 20)
    return pt


def test_prefill_plan_streams_exactly_the_table_pages():
    pt = _held_table()
    plan = pt.prefill_plan(0, 20, n_q_heads=4, d_model=64, d_ff=128)
    plan.validate()
    held = {int(p) for p in pt.tables[0, :int(pt.held[0])]}
    for pool in ("k", "v"):
        read = {e.page[1] for e in plan.events
                if e.kind is P.EventKind.DMA_IN and e.page[0] == pool}
        written = {e.page[1] for e in plan.events
                   if e.kind is P.EventKind.DMA_OUT
                   and e.page[0] == pool}
        assert read == held and written == held
    # chunk-causal structure: chunk i streams i+1 K pages, so QK passes
    # per pool page sum to group * (1 + 2 + ... + npg)
    group, npg = 4 // 2, 3
    qk = sum(1 for e in plan.events if e.op == "prefill_qk")
    assert qk == group * npg * (npg + 1) // 2
    # weight-streaming GEMMs present for every projection
    weights = {n for n, s in plan.tensors.items() if s.kind == "weight"}
    assert weights == {"wqkv", "wo", "w1", "w2"}


def test_prefill_plan_multi_layer_chains_and_replays():
    from repro.accesys.pipeline import replay
    from repro.accesys.system import default_system
    pt = _held_table()
    plan = pt.prefill_plan(0, 20, n_q_heads=4, d_model=64, d_ff=128,
                           n_layers=2)
    plan.validate()
    assert "L0.wqkv" in plan.tensors and "L1.wqkv" in plan.tensors
    # layer 0 output feeds layer 1's QKV projection
    assert plan.tensors["L0.prefill_out"].rows == 20
    for mode in ("DM", "DC", "DevMem"):
        r = replay(default_system(mode, dtype="fp16"), plan)
        assert r.total_s > 0 and r.compute_s > 0 and r.host_s > 0
        assert all(v >= 0 for v in r.buckets().values())


# -------------------------------------------------------- batched trace
def _mixed_trace_plans():
    pt = PageTable(PagedCacheConfig(
        n_pages=32, page_tokens=8, n_kv_heads=2, head_dim=16,
        max_pages_per_seq=4, dtype="float16"), max_seqs=3)
    plans = []
    for slot, ln in enumerate((20, 9, 17)):
        assert pt.alloc_seq(slot, ln)
        pt.note_tokens(slot, ln)
        plans.append(pt.prefill_plan(slot, ln, n_q_heads=4,
                                     d_model=64, d_ff=128, n_layers=2))
    for step in range(4):
        plans.append(pt.decode_step_plan([0, 1, 2], n_q_heads=4,
                                         n_layers=2))
    return plans


@pytest.mark.parametrize("mode,dram", [("DM", None), ("DC", None),
                                       ("DevMem", "HBM2")])
def test_replay_trace_engine_parity_and_attribution(mode, dram):
    """ONE batched compiled replay of a mixed prefill+decode trace must
    equal the sequential event replay on every aggregate field AND on
    every per-plan duration; durations sum to the total."""
    from repro.accesys.components import DRAM
    from repro.accesys.pipeline import replay_trace
    from repro.accesys.system import default_system
    plans = _mixed_trace_plans()
    mk = lambda: default_system(mode, dtype="fp16",
                                dram=DRAM(dram) if dram else None)
    r_e, per_e = replay_trace(mk(), plans, engine="event")
    r_c, per_c = replay_trace(mk(), plans, engine="compiled")
    np.testing.assert_allclose(per_c, per_e, rtol=1e-9)
    for f in dataclasses.fields(r_e):
        a, b = getattr(r_e, f.name), getattr(r_c, f.name)
        if isinstance(a, int):
            assert a == b, (f.name, a, b)
        else:
            assert b == pytest.approx(a, rel=1e-9, abs=1e-30), \
                (f.name, a, b)
    assert np.all(per_c > 0)
    assert per_c.sum() == pytest.approx(r_c.total_s, rel=1e-9)


def test_replay_trace_shares_page_interning_across_steps():
    """The batched replay's SMMU footprint is the union of pages the
    trace touches, not the per-plan sum — consecutive steps re-stream
    the same resident pool."""
    from repro.accesys.pipeline import replay_trace
    from repro.accesys.system import default_system
    plans = _mixed_trace_plans()
    sched = P.PlanSchedule("trace", [(p, 1) for p in plans])
    cp = sched.compile()
    assert len(cp.page_keys) < sum(len({e.page for e in p.events
                                        if e.page is not None})
                                   for p in plans)
    r, per = replay_trace(default_system("DC", dtype="fp16"), sched)
    assert len(per) == len(plans) and r.total_s > 0


def test_replay_trace_rejects_sampled_plans():
    from repro.accesys.pipeline import replay_trace
    from repro.accesys.system import default_system
    sampled = P.gemm_plan(256, 256, 2048, "int8", sample_stride=3)
    assert sampled.sampled_steps < sampled.total_steps
    with pytest.raises(ValueError, match="sampled"):
        replay_trace(default_system("DC"), [sampled])


# -------------------------------------------------- engine + sim report
@pytest.fixture(scope="module")
def reduced_engine_setup():
    import jax
    from repro.configs import get_reduced
    from repro.models.model import Model
    cfg = get_reduced("qwen2_0_5b")
    params = Model(cfg, remat="none").init(jax.random.PRNGKey(0))
    return cfg, params


def _run_recorded(cfg, params, n_req=6, **engine_kw):
    from repro.serving.engine import Request, ServingEngine
    rng = np.random.default_rng(3)
    eng = ServingEngine(cfg, params, record_plans=True, **engine_kw)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, 250, size=6).astype(np.int32),
                    max_new_tokens=3) for i in range(n_req)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_steps=500)
    return eng, reqs


def test_engine_records_request_centric_trace(reduced_engine_setup):
    cfg, params = reduced_engine_setup
    eng, reqs = _run_recorded(cfg, params, slots=2, max_seq=32)
    pre = [r for r in eng.trace if r.kind == "prefill"]
    dec = [r for r in eng.trace if r.kind == "decode"]
    assert len(pre) == len(reqs)
    assert {r.uids[0] for r in pre} == {r.uid for r in reqs}
    # decode plans are multi-layer GQA: model has n_heads > n_kv_heads
    assert cfg.n_heads > cfg.n_kv_heads
    pools = {e.page[0] for e in dec[0].plan.events
             if e.kind is P.EventKind.DMA_IN}
    assert f"L{cfg.n_layers - 1}.k" in pools
    # every decode token is attributed to a live uid at that step
    for rec in dec:
        assert len(rec.slots) == len(rec.uids) >= 1
    assert eng.step_plans == [r.plan for r in dec]


def test_simulated_ttft_tpot_fold_back_onto_requests(
        reduced_engine_setup):
    from repro.accesys.system import default_system
    from repro.serving.sim_report import simulate_serving_trace
    cfg, params = reduced_engine_setup
    eng, reqs = _run_recorded(cfg, params, slots=2, max_seq=32)
    rep = simulate_serving_trace(default_system("DC", dtype="fp16"),
                                 eng.trace)
    assert len(rep.requests) == len(reqs)
    by_uid = {r.uid: r for r in rep.requests}
    dec_steps = {u: 0 for u in by_uid}
    for rec in eng.trace:
        if rec.kind == "decode":
            for u in rec.uids:
                dec_steps[u] += 1
    for r in reqs:
        sim = by_uid[r.uid]
        assert sim.ttft_s > 0
        assert sim.n_tokens == 1 + dec_steps[r.uid] == len(r.output)
        if dec_steps[r.uid]:
            assert sim.tpot_s > 0
    # queueing shows up: with 2 slots and 6 requests, the last-admitted
    # request waits behind earlier completions
    ttfts = [by_uid[r.uid].ttft_s for r in reqs]
    assert max(ttfts) > min(ttfts)
    pct = rep.percentiles()
    assert pct["requests"] == len(reqs)
    assert pct["ttft_p99_us"] >= pct["ttft_p50_us"] > 0
    assert pct["tpot_p99_us"] >= pct["tpot_p50_us"] > 0
    assert rep.per_event_s.sum() == pytest.approx(rep.total_s,
                                                  rel=1e-9)


@pytest.mark.parametrize("device", [False, True])
def test_pages_a_retirement_frees_go_to_growth_only_in_a_plan_step(
        reduced_engine_setup, device):
    """Slot 0 retires in the step in which slot 1 crosses a page
    boundary.  A plan step grows and retires slot by slot, so slot 1
    takes the page slot 0 has just freed; a device step wrote every
    slot's token before anything retires, so the table grows for the
    whole batch first and slot 1 takes a page from the free list."""
    from repro.serving.engine import Request, ServingEngine
    cfg, params = reduced_engine_setup
    reqs = [Request(uid=i, prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=n) for i, n in enumerate((2, 4))]
    if device:
        eng = ServingEngine(cfg, params, slots=2, max_seq=32,
                            record_plans=True)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
    else:
        eng = ServingEngine(cfg, plan_only=True, slots=2, max_seq=32)
        eng.run_open_loop(reqs, np.zeros(2), prefill_chunk_tokens=8)
    dec = [r for r in eng.trace if r.kind == "decode"]
    assert [d.slots for d in dec[:2]] == [(0, 1), (1,)]
    pages = {e.page[1] for e in dec[1].plan.events
             if getattr(e, "page", None) and e.page[0] == "L0.k"}
    assert sorted(pages) == ([1, 3] if device else [1, 2])


def test_engine_defers_admission_when_pool_full_then_readmits(
        reduced_engine_setup):
    """full -> drain -> re-admit: a shadow pool holding only 2 prompts
    defers the rest of the queue instead of raising, retirements free
    pages, every request still completes, and outputs match the
    unconstrained engine (greedy decode is batch-invariant)."""
    cfg, params = reduced_engine_setup
    # prompts are 6 tokens, max_new_tokens=3 -> final len 8 == one page
    eng, reqs = _run_recorded(cfg, params, slots=4, max_seq=16,
                              kv_pool_pages=2)
    assert eng.deferred_admissions > 0
    assert all(len(r.output) == r.max_new_tokens for r in reqs)
    assert not eng.queue
    # page reuse across re-admissions: never more than 2 pages live
    assert eng.sched.table.pages_in_use == 0
    eng.sched.table.validate()              # partitions intact post-drain
    prefills = [r for r in eng.trace if r.kind == "prefill"]
    assert len(prefills) == len(reqs)
    free_eng, free_reqs = _run_recorded(cfg, params, slots=4,
                                        max_seq=16)
    assert free_eng.deferred_admissions == 0
    assert [r.output for r in reqs] == [r.output for r in free_reqs]


def test_conservative_admission_survives_decode_growth(
        reduced_engine_setup):
    """A capped pool with requests whose decode growth crosses a page
    boundary must never crash mid-run: admission reserves the max
    length, so only one request runs at a time here and the rest
    defer until it retires."""
    from repro.serving.engine import Request, ServingEngine
    cfg, params = reduced_engine_setup
    rng = np.random.default_rng(5)
    eng = ServingEngine(cfg, params, slots=4, max_seq=16,
                        record_plans=True, kv_pool_pages=2)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, 250, size=6).astype(np.int32),
                    max_new_tokens=5)        # final len 10 -> 2 pages
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_steps=500)     # must not RuntimeError
    assert all(len(r.output) == r.max_new_tokens for r in reqs)
    assert eng.deferred_admissions > 0
    assert eng.sched.table.pages_in_use == 0
    eng.sched.table.validate()


def test_never_fitting_request_raises_instead_of_livelocking(
        reduced_engine_setup):
    from repro.serving.engine import Request, ServingEngine
    cfg, params = reduced_engine_setup
    eng = ServingEngine(cfg, params, slots=2, max_seq=32,
                        record_plans=True, kv_pool_pages=1)
    eng.submit(Request(uid=0,
                       prompt=np.arange(1, 13).astype(np.int32),
                       max_new_tokens=4))    # needs 2 pages, pool has 1
    with pytest.raises(ValueError, match="can never hold"):
        eng.run_until_drained(max_steps=50)


# ------------------------------------------------------ PageTable churn
def test_page_table_growth_across_boundaries_and_exhaustion_no_leak():
    pt = PageTable(PagedCacheConfig(
        n_pages=4, page_tokens=8, n_kv_heads=2, head_dim=16,
        max_pages_per_seq=4, dtype="float16"), max_seqs=2)
    assert pt.alloc_seq(0, 5)                  # 1 page
    assert pt.note_tokens(0, 8) and pt.held[0] == 1
    assert pt.note_tokens(0, 9) and pt.held[0] == 2   # crossed boundary
    assert pt.note_tokens(0, 17) and pt.held[0] == 3
    assert pt.alloc_seq(1, 3)                  # last free page
    assert pt.pages_in_use == 4
    # exhausted: growth fails but must not leak the pages already held
    assert not pt.note_tokens(1, 9)
    assert pt.held[1] == 1 and pt.pages_in_use == 4
    pt.free_seq(0)
    assert pt.pages_in_use == 1
    assert pt.note_tokens(1, 9) and pt.held[1] == 2   # drain -> regrow
    pt.validate()                      # free/owned partition the pool


def test_recorded_decode_plan_never_references_freed_pages():
    pt = PageTable(PagedCacheConfig(
        n_pages=8, page_tokens=8, n_kv_heads=2, head_dim=16,
        max_pages_per_seq=4, dtype="float16"), max_seqs=3)
    for slot, ln in enumerate((20, 9, 17)):
        assert pt.alloc_seq(slot, ln)
        pt.note_tokens(slot, ln)
    freed = {int(p) for p in pt.tables[1, :int(pt.held[1])]}
    pt.free_seq(1)
    plan = pt.decode_step_plan([0, 2], n_q_heads=4, n_layers=2)
    touched = {e.page[1] for e in plan.events
               if e.kind is P.EventKind.DMA_IN}
    assert not touched & freed
    # re-admission reuses the freed physical pages (LIFO free list)
    assert pt.alloc_seq(1, 9)
    reused = {int(p) for p in pt.tables[1, :int(pt.held[1])]}
    assert reused <= freed
    plan2 = pt.decode_step_plan([0, 1, 2])
    touched2 = {e.page[1] for e in plan2.events
                if e.kind is P.EventKind.DMA_IN}
    assert reused <= touched2
    pt.validate()
