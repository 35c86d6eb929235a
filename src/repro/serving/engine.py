"""Continuous-batching serving engine.

Slot-based continuous batching over the Model API: B decode slots run in
a single jitted decode step (per-slot cache lengths — mixed-progress
sequences in one batch); finished slots are recycled and newly admitted
requests are prefetched (prefilled) into their slot between decode
steps. This is the end-to-end driver the paper's inference setting
dictates (serve batched requests, GEMMs streamed, host orchestrates).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models.model import Model


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (T,) int32
    max_new_tokens: int = 16
    submitted_s: float = 0.0
    admitted_s: Optional[float] = None  # popped off the queue
    first_token_s: Optional[float] = None
    done_s: Optional[float] = None
    output: list = dataclasses.field(default_factory=list)
    # index into the engine's plan trace at submission time — the
    # simulated-arrival anchor for TTFT attribution (0 == trace start)
    arrival_event: int = 0


@dataclasses.dataclass
class PlanRecord:
    """One priced event of a recorded serving trace: a prompt prefill
    (one per admission, or one per chunk under chunked prefill) or a
    batched multi-layer decode step, tagged with the engine step index
    and the slot -> request-uid mapping so simulated time folds back
    onto individual requests.  ``uids == (-1,)`` marks the shared
    prefix-cache prefill, which belongs to no request.  ``swap_out`` /
    ``swap_in`` records carry a preempted request's KV traffic to and
    from host (``n_tokens`` = cached tokens at the swap point)."""
    kind: str             # "prefill" | "decode" | "swap_out" | "swap_in"
    step_idx: int                   # engine decode-step counter
    slots: tuple                    # slot ids this plan covers
    uids: tuple                     # request uid per slot
    plan: object                    # core.plan.StreamPlan
    arrival_event: int = 0          # prefill: requester's arrival index
    n_tokens: int = 0               # prefill: tokens this chunk covers


def arrival_times(kind: str, n: int, qps: float, seed: int = 0, *,
                  burst_factor: float = 4.0, burst_len: float = 16.0,
                  period_s: float = 60.0, depth: float = 0.8
                  ) -> np.ndarray:
    """Seeded open-loop arrival process: ``n`` absolute arrival times
    at a mean offered rate of ``qps`` requests/second.  Deterministic
    in ``(kind, n, qps, seed, shape params)``.

    - ``poisson``: i.i.d. exponential gaps (memoryless).
    - ``bursty``: exponential gaps scaled by alternating quiet/hot
      runs of geometric length ``burst_len`` — hot gaps shrink by
      ``burst_factor``, quiet gaps stretch to keep the mean rate.
    - ``diurnal``: gaps modulated by ``1 + depth*sin(2*pi*t/period_s)``
      — a load wave (period compressed to seconds so a 10k-request
      trace spans several cycles)."""
    if qps <= 0:
        raise ValueError(f"qps must be > 0, got {qps}")
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / qps, size=n)
    if kind == "poisson":
        pass
    elif kind == "bursty":
        lo = 1.0 / burst_factor
        hi = 2.0 - lo                 # quiet stretch preserving mean
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
        raise ValueError(
            f"unknown arrival process {kind!r} — expected poisson, "
            "bursty, or diurnal")
    return np.cumsum(gaps)


@dataclasses.dataclass
class EngineStats:
    decode_steps: int = 0
    prefills: int = 0
    tokens_out: int = 0
    wall_s: float = 0.0
    preemptions: int = 0
    swapped_pages: int = 0          # device pages moved host-ward
    host_syncs: int = 0             # device->host reads (``_fetch``)
    # False when the run hit ``max_steps`` with work still queued or
    # in flight — a truncated sim must never masquerade as a complete
    # one (pair with ``unfinished_uids()`` to censor the report)
    drained: bool = True

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_out / max(self.wall_s, 1e-9)


class ServingEngine:
    """``record_plans=True`` shadows the dense decode cache with a
    driver-side ``PageTable`` (no device pools) and records a
    request-centric plan trace (``trace``): one ``prefill_plan`` per
    admission and one multi-layer GQA ``decode_step_plan`` per engine
    step, each tagged with ``(step_idx, slot -> uid)`` — page ids and
    valid lengths track the REAL batch composition (admissions,
    retirements, page churn) over the run, so one batched accesys
    replay prices the whole trace and folds simulated time back onto
    individual requests (``serving.sim_report``).

    Admission against the shadow pool is CONSERVATIVE: a request is
    admitted only if the free list can hold its maximum length
    (prompt + max_new_tokens, capped at ``max_seq``) on top of the
    worst-case remaining growth of every already-admitted request —
    so decode-time page growth can never fail and the engine never
    crashes mid-run on pool pressure.  Otherwise the request is
    DEFERRED at the head of the queue (FIFO order preserved) until
    retirements drain enough pages.  A request whose maximum length
    cannot fit even an empty pool raises ``ValueError`` at admission
    time (a configuration error deferral would turn into a livelock).
    ``kv_pool_pages`` caps the pool (default: every slot can grow to
    ``max_seq``, so only explicit caps ever defer)."""

    def __init__(self, cfg: ModelConfig, params=None, *, slots: int = 4,
                 max_seq: int = 256, eos_token: Optional[int] = None,
                 record_plans: bool = False, kv_page_tokens: int = 8,
                 kv_dtype: str = "float16",
                 kv_pool_pages: Optional[int] = None,
                 plan_only: bool = False, prefix_tokens: int = 0,
                 prefix_caching: bool = False, templated: bool = True):
        """``plan_only=True`` skips model/cache/jit construction
        entirely (``params`` unused) and drives the shadow PageTable
        alone — the open-loop capacity-planning mode, where generated
        token VALUES never matter and only the plan trace does.
        ``prefix_tokens`` prepends a shared system prompt to every
        request; with ``prefix_caching=True`` its pages are interned
        once per trace (``reserve_prefix``) and every request maps
        them read-only, otherwise each request re-prefills them.
        ``templated`` (default) emits template-instanced plan records
        — each decode/prefill/swap record is a compiled-skeleton
        page-id relabel instead of a fresh event graph, pricing
        bitwise-identically (``templated=False`` restores event-built
        records; ``.events`` on a templated record rebuilds them on
        demand)."""
        self.cfg = cfg
        self.plan_only = plan_only
        record_plans = record_plans or plan_only
        self.model = None if plan_only else Model(cfg, remat="none")
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.eos = eos_token
        self.cache = None if plan_only else \
            self.model.init_cache(slots, max_seq)
        self.slot_req: list[Optional[Request]] = [None] * slots
        self.queue: deque[Request] = deque()
        self.stats = EngineStats()
        self._next_tokens = np.zeros((slots,), np.int32)
        self._remaining = np.zeros((slots,), np.int32)
        # each slot's cache length, counted on the host: the device's
        # ``cache["len"]`` on the jitted path, the plan's on plan-only
        self._lens = np.zeros((slots,), np.int32)
        self.trace: list[PlanRecord] = []
        self.n_records = 0          # records emitted (trace + sinks)
        self.n_finished = 0
        self.deferred_admissions = 0
        self.sim_t = 0.0            # open-loop simulated clock
        self._sink: Optional[list] = None
        self._prefilling: dict = {}  # slot -> [req, done, total]
        # ---- preemption / swap state (open-loop path)
        self._preempt_policy = "none"
        self._stall_budget_s = 0.0
        self._debug_invariants = False
        self._defer_since: Optional[float] = None  # head's wait start
        self._swapped: dict = {}    # uid -> (n_pages, tokens, remaining,
        #                             prefill_total | None)
        self._progress: dict = {}   # slot -> tokens since (re)admission
        self._admit_seq: dict = {}  # slot -> admission order counter
        self._admit_counter = 0
        self._prefix_tokens = int(prefix_tokens)
        self._prefix_pages: Optional[np.ndarray] = None
        self._prefix_recorded = False
        self._table = None
        if record_plans:
            from repro.serving.kv_cache import (PagedCacheConfig,
                                                PageTable)
            pages_per_seq = -(-max_seq // kv_page_tokens)
            self._table = PageTable(
                PagedCacheConfig(
                    n_pages=kv_pool_pages or slots * pages_per_seq,
                    page_tokens=kv_page_tokens,
                    n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.resolved_head_dim,
                    max_pages_per_seq=pages_per_seq,
                    dtype=kv_dtype),
                max_seqs=slots, templated=templated)
        if self._prefix_tokens:
            if self._table is None:
                raise ValueError("prefix_tokens needs record_plans")
            if self._prefix_tokens % kv_page_tokens:
                raise ValueError(
                    f"prefix_tokens={prefix_tokens} must be a multiple "
                    f"of kv_page_tokens={kv_page_tokens} (chunked "
                    "prefill spans are page-aligned)")
            if prefix_caching:
                self._prefix_pages = self._table.reserve_prefix(
                    self._prefix_tokens // kv_page_tokens)

        if not plan_only:
            self._decode = jax.jit(self.model.decode_step)
            self._prefill1 = jax.jit(
                lambda p, b: self.model.prefill(p, b, max_seq))

    @property
    def step_plans(self) -> list:
        """The decode plans of the recorded trace, in step order
        (compatibility view of ``trace``)."""
        return [r.plan for r in self.trace if r.kind == "decode"]

    # ------------------------------------------------------------- API
    def _record(self, rec: PlanRecord) -> int:
        """Append a trace record (to the streaming sink when one is
        installed) and return its global index — the ``arrival_event``
        coordinate space."""
        idx = self.n_records
        self.n_records += 1
        (self.trace if self._sink is None else self._sink).append(rec)
        return idx

    def submit(self, req: Request):
        req.submitted_s = time.perf_counter()
        req.arrival_event = self.n_records
        self.queue.append(req)

    def _max_pages(self, req: Request) -> int:
        """Worst-case pages ``req`` can ever hold (shared prefix pages
        included): its final cache length is min(prefix + prompt +
        max_new_tokens - 1, max_seq - 1), padded to max_seq here for
        safety."""
        max_len = min(self._prefix_tokens + len(req.prompt)
                      + req.max_new_tokens, self.max_seq)
        return -(-max_len // self._table.cfg.page_tokens)

    def _can_admit(self, req: Request) -> bool:
        t = self._table
        need = self._max_pages(req)
        if need > min(t.cfg.n_pages, t.cfg.max_pages_per_seq):
            raise ValueError(
                f"request uid={req.uid} needs {need} KV pages at its "
                f"max length but the pool can never hold that "
                f"(n_pages={t.cfg.n_pages}, "
                f"max_pages_per_seq={t.cfg.max_pages_per_seq})")
        if self._prefix_pages is not None:
            need -= len(self._prefix_pages)   # shared pages are mapped
        # pages admitted slots may still claim while decoding
        growth = sum(self._max_pages(r) - int(t.held[s])
                     for s, r in enumerate(self.slot_req)
                     if r is not None)
        return len(t._free) >= need + growth

    def _fetch(self, x, dtype=None) -> np.ndarray:
        """Device array ``x`` on the host. Every device->host read of the
        jitted serving path goes through here and is counted in
        ``stats.host_syncs``: the host waits for the device at each."""
        self.stats.host_syncs += 1
        return np.asarray(x, dtype)

    def _admit(self):
        for slot in range(self.slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue[0]
            if self._table is not None:
                if not self._can_admit(req):
                    # defer admission — the request stays queued until
                    # retirements free enough pages for its max length
                    self.deferred_admissions += 1
                    return
                if not self._table.alloc_seq(slot, len(req.prompt)):
                    raise RuntimeError(       # _can_admit guarantees it
                        "shadow KV table out of pages at admission")
            with TraceAnnotation("engine.admit", uid=req.uid, slot=slot,
                                 prompt_len=len(req.prompt)):
                self.queue.popleft()
                req.admitted_s = time.perf_counter()
                self._admit_one(req, slot)

    def _admit_one(self, req: Request, slot: int):
        with TraceAnnotation("engine.prefill", uid=req.uid):
            cache1, logits = self._prefill1(
                self.params, {"tokens": jnp.asarray(req.prompt[None])})
        self.stats.prefills += 1
        with TraceAnnotation("engine.splice", uid=req.uid, slot=slot):
            # splice the single-seq cache into this slot
            self.cache = jax.tree.map(
                lambda full, one: (
                    full.at[:, slot].set(one[:, 0])
                    if full.ndim >= 2 and full.shape[1] == self.slots
                    else full),
                self.cache, cache1)
            self.cache["len"] = self.cache["len"].at[slot].set(
                cache1["len"][0])
        self._lens[slot] = len(req.prompt)      # what the prefill wrote
        with TraceAnnotation("engine.first_token", uid=req.uid):
            tok = int(self._fetch(
                jnp.argmax(logits[0, :self.cfg.vocab_size])))
        req.first_token_s = time.perf_counter()
        req.output.append(tok)
        self._next_tokens[slot] = tok
        self._remaining[slot] = req.max_new_tokens - 1
        self.slot_req[slot] = req
        self.stats.tokens_out += 1
        if self._table is not None:
            if not self._table.note_tokens(slot, int(self._lens[slot])):
                raise RuntimeError("shadow KV table out of pages")
            self._record(PlanRecord(
                "prefill", self.stats.decode_steps, (slot,),
                (req.uid,),
                self._table.prefill_plan(
                    slot, len(req.prompt),
                    n_q_heads=self.cfg.n_heads,
                    d_model=self.cfg.d_model, d_ff=self.cfg.d_ff,
                    n_layers=self.cfg.n_layers),
                arrival_event=req.arrival_event,
                n_tokens=len(req.prompt)))

    def _retire(self, slot: int):
        req = self.slot_req[slot]
        req.done_s = time.perf_counter()
        self.slot_req[slot] = None
        self.n_finished += 1
        self.cache["len"] = self.cache["len"].at[slot].set(0)
        self._lens[slot] = 0
        if self._table is not None:
            self._table.free_seq(slot)

    def step(self):
        """One engine iteration: admit + one batched decode step.

        Host spans (``jax.profiler.TraceAnnotation``, recorded only
        while a profiler trace runs): ``engine.step`` holds the
        ``engine.admit`` of each admission (``engine.prefill``,
        ``engine.splice``, ``engine.first_token``), then
        ``engine.decode``, ``engine.sample`` and ``engine.retire``.
        Spans of one step share ``step`` (``stats.decode_steps`` at
        entry), spans of one request share ``uid``."""
        idx = self.stats.decode_steps
        with TraceAnnotation("engine.step", step=idx,
                             active=self.slots - self.slot_req.count(None)):
            return self._step(idx)

    def _step(self, idx: int) -> bool:
        self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return False
        if self._table is not None:
            # the step streams each active slot's currently-resident KV
            # pages; the new token's KV lands before the next step
            self._record(PlanRecord(
                "decode", idx, tuple(active),
                tuple(self.slot_req[s].uid for s in active),
                self._table.decode_step_plan(
                    active, n_q_heads=self.cfg.n_heads,
                    n_layers=self.cfg.n_layers)))
        with TraceAnnotation("engine.decode", step=idx):
            toks = jnp.asarray(self._next_tokens)
            self.cache, logits = self._decode(self.params, self.cache, toks)
        self.stats.decode_steps += 1
        self._lens += 1         # the step moves every slot, free ones too
        if self._table is not None:
            for slot in active:
                if not self._table.note_tokens(slot,
                                               int(self._lens[slot])):
                    raise RuntimeError("shadow KV table out of pages")
        with TraceAnnotation("engine.sample", step=idx):
            nxt = self._fetch(jnp.argmax(
                logits[:, :self.cfg.vocab_size], axis=-1), np.int32)
        with TraceAnnotation("engine.retire", step=idx) as span:
            retired = 0
            for slot in active:
                req = self.slot_req[slot]
                tok = int(nxt[slot])
                req.output.append(tok)
                self.stats.tokens_out += 1
                self._next_tokens[slot] = tok
                self._remaining[slot] -= 1
                hit_eos = self.eos is not None and tok == self.eos
                if self._remaining[slot] <= 0 or hit_eos or \
                        self._lens[slot] >= self.max_seq - 1:
                    self._retire(slot)
                    retired += 1
            span.set_metadata(retired=retired)
        return True

    def run_until_drained(self, max_steps: int = 10_000) -> EngineStats:
        t0 = time.perf_counter()
        steps = 0
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and steps < max_steps:
            self.step()
            steps += 1
        self.stats.wall_s = time.perf_counter() - t0
        # hitting max_steps with work left is a TRUNCATED run — flag
        # it so partial stats can't pass for a drained queue
        self.stats.drained = not self.queue and \
            all(r is None for r in self.slot_req)
        return self.stats

    # ------------------------------------------ open-loop (plan-only)
    def unfinished_uids(self) -> tuple:
        """Uids the engine has accepted but not retired — running,
        prefilling, or queued.  The censoring set for trace-end
        percentile reports."""
        live = [r.uid for r in self.slot_req if r is not None]
        live += [r.uid for r in self.queue]
        return tuple(live)

    def _pick_victim(self, exclude: Optional[int] = None
                     ) -> Optional[int]:
        """Choose a running slot to preempt, or None.  Only slots that
        have produced at least one token since (re)admission are
        eligible — preempting zero-progress work can livelock two
        large requests into evicting each other forever, while
        requiring progress guarantees every preemption cycle advances
        someone.  ``lifo``: most recently admitted (vLLM's default —
        the newest request has the least sunk cost); ``longest``: most
        own pages held (frees the most memory per eviction)."""
        cands = [s for s, r in enumerate(self.slot_req)
                 if r is not None and s != exclude
                 and self._progress.get(s, 0) > 0]
        if not cands:
            return None
        if self._preempt_policy == "lifo":
            return max(cands, key=lambda s: self._admit_seq[s])
        # "longest": frees the most device pages
        t = self._table
        return max(cands, key=lambda s: (int(t.held[s])
                                         - int(t.shared[s]),
                                         self._admit_seq[s]))

    def _preempt(self, slot: int):
        """Evict ``slot``: record the page-aligned swap-out of its
        written KV (``PageTable.swap_out`` frees the device pages),
        stash its exact progress for resume, and re-queue it directly
        BEHIND the queue head — the head's admission is the point of
        the eviction, and the victim resumes right after it."""
        req = self.slot_req[slot]
        pf = self._prefilling.pop(slot, None)
        if pf is not None:
            tokens, remaining, total = pf[1], -1, pf[2]
        else:
            tokens = int(self._lens[slot])
            remaining, total = int(self._remaining[slot]), None
        plan, n_swap = self._table.swap_out(
            slot, tokens, req.uid, n_layers=self.cfg.n_layers)
        if plan is not None:
            self._record(PlanRecord(
                "swap_out", self.stats.decode_steps, (slot,),
                (req.uid,), plan, arrival_event=req.arrival_event,
                n_tokens=tokens))
        self._swapped[req.uid] = (n_swap, tokens, remaining, total)
        self.slot_req[slot] = None
        self._lens[slot] = 0
        self._progress.pop(slot, None)
        self.stats.preemptions += 1
        self.stats.swapped_pages += n_swap
        if self.queue:
            head = self.queue.popleft()
            self.queue.appendleft(req)
            self.queue.appendleft(head)
        else:
            self.queue.appendleft(req)

    def _resume_or_start(self, slot: int, req: Request):
        """Bind ``req`` to ``slot``: allocate its device pages and
        either enter the chunked-prefill state machine (fresh request,
        or one preempted mid-prefill — it continues at the chunk
        boundary it stopped on) or rejoin the decode batch (preempted
        while decoding), recording the swap-in DMA first."""
        swap = self._swapped.pop(req.uid, None)
        full = self._prefix_tokens + len(req.prompt)
        alloc_tokens = full if swap is None else max(full, swap[1])
        if not self._table.alloc_seq(slot, alloc_tokens,
                                     prefix=self._prefix_pages):
            raise RuntimeError(       # _can_admit guarantees it
                "shadow KV table out of pages at admission")
        self.slot_req[slot] = req
        self._progress[slot] = 0
        self._admit_seq[slot] = self._admit_counter
        self._admit_counter += 1
        if swap is None:
            done = self._prefix_tokens \
                if self._prefix_pages is not None else 0
            self._prefilling[slot] = [req, done, full]
            return
        n_swap, tokens, remaining, total = swap
        if n_swap:
            self._record(PlanRecord(
                "swap_in", self.stats.decode_steps, (slot,),
                (req.uid,),
                self._table.swap_in_plan(n_swap, req.uid,
                                         n_layers=self.cfg.n_layers),
                arrival_event=req.arrival_event, n_tokens=tokens))
        if total is not None:            # was mid-prefill: continue it
            self._prefilling[slot] = [req, tokens, total]
        else:                            # was decoding: rejoin batch
            self._lens[slot] = tokens
            if not self._table.note_tokens(slot, tokens):
                raise RuntimeError(   # alloc_seq covered these pages
                    "swap-in lost pages the allocation reserved")
            self._remaining[slot] = remaining

    def _admit_open(self):
        """Open-loop admission: same conservative capacity check as
        ``_admit``, but the admitted request enters the chunked-prefill
        state machine instead of being prefilled whole — long prompts
        cost several engine steps, not one monolithic stall.

        With a preemption policy armed, a head deferred longer than
        the stall budget evicts victims (``_pick_victim``) until its
        conservative reservation fits — head-of-line blocking degrades
        into swap thrash instead of unbounded queueing."""
        for slot in range(self.slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            if not self._can_admit(self.queue[0]):
                if self._defer_since is None:
                    self._defer_since = self.sim_t
                if self._preempt_policy != "none" and \
                        self.sim_t - self._defer_since >= \
                        self._stall_budget_s:
                    while not self._can_admit(self.queue[0]):
                        victim = self._pick_victim()
                        if victim is None:
                            break
                        self._preempt(victim)
                if not self._can_admit(self.queue[0]):
                    self.deferred_admissions += 1
                    return
            self._defer_since = None
            req = self.queue.popleft()
            self._resume_or_start(slot, req)

    def _retire_open(self, slot: int):
        req = self.slot_req[slot]
        req.done_s = self.sim_t
        self.slot_req[slot] = None
        self.n_finished += 1
        self._lens[slot] = 0
        self._progress.pop(slot, None)
        self._table.free_seq(slot)

    def _prefill_chunk_open(self, slot: int, chunk: int,
                            est_prefill_s_per_token: float) -> float:
        """Advance one slot's chunked prefill by one page-aligned span
        and record its plan; on the last chunk the slot joins the
        decode batch (its ``first token`` is the prefill's)."""
        req, done, total = self._prefilling[slot]
        end = total if total - done <= chunk else done + chunk
        self._record(PlanRecord(
            "prefill", self.stats.decode_steps, (slot,), (req.uid,),
            self._table.prefill_plan(
                slot, total, span=(done, end),
                n_q_heads=self.cfg.n_heads, d_model=self.cfg.d_model,
                d_ff=self.cfg.d_ff, n_layers=self.cfg.n_layers),
            arrival_event=req.arrival_event, n_tokens=end - done))
        self.stats.prefills += 1
        self._progress[slot] = self._progress.get(slot, 0) + end - done
        if end == total:
            del self._prefilling[slot]
            self._lens[slot] = total
            if not self._table.note_tokens(slot, total):
                raise RuntimeError("shadow KV table out of pages")
            self._remaining[slot] = req.max_new_tokens - 1
            self.stats.tokens_out += 1
            if self._remaining[slot] <= 0 or total >= self.max_seq - 1:
                self._retire_open(slot)       # prefill-only request
        else:
            self._prefilling[slot][1] = end
        return est_prefill_s_per_token * (end - done)

    def _step_open(self, chunk: int, est_step_s: float,
                   est_prefill_s_per_token: float) -> float:
        """One open-loop engine iteration: advance every in-flight
        chunked prefill by one span, then one batched decode step over
        the slots not still prefilling.  Returns the simulated time
        this step consumed (the admission clock — reported latencies
        come from the accesys replay, not from these estimates)."""
        dt = 0.0
        for slot in sorted(self._prefilling):
            dt += self._prefill_chunk_open(slot, chunk,
                                           est_prefill_s_per_token)
        active = [s for s, r in enumerate(self.slot_req)
                  if r is not None and s not in self._prefilling]
        if active:
            self._record(PlanRecord(
                "decode", self.stats.decode_steps, tuple(active),
                tuple(self.slot_req[s].uid for s in active),
                self._table.decode_step_plan(
                    active, n_q_heads=self.cfg.n_heads,
                    n_layers=self.cfg.n_layers)))
            self.stats.decode_steps += 1
            dt += est_step_s
            for slot in active:
                self._lens[slot] += 1
                self._progress[slot] = self._progress.get(slot, 0) + 1
                self.stats.tokens_out += 1
                self._remaining[slot] -= 1
                grew = self._table.note_tokens(slot,
                                               int(self._lens[slot]))
                if self._remaining[slot] <= 0 or \
                        int(self._lens[slot]) >= self.max_seq - 1:
                    self._retire_open(slot)
                elif not grew:
                    # mid-decode page growth failed — only reachable
                    # when the pool shrank under us (fault injection
                    # seizing pages breaks the conservative admission
                    # reservation).  Degrade gracefully: swap this
                    # slot out and resume it when pages return,
                    # instead of crashing the run.
                    if self._preempt_policy == "none":
                        raise RuntimeError(
                            "shadow KV table out of pages")
                    self._preempt(slot)
        return dt

    def open_loop_records(self, requests, arrival_s, *,
                          est_step_s: float = 1e-3,
                          est_prefill_s_per_token: float = 1e-4,
                          prefill_chunk_tokens: int = 64,
                          max_steps: int = 1_000_000,
                          preempt: str = "none",
                          stall_budget_s: float = 0.0,
                          faults=None,
                          debug_invariants: bool = False):
        """Generator driving an OPEN-loop run — requests arrive on the
        ``arrival_s`` clock whether or not the engine keeps up (the
        queue grows past saturation) — yielding ``PlanRecord``s as they
        are produced WITHOUT retaining them, so a 10k-request trace can
        stream straight into ``replay_trace_streamed`` in O(chunk)
        memory.  Plan-only: token values are never computed; the
        ``est_*`` rates only advance the simulated admission clock
        (calibrate them from a small priced probe trace — reported
        TTFT/TPOT always come from the replay itself).

        ``preempt`` arms graceful degradation under memory pressure:
        when the queue head has been deferred for more than
        ``stall_budget_s`` of simulated time, a running victim
        (``"lifo"``: newest admission; ``"longest"``: most pages) is
        swapped out to host (priced ``swap_out``/``swap_in`` records)
        and re-queued behind the head.  ``faults`` is an optional
        object whose ``on_step(engine, step_idx)`` is called once per
        iteration (``serving.faults`` injects pool seizures there);
        ``debug_invariants`` runs the ``serving.invariants`` validator
        every step and at drain.

        Deterministic: same requests + arrivals => identical records.
        Use ``run_open_loop`` to retain the trace instead."""
        if not self.plan_only or self._table is None:
            raise ValueError(
                "open_loop_records() needs plan_only=True (the jitted "
                "model path is closed-loop only)")
        if preempt not in ("none", "lifo", "longest"):
            raise ValueError(
                f"unknown preemption policy {preempt!r} — expected "
                "none, lifo, or longest")
        if stall_budget_s < 0:
            raise ValueError(
                f"stall_budget_s must be >= 0: {stall_budget_s}")
        self._preempt_policy = preempt
        self._stall_budget_s = float(stall_budget_s)
        self._debug_invariants = bool(debug_invariants)
        if prefill_chunk_tokens % self._table.cfg.page_tokens:
            raise ValueError(
                f"prefill_chunk_tokens={prefill_chunk_tokens} must be "
                f"page-aligned ({self._table.cfg.page_tokens} tokens)")
        reqs = list(requests)
        arr = np.asarray(arrival_s, float)
        if len(reqs) != arr.size:
            raise ValueError(
                f"{len(reqs)} requests but {arr.size} arrival times")
        buf: list = []
        self._sink = buf
        try:
            if self._prefix_pages is not None and \
                    not self._prefix_recorded:
                self._prefix_recorded = True
                self._record(PlanRecord(
                    "prefill", 0, (), (-1,),
                    self._table.shared_prefill_plan(
                        self._prefix_pages, self._prefix_tokens,
                        n_q_heads=self.cfg.n_heads,
                        d_model=self.cfg.d_model, d_ff=self.cfg.d_ff,
                        n_layers=self.cfg.n_layers),
                    n_tokens=self._prefix_tokens))
            i = 0
            steps = 0
            while i < len(reqs) or self.queue or \
                    any(r is not None for r in self.slot_req):
                if steps >= max_steps:
                    break
                busy = self.queue or \
                    any(r is not None for r in self.slot_req)
                if not busy and arr[i] > self.sim_t:
                    self.sim_t = float(arr[i])    # idle: jump ahead
                while i < len(reqs) and arr[i] <= self.sim_t:
                    req = reqs[i]
                    self.submit(req)
                    req.submitted_s = float(arr[i])
                    i += 1
                if faults is not None:
                    faults.on_step(self, steps)
                self._admit_open()
                dt = self._step_open(prefill_chunk_tokens, est_step_s,
                                     est_prefill_s_per_token)
                self.sim_t += dt
                if dt == 0.0 and self.queue and i < len(reqs) and \
                        all(r is None for r in self.slot_req):
                    # fully stalled on admission (nothing running,
                    # head deferred): open-loop time still passes —
                    # jump to the next arrival so the queue keeps
                    # growing instead of the loop spinning in place
                    self.sim_t = max(self.sim_t, float(arr[i]))
                if self._debug_invariants:
                    from repro.serving import invariants
                    invariants.check_step(self)
                steps += 1
                yield from buf
                buf.clear()
            self.stats.drained = i >= len(reqs) and not self.queue \
                and all(r is None for r in self.slot_req)
            if self._debug_invariants and self.stats.drained:
                from repro.serving import invariants
                invariants.check_drained(self)
        finally:
            self._sink = None

    def run_open_loop(self, requests, arrival_s, **kw) -> EngineStats:
        """Open-loop run retaining the full trace (small-n paths and
        tests; the load sweep streams ``open_loop_records`` instead)."""
        for rec in self.open_loop_records(requests, arrival_s, **kw):
            self.trace.append(rec)
        return self.stats
