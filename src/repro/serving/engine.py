"""Continuous-batching serving engine.

Slot-based continuous batching over the Model API: B decode slots run in
a single jitted decode step (per-slot cache lengths — mixed-progress
sequences in one batch); finished slots are recycled and newly admitted
requests are prefetched (prefilled) into their slot between decode
steps. ``serving.scheduler`` decides what to admit, prefill, decode and
retire; this module runs it on the model and records it. This is the
end-to-end serving loop the paper's inference setting dictates (serve
batched requests, GEMMs streamed, host orchestrates).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models.model import Model
from repro.serving.scheduler import PlanRecord, Scheduler


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
    """Drives one ``serving.scheduler.Scheduler`` (``sched``) and hands
    what it decides to each consumer it has: the jitted model, and with
    ``record_plans=True`` a host-side ``PageTable`` (no device pools)
    whose plan trace (``trace``) holds one ``prefill_plan`` per prefill
    span and one multi-layer GQA ``decode_step_plan`` per engine step,
    each tagged with ``(step_idx, slot -> uid)``, so one batched accesys
    replay prices the whole run and folds simulated time back onto each
    request (``serving.sim_report``).  ``kv_pool_pages`` caps the pool
    (default: every slot can grow to ``max_seq``, so only explicit caps
    ever defer an admission)."""

    def __init__(self, cfg: ModelConfig, params=None, *, slots: int = 4,
                 max_seq: int = 256, eos_token: Optional[int] = None,
                 record_plans: bool = False, kv_page_tokens: int = 8,
                 kv_dtype: str = "float16",
                 kv_pool_pages: Optional[int] = None,
                 plan_only: bool = False, prefix_tokens: int = 0,
                 prefix_caching: bool = False, templated: bool = True):
        """``plan_only=True`` builds no model, cache or jit (``params``
        unused) and drives the shadow PageTable alone — the open-loop
        capacity-planning mode, where only the plan trace matters.
        ``prefix_tokens`` (plan-only) prepends a shared system prompt to
        every request; with ``prefix_caching=True`` its pages are
        interned once per trace and every request maps them read-only,
        otherwise each request re-prefills them.  ``templated`` (default)
        emits template-instanced plan records that price
        bitwise-identically to event-built ones (``.events`` on a
        templated record rebuilds them on demand)."""
        if prefix_tokens and not plan_only:
            raise ValueError("prefix_tokens needs plan_only=True: the "
                             "model prefills no shared prefix")
        self.cfg = cfg
        self.model = None if plan_only else Model(cfg, remat="none")
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.cache = None if plan_only else \
            self.model.init_cache(slots, max_seq)
        self.stats = EngineStats()
        self._next_tokens = np.zeros((slots,), np.int32)
        self.trace: list[PlanRecord] = []
        self.n_records = 0          # records emitted (trace + sinks)
        self.sim_t = 0.0            # open-loop simulated clock
        self._sink: Optional[list] = None
        self._prefix_recorded = False
        table = None
        if record_plans or plan_only:
            from repro.serving.kv_cache import (PagedCacheConfig,
                                                PageTable)
            pages_per_seq = -(-max_seq // kv_page_tokens)
            table = PageTable(
                PagedCacheConfig(
                    n_pages=kv_pool_pages or slots * pages_per_seq,
                    page_tokens=kv_page_tokens,
                    n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.resolved_head_dim,
                    max_pages_per_seq=pages_per_seq,
                    dtype=kv_dtype),
                max_seqs=slots, templated=templated)
        self.sched = Scheduler(
            cfg, self.stats, self._record, slots=slots, max_seq=max_seq,
            eos=eos_token, table=table, prefix_tokens=prefix_tokens,
            prefix_caching=prefix_caching,
            clock=(lambda: self.sim_t) if plan_only else
            time.perf_counter)

        if not plan_only:
            self._decode = jax.jit(self.model.decode_step)
            self._prefill1 = jax.jit(
                lambda p, b: self.model.prefill(p, b, max_seq))

    # the scheduler's state that callers read
    queue = property(lambda self: self.sched.queue)
    slot_req = property(lambda self: self.sched.slot_req)
    n_finished = property(lambda self: self.sched.n_finished)
    deferred_admissions = property(
        lambda self: self.sched.deferred_admissions)

    @property
    def step_plans(self) -> list:
        """The decode plans of the recorded trace, in step order
        (compatibility view of ``trace``)."""
        return [r.plan for r in self.trace if r.kind == "decode"]

    # ------------------------------------------------------------- API
    def _record(self, rec: PlanRecord):
        """Append a trace record (to the streaming sink when one is
        installed); ``n_records`` counts them, the ``arrival_event``
        coordinate space."""
        self.n_records += 1
        (self.trace if self._sink is None else self._sink).append(rec)

    def submit(self, req: Request):
        req.submitted_s = time.perf_counter()
        req.arrival_event = self.n_records
        self.queue.append(req)

    def _fetch(self, x, dtype=None) -> np.ndarray:
        """Device array ``x`` on the host. Every device->host read of the
        jitted serving path goes through here and is counted in
        ``stats.host_syncs``: the host waits for the device at each."""
        self.stats.host_syncs += 1
        return np.asarray(x, dtype)

    def _prefill(self, slot: int, req: Request) -> int:
        """Prefill ``req``'s whole prompt on the device, splice its cache
        into ``slot`` and return its first token."""
        with TraceAnnotation("engine.admit", uid=req.uid, slot=slot,
                             prompt_len=len(req.prompt)):
            req.admitted_s = time.perf_counter()
            with TraceAnnotation("engine.prefill", uid=req.uid):
                cache1, logits = self._prefill1(
                    self.params, {"tokens": jnp.asarray(req.prompt[None])})
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
            with TraceAnnotation("engine.first_token", uid=req.uid):
                tok = int(self._fetch(
                    jnp.argmax(logits[0, :self.cfg.vocab_size])))
            req.first_token_s = time.perf_counter()
        self._next_tokens[slot] = tok
        return tok

    def _free(self, slot: int):
        """A retired slot's device cache length back to 0."""
        self.cache["len"] = self.cache["len"].at[slot].set(0)

    def step(self) -> bool:
        """One engine iteration: admit, prefill, one batched decode step.
        False when there was nothing to do.

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
            spans, decoded = self._step(idx)
        return decoded or bool(spans)

    def _step(self, idx: int, chunk: Optional[int] = None) -> tuple:
        """The one step body of every run: the scheduler admits,
        advances each prefill by one span (``chunk`` tokens at most;
        the whole prompt without), and books one decode step over the
        batch; the model, where there is one, runs each of them.
        Returns the token count of each prefill span and whether a
        decode step ran."""
        s = self.sched
        s.admit()
        spans = []
        for slot in sorted(s.prefilling):
            req, done, end = s.next_span(slot, chunk)
            tok = None if self.model is None else self._prefill(slot, req)
            if s.prefilled(slot, end, tok) and self.model is not None:
                self._free(slot)
            spans.append(end - done)
        active = s.batch()
        if not active:
            return spans, False
        nxt = None
        if self.model is not None:
            with TraceAnnotation("engine.decode", step=idx):
                toks = jnp.asarray(self._next_tokens)
                self.cache, logits = self._decode(self.params, self.cache,
                                                  toks)
            with TraceAnnotation("engine.sample", step=idx):
                nxt = self._fetch(jnp.argmax(
                    logits[:, :self.cfg.vocab_size], axis=-1), np.int32)
            self._next_tokens[active] = nxt[active]
        with TraceAnnotation("engine.retire", step=idx) as span:
            retired = s.decoded(active, nxt)
            if self.model is not None:
                for slot in retired:
                    self._free(slot)
            span.set_metadata(retired=len(retired))
        return spans, True

    def run_until_drained(self, max_steps: int = 10_000) -> EngineStats:
        t0 = time.perf_counter()
        steps = 0
        while self.sched.busy and steps < max_steps:
            self.step()
            steps += 1
        self.stats.wall_s = time.perf_counter() - t0
        # hitting max_steps with work left is a TRUNCATED run — flag
        # it so partial stats can't pass for a drained queue
        self.stats.drained = not self.sched.busy
        return self.stats

    # ------------------------------------------ open-loop (plan-only)
    def unfinished_uids(self) -> tuple:
        """Uids the engine has accepted but not retired — running,
        prefilling, or queued.  The censoring set for trace-end
        percentile reports."""
        live = [r.uid for r in self.slot_req if r is not None]
        live += [r.uid for r in self.queue]
        return tuple(live)

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
        TTFT/TPOT always come from the replay itself).  Prompts are
        prefilled in page-aligned spans of ``prefill_chunk_tokens``, so
        a long prompt costs several engine steps, not one stall.

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
        ``run_open_loop`` retains the trace instead."""
        if self.model is not None:
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
        s = self.sched
        s.preempt_policy = preempt
        s.stall_budget_s = float(stall_budget_s)
        if prefill_chunk_tokens % s.table.cfg.page_tokens:
            raise ValueError(
                f"prefill_chunk_tokens={prefill_chunk_tokens} must be "
                f"page-aligned ({s.table.cfg.page_tokens} tokens)")
        if debug_invariants:
            from repro.serving import invariants
        reqs = list(requests)
        arr = np.asarray(arrival_s, float)
        if len(reqs) != arr.size:
            raise ValueError(
                f"{len(reqs)} requests but {arr.size} arrival times")
        buf: list = []
        self._sink = buf
        try:
            if s.prefix_pages is not None and not self._prefix_recorded:
                self._prefix_recorded = True
                self._record(PlanRecord(
                    "prefill", 0, (), (-1,),
                    s.table.shared_prefill_plan(
                        s.prefix_pages, s.prefix_tokens,
                        n_q_heads=self.cfg.n_heads,
                        d_model=self.cfg.d_model, d_ff=self.cfg.d_ff,
                        n_layers=self.cfg.n_layers),
                    n_tokens=s.prefix_tokens))
            i = 0
            steps = 0
            while steps < max_steps:
                busy = s.busy
                if not busy and i >= len(reqs):
                    break
                if not busy and arr[i] > self.sim_t:
                    self.sim_t = float(arr[i])    # idle: jump ahead
                while i < len(reqs) and arr[i] <= self.sim_t:
                    req = reqs[i]
                    self.submit(req)
                    req.submitted_s = float(arr[i])
                    i += 1
                if faults is not None:
                    faults.on_step(self, steps)
                spans, decoded = self._step(self.stats.decode_steps,
                                            prefill_chunk_tokens)
                dt = sum((est_prefill_s_per_token * n for n in spans),
                         0.0) + (est_step_s if decoded else 0.0)
                self.sim_t += dt
                if dt == 0.0 and self.queue and i < len(reqs) and \
                        all(r is None for r in self.slot_req):
                    # fully stalled on admission (nothing running,
                    # head deferred): open-loop time still passes —
                    # jump to the next arrival so the queue keeps
                    # growing instead of the loop spinning in place
                    self.sim_t = max(self.sim_t, float(arr[i]))
                if debug_invariants:
                    invariants.check_step(s)
                steps += 1
                yield from buf
                buf.clear()
            self.stats.drained = i >= len(reqs) and not s.busy
            if debug_invariants and self.stats.drained:
                invariants.check_drained(s)
        finally:
            self._sink = None

    def run_open_loop(self, requests, arrival_s, **kw) -> EngineStats:
        """Open-loop run retaining the full trace (small-n paths and
        tests; the load sweep streams ``open_loop_records`` instead)."""
        for rec in self.open_loop_records(requests, arrival_s, **kw):
            self.trace.append(rec)
        return self.stats
