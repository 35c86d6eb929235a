"""The serving engine's scheduler: admission, prefill progress,
retirement, preemption and KV-page bookkeeping, on the host alone (no
JAX).  ``ServingEngine`` drives one per run and hands each decision to
the jitted model (which gives back the sampled tokens), to the plan
recorder (a ``PlanRecord`` priced over a ``PageTable``), or to both.

A request retires by one rule, after its last prefill span and after
each decode: its token count, then EOS where the device gave a token,
then the ``max_seq - 1`` length.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional

import numpy as np


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


class Scheduler:
    """The queue, the slots and what each slot holds: ``lens`` (each
    slot's cache length, counted on the host), ``remaining`` (tokens
    still to produce), ``prefilling`` (slot -> ``[req, done, total]``
    prompt tokens) and ``swapped`` (uid -> ``(n_pages, tokens,
    remaining, prefill_total | None)`` of a preempted request).

    With a ``table``, admission is CONSERVATIVE: a request is admitted
    only if the free list can hold its maximum length (prompt +
    max_new_tokens, capped at ``max_seq``) on top of the worst-case
    remaining growth of every admitted request, so decode-time page
    growth cannot fail; otherwise the head waits and admission stops
    (FIFO order kept).  A request that cannot fit even an empty pool
    raises ``ValueError``.  ``preempt_policy`` (``"lifo"`` or
    ``"longest"``) lets a head that waited ``stall_budget_s`` on
    ``clock`` evict running requests to host until it fits.  Without a
    table a request is admitted whenever a slot is free."""

    def __init__(self, cfg, stats, record: Callable, *, slots: int,
                 max_seq: int, eos: Optional[int] = None, table=None,
                 prefix_tokens: int = 0, prefix_caching: bool = False,
                 clock: Callable[[], float] = time.perf_counter):
        """``record`` takes each ``PlanRecord`` (with a ``table``);
        ``clock`` stamps ``done_s`` and times an admission stall."""
        self.cfg, self.stats, self.table = cfg, stats, table
        self.slots, self.max_seq, self.eos = slots, max_seq, eos
        self.record, self.clock = record, clock
        self.queue: deque = deque()
        self.slot_req: list = [None] * slots
        self.lens = np.zeros((slots,), np.int32)
        self.remaining = np.zeros((slots,), np.int32)
        self.prefilling: dict = {}
        self.swapped: dict = {}
        self.admit_seq = [0] * slots    # order in which slots were bound
        self.n_bound = self.n_finished = self.deferred_admissions = 0
        self.preempt_policy, self.stall_budget_s = "none", 0.0
        self._defer_since: Optional[float] = None   # head's wait start
        self.prefix_tokens = int(prefix_tokens)
        self.prefix_pages: Optional[np.ndarray] = None
        if self.prefix_tokens:
            pt = table.cfg.page_tokens
            if self.prefix_tokens % pt:
                raise ValueError(
                    f"prefix_tokens={prefix_tokens} must be a multiple "
                    f"of kv_page_tokens={pt} (chunked prefill spans are "
                    "page-aligned)")
            if prefix_caching:
                self.prefix_pages = table.reserve_prefix(
                    self.prefix_tokens // pt)

    @property
    def busy(self) -> bool:       # a request is queued or holds a slot
        return bool(self.queue) or self.slot_req.count(None) < self.slots

    # ------------------------------------------------------ admission
    def _max_pages(self, req) -> int:
        """Worst-case pages ``req`` can ever hold, shared prefix pages
        included (its length capped at ``max_seq``)."""
        max_len = min(self.prefix_tokens + len(req.prompt)
                      + req.max_new_tokens, self.max_seq)
        return -(-max_len // self.table.cfg.page_tokens)

    def _can_admit(self, req) -> bool:
        t = self.table
        need = self._max_pages(req)
        if need > min(t.cfg.n_pages, t.cfg.max_pages_per_seq):
            raise ValueError(
                f"request uid={req.uid} needs {need} KV pages at its "
                f"max length but the pool can never hold that "
                f"(n_pages={t.cfg.n_pages}, "
                f"max_pages_per_seq={t.cfg.max_pages_per_seq})")
        if self.prefix_pages is not None:
            need -= len(self.prefix_pages)   # shared pages are mapped
        # pages admitted slots may still claim while decoding
        growth = sum(self._max_pages(r) - int(t.held[s])
                     for s, r in enumerate(self.slot_req)
                     if r is not None)
        return len(t._free) >= need + growth

    def _head_fits(self, fresh: int) -> bool:
        """Whether the queue head may be admitted now, after evicting
        victims if it waited out the stall budget."""
        head = self.queue[0]
        if not self._can_admit(head):
            if self._defer_since is None:
                self._defer_since = self.clock()
            if self.preempt_policy == "none" or self.clock() - \
                    self._defer_since < self.stall_budget_s:
                return False
            while not self._can_admit(head):
                victim = self._pick_victim(fresh)
                if victim is None:
                    return False
                self._preempt(victim)
        self._defer_since = None
        return True

    def admit(self):
        """Bind queued requests to free slots, in FIFO order."""
        fresh = self.n_bound        # slots bound from here on have not run
        for slot in range(self.slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            if self.table is not None and not self._head_fits(fresh):
                self.deferred_admissions += 1
                return
            self._bind(slot, self.queue.popleft())

    def _bind(self, slot: int, req):
        """Put ``req`` in ``slot``: allocate its pages, then queue its
        prefill (a fresh request, or one preempted mid-prefill, which
        resumes at the span it stopped on) or rejoin the decode batch
        (preempted while decoding), recording the swap-in first."""
        swap = self.swapped.pop(req.uid, None)
        full = self.prefix_tokens + len(req.prompt)
        if self.table is not None and not self.table.alloc_seq(
                slot, full if swap is None else max(full, swap[1]),
                prefix=self.prefix_pages):
            raise RuntimeError(       # _can_admit guarantees it
                "shadow KV table out of pages at admission")
        self.slot_req[slot] = req
        self.admit_seq[slot] = self.n_bound
        self.n_bound += 1
        if swap is None:
            done = self.prefix_tokens \
                if self.prefix_pages is not None else 0
            self.prefilling[slot] = [req, done, full]
            return
        n_swap, tokens, remaining, total = swap
        if n_swap:
            self._record("swap_in", slot, req, self.table.swap_in_plan(
                n_swap, req.uid, n_layers=self.cfg.n_layers), tokens)
        if total is not None:            # was mid-prefill: continue it
            self.prefilling[slot] = [req, tokens, total]
        else:                            # was decoding: rejoin batch
            self.lens[slot] = tokens
            if not self.table.note_tokens(slot, tokens):
                raise RuntimeError(   # alloc_seq covered these pages
                    "swap-in lost pages the allocation reserved")
            self.remaining[slot] = remaining

    # ----------------------------------------------------- preemption
    def _pick_victim(self, fresh: int) -> Optional[int]:
        """A running slot to preempt, or None.  Only slots bound before
        this admission pass (``admit_seq < fresh``) are eligible: each
        has made progress since, so two large requests cannot evict each
        other forever.  ``lifo``: most recently admitted (vLLM's
        default: least sunk cost); ``longest``: most own pages held."""
        cands = [s for s, r in enumerate(self.slot_req)
                 if r is not None and self.admit_seq[s] < fresh]
        if not cands:
            return None
        if self.preempt_policy == "lifo":
            return max(cands, key=lambda s: self.admit_seq[s])
        t = self.table
        return max(cands, key=lambda s: (int(t.held[s])
                                         - int(t.shared[s]),
                                         self.admit_seq[s]))

    def _preempt(self, slot: int):
        """Evict ``slot``: record the swap-out of its written KV pages
        (freeing them), keep its exact progress, and re-queue it right
        BEHIND the queue head, whose admission the eviction is for."""
        req = self.slot_req[slot]
        pf = self.prefilling.pop(slot, None)
        if pf is not None:
            tokens, remaining, total = pf[1], -1, pf[2]
        else:
            tokens = int(self.lens[slot])
            remaining, total = int(self.remaining[slot]), None
        plan, n_swap = self.table.swap_out(
            slot, tokens, req.uid, n_layers=self.cfg.n_layers)
        if plan is not None:
            self._record("swap_out", slot, req, plan, tokens)
        self.swapped[req.uid] = (n_swap, tokens, remaining, total)
        self.slot_req[slot] = None
        self.lens[slot] = 0
        self.stats.preemptions += 1
        self.stats.swapped_pages += n_swap
        head = [self.queue.popleft()] if self.queue else []
        self.queue.extendleft([req] + head)

    # -------------------------------------------------- prefill, decode
    def _record(self, kind: str, slot: int, req, plan, n_tokens: int):
        self.record(PlanRecord(
            kind, self.stats.decode_steps, (slot,), (req.uid,), plan,
            arrival_event=req.arrival_event, n_tokens=n_tokens))

    def next_span(self, slot: int, chunk: Optional[int] = None) -> tuple:
        """``(req, done, end)``: the next prefill span of ``slot`` — the
        rest of its prompt without a ``chunk`` size, else at most
        ``chunk`` tokens — recorded and counted."""
        req, done, total = self.prefilling[slot]
        end = total if chunk is None or total - done <= chunk \
            else done + chunk
        if self.table is not None:
            c = self.cfg
            self._record("prefill", slot, req, self.table.prefill_plan(
                slot, total, span=None if chunk is None else (done, end),
                n_q_heads=c.n_heads, d_model=c.d_model, d_ff=c.d_ff,
                n_layers=c.n_layers), end - done)
        self.stats.prefills += 1
        return req, done, end

    def prefilled(self, slot: int, end: int, tok: Optional[int] = None
                  ) -> bool:
        """Book the span of ``slot`` that ended at ``end``.  The last
        span joins the decode batch with the prompt's first token
        (``tok`` when the device sampled it).  True if the request
        retired there."""
        pf = self.prefilling[slot]
        if end < pf[2]:
            pf[1] = end
            return False
        req, _, total = self.prefilling.pop(slot)
        self.lens[slot] = total
        if self.table is not None and \
                not self.table.note_tokens(slot, total):
            raise RuntimeError("shadow KV table out of pages")
        self.remaining[slot] = req.max_new_tokens - 1
        self.stats.tokens_out += 1
        if tok is not None:
            req.output.append(tok)
        return self._retire_if_done(slot, tok)

    def batch(self) -> list:
        """This step's decode batch: every occupied slot whose prefill
        is done, recorded as one decode plan over the KV pages each
        holds now (the new token's KV lands before the next step)."""
        active = [s for s, r in enumerate(self.slot_req) if r is not None]
        if self.prefilling:
            active = [s for s in active if s not in self.prefilling]
        if active and self.table is not None:
            self.record(PlanRecord(
                "decode", self.stats.decode_steps, tuple(active),
                tuple(self.slot_req[s].uid for s in active),
                self.table.decode_step_plan(
                    active, n_q_heads=self.cfg.n_heads,
                    n_layers=self.cfg.n_layers)))
        return active

    def decoded(self, active: list, toks=None) -> list:
        """Book the decode step over ``active``; return the slots that
        retired.  ``toks`` are a device step's sampled tokens per slot:
        that step wrote the next K/V of every slot, free ones too, so
        every length moves and the table grows for the whole batch
        before anything retires.  A plan step (no tokens) moves its
        batch slot by slot; a slot whose page growth fails (a fault
        seized the pool) is swapped out under a preemption policy."""
        self.stats.decode_steps += 1
        self.stats.tokens_out += len(active)
        t = self.table
        if toks is None:
            self.lens[active] += 1
        else:
            self.lens += 1
            if t is not None:
                for slot in active:
                    if not t.note_tokens(slot, int(self.lens[slot])):
                        raise RuntimeError(
                            "shadow KV table out of pages")
        retired = []
        tok = None
        for slot in active:
            if toks is not None:
                tok = int(toks[slot])
                self.slot_req[slot].output.append(tok)
            self.remaining[slot] -= 1
            grew = toks is not None or \
                t.note_tokens(slot, int(self.lens[slot]))
            if self._retire_if_done(slot, tok):
                retired.append(slot)
            elif not grew:
                if self.preempt_policy == "none":
                    raise RuntimeError("shadow KV table out of pages")
                self._preempt(slot)
        return retired

    # ----------------------------------------------------- retirement
    def _retire_if_done(self, slot: int, tok: Optional[int]) -> bool:
        """The one retirement rule: count, then EOS, then length."""
        if self.remaining[slot] <= 0 or \
                (self.eos is not None and tok == self.eos) or \
                self.lens[slot] >= self.max_seq - 1:
            req = self.slot_req[slot]
            req.done_s = self.clock()
            self.slot_req[slot] = None
            self.n_finished += 1
            self.lens[slot] = 0
            if self.table is not None:
                self.table.free_seq(slot)
            return True
        return False
