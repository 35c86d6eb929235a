"""The harness's own serving loop around ``ServingEngine``.

It hands requests to ``submit()`` when they are due, calls ``step()``,
and stamps every token the engine delivered with the host time at
which that ``step()`` returned (after the step's argmax reached the
host). The window opens once traffic is in flight (open loop: after a
lead time; closed loop: once every client's first request holds a
slot), lasts ``seconds``, and the run stops at its end without
draining.

Host spans (``harness.submit``, ``harness.step``, ``harness.harvest``,
``harness.no-work``) are written with ``jax.profiler.TraceAnnotation``
so that idle gaps in a device trace can be put down to what the host
was doing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np

from jax.profiler import TraceAnnotation

@dataclasses.dataclass
class Sent:
    n: int                     # index in the run's plan
    req: object                # the engine's Request
    prompt_len: int
    due: float
    submit: float = 0.0
    stamps: list = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    client: int = -1


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    prefill_lens: list         # prompt lengths admitted in this step
    decode_ctx: list           # cache length before the decode, per slot


@dataclasses.dataclass
class Record:
    slots: int
    t_traffic: float
    t_open: float
    t_close: float
    t_end: float
    sent: list
    steps: list
    no_work: list              # [(t0, t1)] with nothing queued or running
    stats_open: tuple          # (decode_steps, prefills, tokens_out)
    stats_close: tuple
    compiles_in_window: int = 0
    trace_window: Optional[tuple] = None

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def due_in_window(self):
        return [s for s in self.sent if self.t_open <= s.due < self.t_close]


def _stats(engine) -> tuple:
    st = engine.stats
    return (st.decode_steps, st.prefills, st.tokens_out)


def drive(engine, plan, make_request: Callable[[int], object], *,
          seconds: float, lead_s: float = 0.0,
          clock=time.perf_counter, sleep=time.sleep,
          compile_count: Callable[[], int] = lambda: 0,
          tracer=None, trace_at: float = 0.0,
          trace_len: float = 0.0) -> Record:
    """Drive ``engine`` with ``plan`` for a window of ``seconds``.

    ``make_request(n)`` builds request ``n`` of the plan. In an open
    loop request ``n`` is due at ``offsets_s[n]`` after traffic starts
    and the window opens ``lead_s`` after that. ``tracer`` (with
    ``start()`` and ``stop()``) is started ``trace_at`` seconds into
    the window and stopped ``trace_len`` seconds later, or at its end.
    """
    n_plan = len(plan.prompt_lens)
    sent: list[Sent] = []
    live: list[Sent] = []
    steps: list[Step] = []
    no_work: list = []
    due_q: list = []            # [(due, n, client)], ascending for open
    next_n = 0
    t_traffic = clock()
    if plan.loop == "open":
        due_q = [(t_traffic + float(o), i, -1)
                 for i, o in enumerate(plan.offsets_s)]
        next_n = n_plan
        t_open = t_traffic + lead_s
        t_close = t_open + seconds
    else:
        for c in range(plan.clients):
            due_q.append((t_traffic, next_n, c))
            next_n += 1
        t_open = t_close = None
    qi = 0
    stats_open, compiles_open = None, 0
    first_wave = plan.clients
    trace_window = None
    tracing = False
    t_trace_stop = None

    def has_work():
        return bool(engine.queue) or any(
            r is not None for r in engine.slot_req)

    while True:
        now = clock()
        if t_close is not None and now >= t_close:
            break
        if stats_open is None and t_open is not None and now >= t_open:
            stats_open = _stats(engine)
            compiles_open = compile_count()
        if tracer is not None and t_open is not None:
            if trace_window is None and not tracing and \
                    now >= t_open + trace_at:
                tracer.start()
                tracing = True
                trace_window = (clock(), None)
                t_trace_stop = trace_window[0] + trace_len
            elif tracing and now >= t_trace_stop:
                trace_window = (trace_window[0], clock())
                tracer.stop()
                tracing = False
        with TraceAnnotation("harness.submit"):
            while qi < len(due_q) and due_q[qi][0] <= now:
                due, n, client = due_q[qi]
                qi += 1
                req = make_request(n)
                s = Sent(n, req, int(plan.prompt_lens[n % n_plan]), due,
                         client=client)
                s.submit = clock()
                engine.submit(req)
                sent.append(s)
                live.append(s)
        if has_work():
            t0 = clock()
            with TraceAnnotation("harness.step"):
                engine.step()
            t1 = clock()
            with TraceAnnotation("harness.harvest"):
                prefill_lens, decode_ctx, still = [], [], []
                for s in live:
                    k = len(s.req.output) - len(s.stamps)
                    if k:
                        new = not s.stamps
                        s.stamps.extend([t1] * k)
                        if new:
                            prefill_lens.append(s.prompt_len)
                        if k - new > 0:
                            decode_ctx.append(
                                s.prompt_len + len(s.req.output) - 2)
                    if s.req.done_s is not None:
                        s.done = t1
                        if plan.loop == "closed":
                            due_q.append((t1, next_n, s.client))
                            next_n += 1
                    else:
                        still.append(s)
                live = still
                steps.append(Step(t0, t1, prefill_lens, decode_ctx))
            if t_open is None and all(
                    s.stamps for s in sent[:first_wave]):
                t_open = t1
                t_close = t_open + seconds
        else:
            wake = due_q[qi][0] if qi < len(due_q) else now + 0.05
            if t_close is not None:
                wake = min(wake, t_close)
            t0 = clock()
            with TraceAnnotation("harness.no-work"):
                if wake > t0:
                    sleep(wake - t0)
            no_work.append((t0, clock()))
    t_end = clock()
    if stats_open is None:
        stats_open, compiles_open = _stats(engine), compile_count()
    if tracing:
        trace_window = (trace_window[0], clock())
        tracer.stop()
    return Record(
        slots=engine.slots, t_traffic=t_traffic, t_open=t_open,
        t_close=t_close, t_end=t_end, sent=sent, steps=steps,
        no_work=no_work, stats_open=stats_open,
        stats_close=_stats(engine),
        compiles_in_window=compile_count() - compiles_open,
        trace_window=trace_window)


# ------------------------------------------------------------ readings
def ttft_s(rec: Record) -> np.ndarray:
    """Time to first token of every request due in the window, from
    its due time. A request without its first token by the close
    counts with close - due."""
    out = []
    for s in rec.due_in_window():
        t = s.stamps[0] if s.stamps and s.stamps[0] <= rec.t_close \
            else rec.t_close
        out.append(t - s.due)
    return np.asarray(out)


def itl_s(rec: Record) -> np.ndarray:
    """Every gap between consecutive tokens of a request, where both
    tokens came inside the window."""
    out = []
    for s in rec.sent:
        st = [t for t in s.stamps if rec.t_open <= t <= rec.t_close]
        out.extend(np.diff(st).tolist())
    return np.asarray(out)


def tokens_in_window(rec: Record) -> int:
    return sum(1 for s in rec.sent for t in s.stamps
               if rec.t_open < t <= rec.t_close)


def submit_lag_s(rec: Record) -> np.ndarray:
    return np.asarray([s.submit - s.due for s in rec.due_in_window()])
