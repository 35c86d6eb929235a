"""Readings of the serving engine's own stamps, counters and spans, and of
the model's named scopes (``program_trace.py``).

Each returns None where the program under test does not write what it
reads (a program without ``Request.admitted_s``, ``EngineStats.host_syncs``
or the ``engine.*`` spans and scopes), so that a metric built on one is
left out of the result line there.

- ``run.record``: the harness's ``loop.Record``;
- ``run.trace``: ``program_trace.reduce`` of the traced stretch;
- ``run.engine_counts``: ``[(decode_steps, host_syncs)]`` of the engine's
  stats where the traced stretch starts and where it ends.
"""
from __future__ import annotations

import numpy as np


def queue_wait_s(rec) -> np.ndarray | None:
    """Wait from ``submit()`` to admission of every request due in the
    window: ``Request.admitted_s - Request.submitted_s``. A request not
    admitted by the close counts with close - submitted."""
    due = rec.due_in_window()
    if not due or not hasattr(due[0].req, "admitted_s"):
        return None
    out = []
    for s in due:
        t = s.req.admitted_s
        if t is None or t > rec.t_close:
            t = rec.t_close
        out.append(t - s.req.submitted_s)
    return np.asarray(out)


def queue_wait_p90_ms(run):
    wait = queue_wait_s(run.record)
    return None if wait is None else float(np.percentile(wait, 90)) * 1e3


def host_syncs_per_step(run):
    """Device-to-host reads of the engine per decode step, over the
    traced stretch: (active slots + 1) per step, and one per admission."""
    counts = getattr(run, "engine_counts", None)
    if not counts or len(counts) < 2 or counts[0][1] is None:
        return None
    (s0, h0), (s1, h1) = counts[0], counts[-1]
    return (h1 - h0) / (s1 - s0) if s1 > s0 else None


def sync_idle_ms_per_step(run):
    """Device-idle time inside the ``engine.retire`` spans (the per-slot
    length reads) per traced engine step that decoded."""
    red = run.trace
    if red is None or not red.get("decode_steps"):
        return None
    return 1e3 * red["retire_idle_s"] / red["decode_steps"]


def decode_scope_ms(run, scope: str):
    """Device time of the decode module's ops under ``scope`` (innermost:
    ``attention`` leaves out ``kv_write``) per decode execution."""
    red = run.trace
    if red is None:
        return None
    parts = red.get("scopes", {}).get("jit_decode_step", {})
    n = red["modules"].get("jit_decode_step", (0, 0.0))[0]
    if scope not in parts or not n:
        return None
    return 1e3 * parts[scope] / n
