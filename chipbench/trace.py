"""Reduction of a profiler trace to the numbers the metrics read.

``extract`` turns the profiler's ``.xplane.pb`` into plain lists on the
trace's own clock: device ops and module executions of every TPU, and
the harness's host spans (``harness.*``, written by ``loop.py``). The reduction works on those
lists alone, so it is tested on a hand-built trace and on one recorded
on the chip (``testdata/``).

- busy: the union of the intervals in which an op ran, per device,
  averaged over the devices;
- window: from the first harness span to the end of the last one;
- work: the window less the ``harness.no-work`` spans, in which nothing
  was queued or running;
- module time: executions that began in the window, and their device
  seconds, per XLA module;
- idle gaps: the stretches of the window in which no op ran, each put
  down to the harness span in which its midpoint fell.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_WORK = "harness.no-work"
STEP_SPAN = "harness.step"


def module_name(name: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def extract(trace_dir: str, span_prefix: str = "harness.") -> dict:
    """Plain event lists from the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, modules, host = [], [], []
    for plane in pd.planes:
        dm = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dm and line.name in (OPS_LINE, MODULES_LINE):
                out = ops if line.name == OPS_LINE else modules
                dev = int(dm.group(1))
                for e in line.events:
                    # an op's name is its whole HLO line; keep "%name"
                    name = e.name.split(" = ")[0]
                    out.append([dev, name, e.start_ns, e.end_ns])
            elif not dm:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        host.append([e.name, e.start_ns, e.end_ns])
    return {"ops": ops, "modules": modules, "host": host}


def union(intervals) -> list:
    """Merge [start, end] intervals into disjoint ones, sorted."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def reduce(tr: dict) -> dict:
    """Busy, window, work and per-module seconds, and the idle gaps."""
    host = tr["host"]
    if not host:
        raise ValueError("the trace holds no harness spans")
    lo = min(s for _, s, _ in host)
    hi = max(e for _, _, e in host)
    devices = sorted({d for d, *_ in tr["ops"]} |
                     {d for d, *_ in tr["modules"]})
    busy_per_dev = []
    merged0 = []
    for dev in devices:
        ev = [[s, e] for d, _, s, e in tr["ops"] if d == dev] or \
             [[s, e] for d, _, s, e in tr["modules"] if d == dev]
        merged = union(_clip(ev, lo, hi))
        busy_per_dev.append(length(merged))
        if not merged0:
            merged0 = merged
    no_work = union(_clip([[s, e] for n, s, e in host if n == NO_WORK],
                          lo, hi))
    modules: dict = {}
    for d, name, s, e in tr["modules"]:
        if devices and d != devices[0] or not lo <= s < hi:
            continue
        c, t = modules.get(module_name(name), (0, 0.0))
        modules[module_name(name)] = (c + 1, t + (e - s) * 1e-9)
    gaps = []
    prev = lo
    for s, e in merged0 + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        owner = "loop"
        for n, hs, he in host:
            if hs <= mid < he:
                owner = n.removeprefix("harness.")
        named.append((owner, (e - s) * 1e-9))
    named.sort(key=lambda x: -x[1])
    busy = sum(busy_per_dev) / max(len(busy_per_dev), 1)
    return {"busy_s": busy * 1e-9, "window_s": (hi - lo) * 1e-9,
            "work_s": (hi - lo - length(no_work)) * 1e-9,
            "modules": modules, "idle_gaps": named,
            "steps": per_step(tr, devices[0] if devices else 0)}


def per_step(tr: dict, dev: int = 0) -> list:
    """For each ``harness.step`` span, in order, the device seconds of
    each module that ran in it: {module: seconds}. A module belongs to
    the span that holds most of it, since the host's and the device's
    clocks in one trace can stand a fraction of a millisecond apart."""
    spans = sorted([hs, he] for n, hs, he in tr["host"]
                   if n == STEP_SPAN)
    out = [{} for _ in spans]
    starts = [hs for hs, _ in spans]
    for d, name, s, e in tr["modules"]:
        if d != dev or e <= s:
            continue
        i = bisect.bisect_right(starts, (s + e) / 2) - 1
        for j in (i, i + 1):
            if 0 <= j < len(spans):
                hs, he = spans[j]
                if min(e, he) - max(s, hs) >= 0.5 * (e - s):
                    mod = module_name(name)
                    out[j][mod] = out[j].get(mod, 0.0) + (e - s) * 1e-9
                    break
    return out


def is_decode(module: str) -> bool:
    return module.startswith("jit_decode_step")


def is_prefill(module: str) -> bool:
    """The engine's prefill is a jitted lambda."""
    return module.startswith("jit__lambda")


def breakdown(red: dict, top: int = 10) -> dict:
    mods = sorted(red["modules"].items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": [[n, t] for n, (_, t) in mods],
            "idle_gaps": [[n, t] for n, t in red["idle_gaps"][:top]]}


def module_totals(red: dict, pred) -> tuple[int, float]:
    """(executions, device seconds) of the modules ``pred`` accepts."""
    n = t = 0
    for name, (c, s) in red["modules"].items():
        if pred(name):
            n, t = n + c, t + s
    return n, t


def traced_steps(record) -> list:
    """The harness's steps that began and ended inside the trace."""
    if record.trace_window is None:
        return []
    lo, hi = record.trace_window
    return [s for s in record.steps if s.t0 >= lo and s.t1 <= hi]


def matched_steps(run, pred) -> list:
    """[(harness step, device seconds of the modules ``pred`` accepts)]
    for the steps of the traced stretch, the trace's step spans and the
    harness's steps paired in order; [] where their counts differ."""
    steps = traced_steps(run.record)
    dev = run.trace["steps"]
    if len(steps) != len(dev):
        return []
    return [(s, sum(t for m, t in d.items() if pred(m)))
            for s, d in zip(steps, dev)]
