"""The program's own spans and named scopes in a profiler trace.

``trace.py`` reduces the harness's spans and the device's ops and
modules. This module adds what the serving engine and the model write
into the same trace, and leaves every number of ``trace.py`` as it is:

- ``engine``: the engine's host spans (``engine.step``, ``engine.admit``,
  ``engine.prefill``, ``engine.splice``, ``engine.first_token``,
  ``engine.decode``, ``engine.sample``, ``engine.retire``), each with the
  plain integers it was written with (``step``, ``uid``, ...);
- ``scoped_ops``: every device op with the innermost of the model's
  named scopes (``attention``, ``kv_write``, ``mlp``) in its op-name
  path, or None.

``reduce`` gives ``trace.reduce``'s numbers, except that each idle gap
is put down to the innermost span (harness or engine) that holds its
midpoint, and adds the device-idle time inside ``engine.retire``, the
steps that decoded, and each decode and prefill module's device time
split by scope.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from chipbench import trace

SCOPES = ("attention", "kv_write", "mlp")
ENGINE = "engine."
HLO_MODULE = re.compile(r"HloModule (\S+?),")
HLO_OP = re.compile(r'^\s*(?:ROOT )?(%\S+) = .*?metadata=\{op_name="([^"]*)"',
                    re.M)
NO_SCOPE = "-"


def scope_of(path) -> str | None:
    """The innermost of ``SCOPES`` in an op-name path such as
    ``jit(decode_step)/while/body/attention/kv_write/scatter``."""
    for part in reversed((path or "").split("/")):
        if part in SCOPES:
            return part
    return None


def hlo_paths(texts) -> dict:
    """{module: {op: op-name path}} from the text of compiled modules
    (``jax.jit(f).lower(...).compile().as_text()``). A module compiled
    for several input shapes gives several texts under one name; an op
    whose path differs between them is left out."""
    out: dict = {}
    for text in texts:
        paths = out.setdefault(HLO_MODULE.match(text).group(1), {})
        for op, path in HLO_OP.findall(text):
            paths[op] = path if paths.get(op, path) == path else None
    return out


def _newest(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def modules_by_device(modules) -> dict:
    """{device: [(start, end, module)]} of the module executions, by
    start."""
    runs: dict = {}
    for d, name, s, e in sorted(modules, key=lambda m: m[2]):
        runs.setdefault(d, []).append((s, e, trace.module_name(name)))
    return runs


def module_at(runs: dict, dev: int, at: float) -> str | None:
    """The module that ran on ``dev`` at time ``at``, or None."""
    mods = runs.get(dev, [])
    i = bisect.bisect_right(mods, (at, float("inf"))) - 1
    return mods[i][2] if i >= 0 and at < mods[i][1] else None


def extract(trace_dir: str, hlo: dict) -> dict:
    """``trace.extract``'s lists, and the ``engine`` spans and
    ``scoped_ops`` of the same ``.xplane.pb``. The TPU's op events carry
    the HLO line of the op without its metadata, so each op's path is
    looked up by name in ``hlo`` (``hlo_paths``), under the module that
    ran when it began."""
    from jax.profiler import ProfileData
    tr = trace.extract(trace_dir)
    runs = modules_by_device(tr["modules"])
    engine, scoped = [], []
    for plane in ProfileData.from_file(_newest(trace_dir)).planes:
        dm = trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dm and line.name == trace.OPS_LINE:
                dev = int(dm.group(1))
                for e in line.events:
                    op = e.name.split(" = ")[0]
                    paths = hlo.get(module_at(runs, dev, e.start_ns), {})
                    scoped.append([dev, op, e.start_ns, e.end_ns,
                                   scope_of(paths.get(op))])
            elif not dm:
                for e in line.events:
                    if e.name.startswith(ENGINE):
                        engine.append([e.name, e.start_ns, e.end_ns,
                                       {k: v for k, v in e.stats}])
    tr["engine"] = engine
    tr["scoped_ops"] = scoped
    return tr


def _busy(tr: dict, devices: list, lo: float, hi: float) -> list:
    """Disjoint intervals of [lo, hi) in which an op ran (a module, where
    the trace has no ops) on the first device with any, the device whose
    gaps ``trace.reduce`` names."""
    for dev in devices:
        ev = [[s, e] for d, _, s, e in tr["ops"] if d == dev] or \
             [[s, e] for d, _, s, e in tr["modules"] if d == dev]
        busy = trace.union([[max(s, lo), min(e, hi)] for s, e in ev
                            if e > lo and s < hi])
        if busy:
            return busy
    return []


def _overlap(busy: list, starts: list, lo: float, hi: float) -> float:
    """Length of the part of [lo, hi) that ``busy`` covers."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    t = 0.0
    while i < len(busy) and busy[i][0] < hi:
        t += max(0.0, min(busy[i][1], hi) - max(busy[i][0], lo))
        i += 1
    return t


def _innermost(spans: list, t: float) -> str:
    """The shortest span that holds time ``t``; ``loop`` where none."""
    best, owner = None, "loop"
    for n, s, e in spans:
        if s <= t < e and (best is None or e - s < best):
            best, owner = e - s, n.removeprefix("harness.")
    return owner


def split_by_scope(tr: dict, dev: int, lo: float, hi: float) -> dict:
    """{module: {scope or "-": device seconds}} over the executions of the
    decode and prefill modules that began in [lo, hi). Each instant of an
    execution goes to the innermost op running then (the op that began
    last: a while op holds its body's ops); instants in no op, or in an
    op of no scope, go to "-". So a module's parts add up to its time."""
    ops = sorted((s, e, scope) for d, _, s, e, scope in tr["scoped_ops"]
                 if d == dev)
    starts = [s for s, _, _ in ops]
    out: dict = {}
    for d, name, ms, me in tr["modules"]:
        mod = trace.module_name(name)
        if d != dev or not lo <= ms < hi or not (
                trace.is_decode(mod) or trace.is_prefill(mod)):
            continue
        parts = out.setdefault(mod, {})
        i = bisect.bisect_left(starts, ms)
        edges = []
        while i < len(ops) and ops[i][0] < me:
            edges += [(ops[i][0], 1, i), (min(ops[i][1], me), 0, i)]
            i += 1
        edges.sort()
        stack: list = []
        t = ms
        for at, kind, k in edges + [(me, 0, None)]:
            if at > t:
                key = (ops[stack[-1]][2] if stack else None) or NO_SCOPE
                parts[key] = parts.get(key, 0.0) + (at - t) * 1e-9
                t = at
            if k is None:
                continue
            if kind:
                stack.append(k)
            elif k in stack:
                stack.remove(k)
    return out


def reduce(tr: dict) -> dict:
    """``trace.reduce``, with idle gaps named by the innermost span, and
    the engine's numbers (none where the trace holds no engine spans)."""
    red = trace.reduce(tr)
    host = tr["host"]
    lo = min(s for _, s, _ in host)
    hi = max(e for _, _, e in host)
    devices = sorted({d for d, *_ in tr["ops"]} |
                     {d for d, *_ in tr["modules"]})
    dev = devices[0] if devices else 0
    busy = _busy(tr, devices, lo, hi)
    engine = [[n, s, e] for n, s, e, _ in tr.get("engine", [])]
    spans = [[n, s, e] for n, s, e in host] + engine
    named = []
    prev = lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            named.append((_innermost(spans, (prev + s) / 2),
                          (s - prev) * 1e-9))
        prev = max(prev, e)
    named.sort(key=lambda x: -x[1])
    red["idle_gaps"] = named

    starts = [s for s, _ in busy]
    steps = [(s, e) for n, s, e in engine if n == "engine.step"]
    decodes = sorted(s for n, s, _ in engine if n == "engine.decode")
    red["engine_spans"] = len(engine)
    red["engine_steps"] = len(steps)
    red["decode_steps"] = sum(
        bisect.bisect_left(decodes, e) > bisect.bisect_left(decodes, s)
        for s, e in steps)
    red["retire_idle_s"] = 1e-9 * sum(
        (e - s) - _overlap(busy, starts, s, e)
        for n, s, e in engine if n == "engine.retire")
    red["scopes"] = split_by_scope(tr, dev, lo, hi) \
        if "scoped_ops" in tr else {}
    return red


def breakdown(red: dict, top: int = 16) -> dict:
    """``trace.breakdown`` with each decode and prefill module split by
    scope (``jit_decode_step/attention``, ``.../kv_write``, ``.../mlp``,
    ``.../-``), and the idle gaps by innermost span."""
    rows = []
    for name, (_, t) in red["modules"].items():
        parts = red["scopes"].get(name)
        if parts and any(k != NO_SCOPE for k in parts):
            rows += [[f"{name}/{k}", v] for k, v in parts.items()]
        else:
            rows.append([name, t])
    rows.sort(key=lambda r: -r[1])
    return {"device_ops": rows[:top],
            "idle_gaps": [[n, t] for n, t in red["idle_gaps"][:top]]}
