"""One run of one cell: set-up, the measured window, the readings and
the comparison with the plain reference that decides ``correct``.

``run_cell`` is what ``run.py`` calls; the tests call it too, on the
CPU at a reduced size, with ``require_tpu=False``.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

import jax
import numpy as np

from chipbench import loop, program, spec, traffic
from chipbench import trace as trace_mod
from chipbench.compile_meter import CompileMeter

TRACE_S = 10.0       # the traced stretch: the last seconds of the window


class NoChip(RuntimeError):
    pass


def require_chips(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"this benchmark runs on TPU chips only; JAX's first "
                     f"device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[0]


def warm_up(engine, buckets, vocab):
    """Every prefill bucket, every slot's splice, reads and retirement,
    and the decode step, on this engine instance. Three tokens each, so
    that a step reads every slot's cache length before it retires."""
    lens = list(buckets) * (-(-engine.slots // len(buckets)))
    for i, n in enumerate(lens):
        engine.submit(program.Request(
            uid=-1 - i, prompt=np.zeros(n, np.int32) + (i % vocab),
            max_new_tokens=3))
    while engine.queue or any(r is not None for r in engine.slot_req):
        engine.step()


def sample(finished: list, k: int, seed: int) -> list:
    """The longest finished request and k - 1 others drawn from the
    seed."""
    if not finished:
        return []
    size = [s.prompt_len + len(s.req.output) for s in finished]
    first = int(np.argmax(size))
    rest = [i for i in range(len(finished)) if i != first]
    rng = np.random.default_rng([int(seed), 3])
    pick = rng.choice(rest, min(k - 1, len(rest)), replace=False) \
        if rest and k > 1 else []
    return [finished[first]] + [finished[int(i)] for i in pick]


def check(ref, m: dict, seed: int, chosen: list, limits: dict) -> dict:
    """Compare the served tokens of ``chosen`` with the reference."""
    prompts = [np.asarray(s.req.prompt) for s in chosen]
    outputs = [np.asarray(s.req.output, np.int32) for s in chosen]
    gaps = ref.gaps(m, seed, prompts, outputs) if chosen else []
    widest = [float(np.max(g)) for g in gaps]
    n_tok = int(sum(len(o) for o in outputs))
    failed = sum(w > limits["logit_gap"] for w in widest)
    checks = {
        "logit_gap": {"value": max(widest, default=float("nan")),
                      "limit": limits["logit_gap"], "pass": "<="},
        "tokens_checked": {"value": n_tok, "limit": limits["min_tokens"],
                           "pass": ">="},
    }
    ok = bool(chosen) and failed == 0 and n_tok >= limits["min_tokens"]
    return {"correct": ok, "failed": int(failed), "checks": checks}


class _Tracer:
    def __init__(self, path):
        self.path = path

    def start(self):
        jax.profiler.start_trace(self.path)

    def stop(self):
        jax.profiler.stop_trace()


def load_cell(root: Path, workload_name: str, traced: bool,
              base: Path | None = None):
    """Everything a run of the cell reads, found by name."""
    bench = spec.load_benchmark(root)
    base = Path(base) if base else spec.HERE
    wl = spec.workload(bench, workload_name)
    cfg_file = spec.config(wl["config"], base)
    return types.SimpleNamespace(
        wl=wl, cfg_file=cfg_file, m=cfg_file["model"],
        mix=spec.traffic(wl["traffic"], base),
        cell=spec.cell(wl["name"], base),
        readers={e["name"]: spec.metric(e, base)
                 for e in spec.cell_metrics(bench, wl["name"], traced)},
        ref=spec.reference(cfg_file["reference"], base))


def set_up(c, seed: int):
    """The program with the seed's weights, its engine warmed up."""
    cfg = program.model_config(c.cfg_file["name"], c.m)
    params = program.make_params(cfg, c.m, seed)
    engine = program.make_engine(cfg, params, slots=c.cell["slots"],
                                 max_seq=c.cell["max_seq"])
    warm_up(engine, c.mix["prompt"]["buckets"], c.m["vocab"])
    return engine


def serve(c, engine, seed: int, seconds: float, meter, *, tracer=None,
          rate: float | None = None):
    """The measured window; ``rate`` overrides the cell's (knee sweep)."""
    cell = dict(c.cell, rate_rps=rate) if rate else c.cell
    plan = traffic.plan(c.mix, cell, seed, seconds)
    n_plan = len(plan.prompt_lens)

    def make_request(n):
        i = n % n_plan
        return program.Request(
            uid=n, prompt=traffic.prompt_tokens(
                seed, n, int(plan.prompt_lens[i]), c.m["vocab"]),
            max_new_tokens=int(plan.output_lens[i]))

    return loop.drive(
        engine, plan, make_request, seconds=seconds,
        lead_s=cell.get("lead_s", 0.0),
        compile_count=meter.programs_fetched, tracer=tracer,
        trace_at=max(0.0, seconds - TRACE_S), trace_len=TRACE_S)


def finished_sample(c, rec, seed: int) -> list:
    return sample([s for s in rec.sent if s.done is not None],
                  c.cell["check"]["requests"], seed)


def run_cell(root: Path, workload_name: str, seed: int, seconds: float,
             traced: bool, *, t_proc: float, require_tpu: bool = True,
             base: Path | None = None, save_trace: str | None = None,
             log=lambda msg: print(msg, file=sys.stderr, flush=True)):
    """Run one cell once. Returns (result dict, check lines)."""
    c = load_cell(root, workload_name, traced, base)
    dev = require_chips(c.wl["chips"]) if require_tpu else jax.devices()[0]
    meter = CompileMeter()
    engine = set_up(c, seed)
    setup_compile_s = meter.seconds
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if traced else None
    t_setup = time.perf_counter()
    rec = serve(c, engine, seed, seconds, meter,
                tracer=_Tracer(trace_dir) if traced else None)
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"[chipbench] {c.wl['name']} seed {seed}: "
        f"{len(rec.due_in_window())} requests due in the window, "
        f"{loop.tokens_in_window(rec)} tokens, {len(rec.steps)} steps")
    log(f"[chipbench] programs compiled or loaded inside the window: "
        f"{rec.compiles_in_window}")

    red = None
    if traced:
        tr = trace_mod.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if save_trace:
            Path(save_trace).write_text(json.dumps(tr))
        red = trace_mod.reduce(tr)

    run = types.SimpleNamespace(
        record=rec, trace=red, model=c.m, chips=c.wl["chips"],
        setup_s=t_setup - t_proc, setup_compile_s=setup_compile_s,
        memory_peak_bytes=mem_peak, device_kind=dev.device_kind)
    metrics = {}
    for name, rd in c.readers.items():
        v = rd.read(run)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": rd.UNIT}

    # free the program's state before the reference runs
    chosen = finished_sample(c, rec, seed)
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    verdict = check(c.ref, c.m, seed, chosen, c.cell["check"])
    log(f"[chipbench] reference over {len(chosen)} requests: "
        f"{time.perf_counter() - t_ref:.1f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem_peak}
    result = {"correct": verdict["correct"],
              "attempted": len(rec.due_in_window()),
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = trace_mod.breakdown(red)
    result["checks"] = verdict["checks"]
    lines = [f"check {k}: {c['value']} (must be {c['pass']} {c['limit']})"
             for k, c in verdict["checks"].items()]
    return result, lines
