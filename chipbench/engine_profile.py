"""Look inside one cell's serving window: the program's own spans,
counters and named scopes.

    python3 chipbench/engine_profile.py --workload <name> --seed <n> \
        --seconds <s> [--save-trace PATH]

Sets the cell up as ``run.py`` does, serves ``--seconds`` and profiles
the last ``harness.TRACE_S`` of them, with Python's garbage collections
written as ``harness.gc`` spans. The last line of stdout is one JSON
object: the engine's readings (``engine_readings.py``), the breakdown
with the decode and prefill modules split by scope and the idle gaps by
innermost span (``program_trace.py``), the engine spans per step, and
the programs traced or compiled while serving. It runs no reference and
decides no ``correct``: it is not a run of the benchmark.
``--save-trace`` writes three consecutive engine steps that decoded and
admitted nothing, for the tests. Needs a TPU.
"""
import argparse
import gc
import json
import shutil
import sys
import tempfile
import types
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"     # as run.py keeps it
SAVE_STEPS = 3
JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"


class Tracer:
    """The profiler, with the engine's counters where it starts and
    stops."""

    def __init__(self, path, engine):
        self.path, self.engine, self.counts = path, engine, []

    def _count(self):
        st = self.engine.stats
        self.counts.append((st.decode_steps,
                            getattr(st, "host_syncs", None)))

    def start(self):
        jax.profiler.start_trace(self.path)
        self._count()

    def stop(self):
        self._count()
        jax.profiler.stop_trace()


def crop(tr: dict, n: int = SAVE_STEPS, source: str = "") -> dict:
    """``n`` consecutive harness steps that each decoded and admitted
    nothing, with the spans just before and after them (``cut``)."""
    host = sorted((h for h in tr["host"] if h[0] != "harness.gc"),
                  key=lambda h: h[1])
    engine = tr["engine"]

    def plain(h):
        s, e = h[1], h[2]
        inside = {x[0] for x in engine if s <= x[1] < e}
        return "engine.decode" in inside and "engine.admit" not in inside

    idx = [i for i, h in enumerate(host) if h[0] == "harness.step"]
    for j in range(len(idx) - n + 1):
        run = idx[j:j + n]
        if all(plain(host[i]) for i in run):
            break
    else:
        raise ValueError(f"no {n} consecutive plain steps in the trace")
    return cut(dict(tr, host=host), host[max(run[0] - 1, 0)][1],
               host[min(run[-1] + 1, len(host) - 1)][2], source)


def cut(tr: dict, lo: float, hi: float, source: str) -> dict:
    """The spans and module executions that lie wholly in [lo, hi], and
    the ops that began in a kept execution. (The device's clock can run a
    fraction of a millisecond ahead of the host's, so the next step's
    module may begin before ``hi``: it is left out whole.)"""
    from chipbench import program_trace

    def inside(evs, s, e):
        return [x for x in evs if lo <= x[s] and x[e] <= hi]

    modules = inside(tr["modules"], 2, 3)
    runs = program_trace.modules_by_device(modules)

    def ran(ops):
        return [o for o in ops
                if program_trace.module_at(runs, o[0], o[2]) is not None]

    return {"source": source, "ops": ran(tr["ops"]), "modules": modules,
            "host": inside(tr["host"], 1, 2),
            "engine": inside(tr["engine"], 1, 2),
            "scoped_ops": ran(tr["scoped_ops"])}


def program_texts(engine, buckets) -> list:
    """The compiled text of the engine's decode step and of its prefill
    at each prompt bucket: the same programs as the engine's jitted
    ``decode_step`` and prefill lambda, whose op names the trace holds."""
    model = engine.model
    toks = jax.ShapeDtypeStruct((engine.slots,), jnp.int32)
    texts = [jax.jit(model.decode_step).lower(
        engine.params, engine.cache, toks).compile().as_text()]
    prefill = jax.jit(lambda p, b: model.prefill(p, b, engine.max_seq))
    for n in buckets:
        batch = {"tokens": jax.ShapeDtypeStruct((1, n), jnp.int32)}
        texts.append(prefill.lower(engine.params, batch).compile().as_text())
    return texts


class GcSpans:
    """Python's garbage collections as ``harness.gc`` host spans, so that
    an idle gap one causes is put down to it."""

    def __init__(self):
        self.span = None

    def __call__(self, phase, info):
        if phase == "start":
            self.span = TraceAnnotation("harness.gc",
                                        generation=info["generation"])
            self.span.__enter__()
        elif self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None


def profile(root: Path, workload: str, seed: int, seconds: float, *,
            require_tpu: bool = True, base=None, save_trace=None) -> dict:
    from chipbench import engine_readings as er
    from chipbench import harness, loop, program_trace
    from chipbench.compile_meter import CompileMeter

    c = harness.load_cell(root, workload, False, base)
    dev = harness.require_chips(c.wl["chips"]) if require_tpu \
        else jax.devices()[0]
    meter = CompileMeter()
    engine = harness.set_up(c, seed)
    trace_dir = tempfile.mkdtemp(prefix="engine-profile-")
    tracer = Tracer(trace_dir, engine)
    gc_spans = GcSpans()
    traced = []

    def on_duration(event, duration, **_):
        if event == JAXPR_TRACE:
            traced.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    gc.callbacks.append(gc_spans)
    try:
        rec = harness.serve(c, engine, seed, seconds, meter, tracer=tracer)
    finally:
        gc.callbacks.remove(gc_spans)
        jax.monitoring.unregister_event_duration_listener(on_duration)
    try:
        hlo = program_trace.hlo_paths(program_texts(
            engine, c.mix["prompt"]["buckets"]))
        tr = program_trace.extract(trace_dir, hlo)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if save_trace:
        Path(save_trace).write_text(json.dumps(crop(
            tr, source=f"recorded on one {dev.device_kind} chip: "
                       f"{workload}, seed {seed}, {SAVE_STEPS} engine steps "
                       f"of chipbench/engine_profile.py")))
    red = program_trace.reduce(tr)
    run = types.SimpleNamespace(record=rec, trace=red,
                                engine_counts=tracer.counts)
    readings = {
        "queue_wait_p90_ms": er.queue_wait_p90_ms(run),
        "host_syncs_per_step": er.host_syncs_per_step(run),
        "sync_idle_ms_per_step": er.sync_idle_ms_per_step(run),
        **{f"decode_{s}_ms": er.decode_scope_ms(run, s)
           for s in program_trace.SCOPES}}
    return {"workload": workload, "seed": seed,
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "tokens_in_window": loop.tokens_in_window(rec),
            "programs_traced_while_serving": len(traced),
            "programs_compiled_in_window": rec.compiles_in_window,
            "readings": readings,
            "engine_spans_per_step":
                red["engine_spans"] / max(red["engine_steps"], 1),
            "engine_steps": red["engine_steps"],
            "decode_steps": red["decode_steps"],
            "busy_s": red["busy_s"], "window_s": red["window_s"],
            "work_s": red["work_s"], "modules": red["modules"],
            "scopes": red["scopes"],
            "breakdown": program_trace.breakdown(red)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save-trace", default=None)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT))
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import harness
    try:
        result = profile(ROOT, args.workload, args.seed, args.seconds,
                         save_trace=args.save_trace)
    except harness.NoChip as e:
        print(f"[engine_profile] {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
