"""The program's own spans and scopes in a trace (``program_trace.py``),
and the readings built on them (``engine_readings.py``): a hand-built
trace, the harness-only trace recorded on the chip, and a recorded trace
that holds engine spans and scoped ops."""
import json
import time
import types
from pathlib import Path

import numpy as np
import pytest

from chipbench import engine_profile, engine_readings, harness, loop
from chipbench import program_trace, trace

TESTDATA = Path(__file__).resolve().parents[2] / "chipbench" / "testdata"
MS = 1_000_000  # ns


def hand_built():
    """One harness step: an admission (prefill 2-6 ms), a decode (9-19 ms)
    and two length reads in retirement (22 and 25 ms)."""
    host = [["harness.submit", 0, 1 * MS],
            ["harness.step", 1 * MS, 31 * MS],
            ["harness.harvest", 31 * MS, 32 * MS]]
    engine = [["engine.step", 1.1 * MS, 30.9 * MS, {"step": 7, "active": 1}],
              ["engine.admit", 1.2 * MS, 8 * MS, {"uid": 5, "slot": 0}],
              ["engine.prefill", 1.3 * MS, 2 * MS, {"uid": 5}],
              ["engine.splice", 2 * MS, 3 * MS, {"uid": 5, "slot": 0}],
              ["engine.first_token", 3 * MS, 8 * MS, {"uid": 5}],
              ["engine.decode", 8.1 * MS, 9 * MS, {"step": 7}],
              ["engine.sample", 9 * MS, 21 * MS, {"step": 7}],
              ["engine.retire", 21 * MS, 30.8 * MS,
               {"step": 7, "retired": 0}]]
    modules = [[0, "jit__lambda(3)", 2 * MS, 6 * MS],
               [0, "jit_decode_step(7)", 9 * MS, 19 * MS],
               [0, "jit_dynamic_slice(1)", 22 * MS, 22.5 * MS],
               [0, "jit_dynamic_slice(1)", 25 * MS, 25.5 * MS],
               [0, "jit__argmax(2)", 31.2 * MS, 31.4 * MS]]
    scoped = [[0, "%fusion.1", 2 * MS, 4 * MS, "attention"],
              [0, "%fusion.2", 4 * MS, 5 * MS, "mlp"],
              [0, "%copy.3", 5 * MS, 6 * MS, None],
              # a while op holds the layer body's ops
              [0, "%while", 9 * MS, 19 * MS, None],
              [0, "%fusion.4", 10 * MS, 12 * MS, "attention"],
              [0, "%dynamic-update-slice", 12 * MS, 13 * MS, "kv_write"],
              [0, "%fusion.5", 14 * MS, 17 * MS, "mlp"],
              [0, "%dynamic_slice", 22 * MS, 22.5 * MS, None],
              [0, "%dynamic_slice", 25 * MS, 25.5 * MS, None],
              [0, "%argmax", 31.2 * MS, 31.4 * MS, None]]
    return {"ops": [o[:4] for o in scoped], "modules": modules,
            "host": host, "engine": engine, "scoped_ops": scoped}


def test_scope_is_the_innermost_named_one():
    sc = program_trace.scope_of
    assert sc("jit(decode_step)/while/body/closed_call/attention/"
              "kv_write/scatter") == "kv_write"
    assert sc("jit(decode_step)/while/body/attention/dot_general") == \
        "attention"
    assert sc("jit(decode_step)/while/body/mlp/jit(silu)/logistic") == "mlp"
    assert sc("jit(decode_step)/while") is None and sc(None) is None


HLO = """HloModule jit_decode_step, is_scheduled=true
%fused (p: f32[2]) -> f32[2] {
  ROOT %mul.1 = f32[2]{0} multiply(%p, %p), metadata={op_name="jit(decode_step)/mlp/mul"}
}
ENTRY %main (x: f32[2]) -> f32[2] {
  %fusion.4 = f32[2]{0} fusion(%x), kind=kLoop, calls=%fused, metadata={op_name="jit(decode_step)/while/body/attention/dot_general" stack_frame_id=3}
  %copy.78 = f32[2]{0} copy(%fusion.4), metadata={op_name="jit(decode_step)/while/body/dynamic_update_slice"}
  ROOT %fusion.5 = f32[2]{0} fusion(%copy.78), kind=kLoop, calls=%fused, metadata={op_name="jit(decode_step)/mlp/mul"}
}
"""


def test_op_paths_come_from_the_compiled_text():
    other = HLO.replace("while/body/attention/dot_general",
                        "while/body/mlp/dot_general")
    one = program_trace.hlo_paths([HLO])["jit_decode_step"]
    assert one["%fusion.4"] == \
        "jit(decode_step)/while/body/attention/dot_general"
    assert one["%fusion.5"] == one["%mul.1"] == "jit(decode_step)/mlp/mul"
    # two compiles under one name: an op whose path differs is left out
    two = program_trace.hlo_paths([HLO, other])["jit_decode_step"]
    assert two["%fusion.4"] is None and two["%copy.78"] == one["%copy.78"]
    # an op is looked up under the module that ran when it began
    runs = program_trace.modules_by_device(
        [[0, "jit_decode_step(7)", 10, 20], [0, "jit__lambda(3)", 0, 5],
         [1, "jit__lambda(3)", 10, 20]])
    assert program_trace.module_at(runs, 0, 12) == "jit_decode_step"
    assert program_trace.module_at(runs, 0, 3) == "jit__lambda"
    assert program_trace.module_at(runs, 0, 7) is None
    assert program_trace.module_at(runs, 1, 10) == "jit__lambda"
    assert program_trace.module_at(runs, 2, 12) is None


def test_gaps_go_to_the_innermost_span():
    red = program_trace.reduce(hand_built())
    gaps = sorted((n, round(t * 1e3, 6)) for n, t in red["idle_gaps"])
    assert gaps == sorted([("step", 2.0),             # 0-2 ms: mid 1.0
                           ("engine.first_token", 3.0),   # 6-9 ms
                           ("engine.sample", 3.0),        # 19-22 ms
                           ("engine.retire", 2.5),        # 22.5-25 ms
                           ("engine.retire", 5.7),        # 25.5-31.2 ms
                           ("harvest", 0.6)])             # 31.4-32 ms


def test_retire_idle_steps_and_scope_split():
    red = program_trace.reduce(hand_built())
    assert red["engine_steps"] == red["decode_steps"] == 1
    assert red["engine_spans"] == 8
    # retirement 21-30.8 ms, of which the two reads ran 1 ms
    assert red["retire_idle_s"] == pytest.approx(0.0088)
    dec = red["scopes"]["jit_decode_step"]
    assert dec == {"-": pytest.approx(0.004), "attention": pytest.approx(
        0.002), "kv_write": pytest.approx(0.001), "mlp": pytest.approx(0.003)}
    pre = red["scopes"]["jit__lambda"]
    assert pre == {"attention": pytest.approx(0.002),
                   "mlp": pytest.approx(0.001), "-": pytest.approx(0.001)}
    bd = program_trace.breakdown(red)
    rows = dict(bd["device_ops"])
    assert rows["jit_decode_step/-"] == pytest.approx(0.004)
    assert rows["jit__lambda/attention"] == pytest.approx(0.002)
    assert "jit_decode_step" not in rows and "jit_dynamic_slice" in rows
    assert bd["idle_gaps"][0][0] == "engine.retire"

    run = types.SimpleNamespace(trace=red)
    assert engine_readings.sync_idle_ms_per_step(run) == pytest.approx(8.8)
    assert engine_readings.decode_scope_ms(run, "attention") == \
        pytest.approx(2.0)
    assert engine_readings.decode_scope_ms(run, "kv_write") == \
        pytest.approx(1.0)
    assert engine_readings.decode_scope_ms(run, "mlp") == pytest.approx(3.0)


def test_a_program_without_spans_or_scopes_reads_nothing():
    tr = hand_built()
    tr["engine"] = []
    tr["scoped_ops"] = [o[:4] + [None] for o in tr["scoped_ops"]]
    run = types.SimpleNamespace(trace=program_trace.reduce(tr))
    assert engine_readings.sync_idle_ms_per_step(run) is None
    for scope in program_trace.SCOPES:
        assert engine_readings.decode_scope_ms(run, scope) is None
    assert program_trace.breakdown(run.trace)["device_ops"][0] == \
        ["jit_decode_step", pytest.approx(0.010)]
    assert engine_readings.host_syncs_per_step(run) is None
    run.engine_counts = [(10, None), (20, None)]
    assert engine_readings.host_syncs_per_step(run) is None
    run.engine_counts = [(10, 100), (20, 430)]
    assert engine_readings.host_syncs_per_step(run) == 33.0


def _record(reqs, t_open=10.0, t_close=20.0):
    rec = loop.Record(slots=2, t_traffic=0.0, t_open=t_open,
                      t_close=t_close, t_end=t_close, sent=[], steps=[],
                      no_work=[], stats_open=(0, 0, 0),
                      stats_close=(0, 0, 0))
    rec.sent = [loop.Sent(i, r, 8, due=r.submitted_s - 0.01,
                          submit=r.submitted_s)
                for i, r in enumerate(reqs)]
    return rec


def test_queue_wait_censors_requests_not_admitted_by_the_close():
    req = types.SimpleNamespace
    rec = _record([req(submitted_s=5.0, admitted_s=5.5),      # not due
                   req(submitted_s=12.0, admitted_s=12.002),
                   req(submitted_s=13.0, admitted_s=13.1),
                   req(submitted_s=19.0, admitted_s=20.5),
                   req(submitted_s=19.5, admitted_s=None)])
    assert engine_readings.queue_wait_s(rec).tolist() == pytest.approx(
        [0.002, 0.1, 1.0, 0.5])
    run = types.SimpleNamespace(record=rec)
    assert engine_readings.queue_wait_p90_ms(run) == pytest.approx(
        float(np.percentile([2, 100, 1000, 500], 90)))
    # a program that stamps no admission
    old = _record([req(submitted_s=12.0), req(submitted_s=13.0)])
    assert engine_readings.queue_wait_p90_ms(
        types.SimpleNamespace(record=old)) is None


def test_the_harness_only_trace_reduces_as_before():
    tr = json.loads((TESTDATA / "qwen2-0.5b.long-decode.trace.json")
                    .read_text())
    old, new = trace.reduce(tr), program_trace.reduce(tr)
    for key in ("busy_s", "window_s", "work_s", "modules", "steps"):
        assert new[key] == old[key]
    # no span nests in another, so innermost and last are the same owner
    assert new["idle_gaps"] == old["idle_gaps"]
    assert new["engine_steps"] == 0 and new["scopes"] == {}


def test_queue_wait_reported_by_a_traced_run(tiny_bench):
    root, base = tiny_bench
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next(e for e in bench["per_layer"]
                 if e["name"] == "queue_wait_p90_ms")
    entry["workloads"].append("tiny.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result, _ = harness.run_cell(
        root, "tiny.closed", 2**33 + 5, 0.8, True,
        t_proc=time.perf_counter(), require_tpu=False, base=base)
    assert result["correct"] is True
    wait = result["metrics"]["queue_wait_p90_ms"]
    assert wait["unit"] == "ms" and wait["value"] >= 0


def test_profile_tool_on_the_cpu(tiny_bench):
    root, base = tiny_bench
    r = engine_profile.profile(root, "tiny.closed", 3, 0.8,
                               require_tpu=False, base=base)
    rd = r["readings"]
    # 4 slots: the argmax, up to 4 length reads and the admissions
    assert 1 < rd["host_syncs_per_step"] <= 6
    assert rd["queue_wait_p90_ms"] >= 0
    assert rd["sync_idle_ms_per_step"] > 0
    # the CPU trace holds no device ops, so no scope is read
    assert rd["decode_attention_ms"] is None
    assert r["decode_steps"] == r["engine_steps"] > 0
    assert r["engine_spans_per_step"] >= 4
    # warmed up: nothing is traced or compiled while serving
    assert r["programs_compiled_in_window"] == 0
    assert r["programs_traced_while_serving"] == 0


def test_program_texts_name_the_traced_modules(tiny_bench):
    root, base = tiny_bench
    c = harness.load_cell(root, "tiny.closed", False, base)
    engine = harness.set_up(c, 3)
    hlo = program_trace.hlo_paths(engine_profile.program_texts(
        engine, c.mix["prompt"]["buckets"]))
    assert set(hlo) == {"jit_decode_step", "jit__lambda"}
    for mod, scopes in (("jit_decode_step", {"attention", "kv_write", "mlp"}),
                        ("jit__lambda", {"attention", "mlp"})):
        assert {program_trace.scope_of(p) for p in hlo[mod].values()} >= \
            scopes


def test_recorded_chip_trace_with_engine_spans_and_scopes():
    tr = json.loads((TESTDATA / "qwen2-0.5b.long-decode.engine.trace.json")
                    .read_text())
    old, red = trace.reduce(tr), program_trace.reduce(tr)
    for key in ("busy_s", "window_s", "work_s", "modules", "steps"):
        assert red[key] == old[key]
    assert red["engine_steps"] == red["decode_steps"] == 3
    # every device-trace reader gives a value
    run = types.SimpleNamespace(trace=red)
    # 32 per-slot length reads leave the device idle 40-50 ms a step
    assert 40 < engine_readings.sync_idle_ms_per_step(run) < 50
    ms = {s: engine_readings.decode_scope_ms(run, s)
          for s in program_trace.SCOPES}
    assert all(v > 0 for v in ms.values())
    assert ms["attention"] > ms["mlp"] > ms["kv_write"]
    # the scoped split and its remainder add up to the decode module
    n, t = red["modules"]["jit_decode_step"]
    parts = red["scopes"]["jit_decode_step"]
    assert sum(parts.values()) == pytest.approx(t, rel=0.01)
    assert t / n == pytest.approx(0.0517, rel=0.01)
    # the gaps lie in retirement, where the lengths are read one by one
    idle: dict = {}
    for name, s in red["idle_gaps"]:
        idle[name] = idle.get(name, 0.0) + s
    assert max(idle, key=idle.get) == "engine.retire"
    assert {a["step"] for *_, a in tr["engine"]} == {111, 112, 113}


def test_cut_keeps_whole_module_executions():
    tr = hand_built()
    part = engine_profile.cut(tr, 0, 15 * MS, "test")
    assert [m[1] for m in part["modules"]] == ["jit__lambda(3)"]
    # the decode's ops went with it; the prefill's stayed
    assert [o[1] for o in part["scoped_ops"]] == [
        "%fusion.1", "%fusion.2", "%copy.3"]
    assert part["ops"] == [o[:4] for o in part["scoped_ops"]]
    assert [h[0] for h in part["host"]] == ["harness.submit"]
    assert [e[0] for e in part["engine"]] == [
        "engine.admit", "engine.prefill", "engine.splice",
        "engine.first_token", "engine.decode"]
