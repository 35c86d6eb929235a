"""A configuration, a traffic mix, a cell and a per-layer metric added as
new files are found by name and run; no existing file is edited."""
import json
import time

from chipbench import harness, spec

METRIC = '''"""Requests due in the window (a fixture metric)."""
LAYER = "scheduler (serving/engine.py)"
UNIT = "requests"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "tokens_per_s"


def read(run):
    return len(run.record.due_in_window())
'''

SILENT = '''"""A metric that finds nothing to read in this cell."""
LAYER = "device (TPU v5e)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    return None
'''


def test_new_files_are_found_by_name(tiny_bench):
    root, base = tiny_bench
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    (base / "metrics" / "tiny_due.py").write_text(METRIC)
    (base / "metrics" / "tiny_silent.py").write_text(SILENT)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, layer, source in (("tiny_due", "scheduler (serving/engine.py)",
                                 "host_clock"),
                                ("tiny_silent", "device (TPU v5e)",
                                 "device_trace")):
        bench["per_layer"].append({
            "name": name, "unit": "requests" if name == "tiny_due" else "%",
            "better": "higher", "source": source, "layer": layer,
            "moves": "tokens_per_s", "workloads": ["tiny.closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    entries = spec.cell_metrics(bench, "tiny.closed", traced=True)
    assert [e["name"] for e in entries] == ["tiny_due", "tiny_silent"]
    result, lines = harness.run_cell(
        root, "tiny.closed", 17, 0.8, True, t_proc=time.perf_counter(),
        require_tpu=False, base=base)
    assert result["correct"] is True
    # a reader that finds nothing leaves its metric out of the line
    assert set(result["metrics"]) == {"tiny_due"}
    assert result["metrics"]["tiny_due"]["unit"] == "requests"
    assert list(result)[-1] == "checks"
    assert "busy_s" in result["device"] and "breakdown" in result
    assert lines[0].startswith("check logit_gap")
    # the files that were there are as they were
    assert all(p.read_bytes() == b for p, b in before.items())


def test_untraced_run_reports_the_end_to_end_metrics(tiny_bench):
    root, base = tiny_bench
    result, _ = harness.run_cell(
        root, "tiny.open", 2**33 + 1, 1.0, False,
        t_proc=time.perf_counter(), require_tpu=False, base=base)
    # the metrics that list no cells; the others name the cells they bind
    assert set(result["metrics"]) == {"setup_s", "ttft_p90_ms"}
    assert result["correct"] is True and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"


def test_the_harness_refuses_a_cpu(tiny_bench):
    import pytest
    root, base = tiny_bench
    with pytest.raises(harness.NoChip, match="TPU"):
        harness.run_cell(root, "tiny.open", 1, 1.0, False,
                         t_proc=time.perf_counter(), base=base)
