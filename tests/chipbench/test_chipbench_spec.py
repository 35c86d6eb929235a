"""BENCHMARK.json against the rules the harness relies on: every name a
file, every metric a reader that agrees with its entry, every
configuration's model group true to its published keys."""
import json
import re
from pathlib import Path

import pytest

from chipbench import program, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def check_names(bench):
    """The top-level keys, and every name, of a BENCHMARK.json."""
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    assert len({m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
               ) == len(bench["end_to_end"]) + len(bench["per_layer"])
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert 1 <= bench["run_seconds"] <= 51


def test_top_level_keys_and_names():
    check_names(BENCH)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files(w):
    cfg = spec.config(w["config"])
    mix = spec.traffic(w["traffic"])
    cell = spec.cell(w["name"])
    assert spec.reference(cfg["reference"]).gaps
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert (ROOT / entry["file"]).is_file()
    assert cfg["name"] == w["config"]
    assert (mix["loop"] == "open") == ("rate_rps" in cell)
    assert set(cell["check"]) == {"requests", "min_tokens", "logit_gap"}
    reported = spec.cell_metrics(BENCH, w["name"], False)
    assert {"setup_s"} < {e["name"] for e in reported}
    assert spec.cell_metrics(BENCH, w["name"], True)


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader_that_agrees(m):
    mod = spec.metric(m)
    assert mod.UNIT == m["unit"] and mod.SOURCE == m["source"]
    if "layer" in m:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    else:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def check_model_group(c, root=ROOT, base=spec.HERE):
    """Configuration entry ``c`` of the BENCHMARK.json at ``root``: its
    file agrees with the entry, its model group with each published key
    that the file's ``published_keys`` maps and with what its family
    reads from the published file, and the program takes the group."""
    f = json.loads((root / c["file"]).read_text())
    assert spec.config(c["name"], base) == f
    m = f["model"]
    assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
    assert set(c["reduced"]) <= set(f["changed"])
    for ours, theirs in f["published_keys"].items():
        assert m[ours] == f[theirs], ours
    spec.family(m["family"], base).check_published(f)
    assert m["dtype"] == f["torch_dtype"]
    # the program takes the group as it is
    cfg = program.model_config(f["name"], m)
    assert cfg.padded_vocab == m["vocab_padded"]


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_model_group_follows_the_published_keys(c):
    check_model_group(c)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_each_listed_cell_reports_what_the_metric_moves(m):
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m["workloads"]) <= cells
    for cell in m["workloads"]:
        e2e = {e["name"] for e in spec.cell_metrics(BENCH, cell, False)}
        assert m["moves"] in e2e, (m["name"], cell)
    if "roofline" in m["name"]:
        # a roofline share has a whole-step share of the peak beside it,
        # moving the same metric in the same cells
        assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                   and o["workloads"] == m["workloads"]
                   for o in BENCH["per_layer"])
