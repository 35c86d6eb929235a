"""Trace reduction: a hand-built trace with known overlaps, and three
engine steps of qwen2-0.5b recorded on a TPU v5e chip."""
import json
from pathlib import Path

import pytest

from chipbench import trace

TESTDATA = Path(__file__).resolve().parents[2] / "chipbench" / "testdata"
MS = 1_000_000  # ns


def hand_built():
    host = [["harness.submit", 0, 1 * MS],
            ["harness.step", 1 * MS, 11 * MS],
            ["harness.harvest", 11 * MS, 12 * MS],
            ["harness.no-work", 12 * MS, 20 * MS],
            ["harness.step", 20 * MS, 30 * MS]]
    modules = [[0, "jit_decode_step(7)", 1 * MS, 5 * MS],
               [0, "jit__lambda(9)", 5 * MS, 8 * MS],
               [0, "jit_decode_step(7)", 21 * MS, 25 * MS]]
    # ops overlap each other (a while op holds its body's ops)
    ops = [[0, "%while", 1 * MS, 5 * MS], [0, "%fusion", 2 * MS, 3 * MS],
           [0, "%dot", 5 * MS, 8 * MS], [0, "%while", 21 * MS, 25 * MS],
           [0, "%early", -5 * MS, 0.5 * MS]]
    return {"ops": ops, "modules": modules, "host": host}


def test_union_merges_overlaps():
    assert trace.union([[5, 8], [1, 3], [2, 4], [8, 9]]) == [[1, 4], [5, 9]]
    assert trace.length(trace.union([[0, 2], [1, 3], [10, 11]])) == 4


def test_hand_built_trace():
    red = trace.reduce(hand_built())
    assert red["window_s"] == pytest.approx(0.030)
    # 0.5 ms of %early inside the window, 1-8 ms, 21-25 ms
    assert red["busy_s"] == pytest.approx(0.0115)
    assert red["work_s"] == pytest.approx(0.022)
    assert red["modules"]["jit_decode_step"] == (2, pytest.approx(0.008))
    assert red["modules"]["jit__lambda"] == (1, pytest.approx(0.003))
    gaps = dict((round(t, 6), n) for n, t in red["idle_gaps"])
    assert gaps[0.013] == "no-work"       # 8 ms to 21 ms, midpoint 14.5
    assert gaps[0.005] == "step"          # 25 ms to 30 ms
    assert red["steps"] == [{"jit_decode_step": pytest.approx(0.004),
                             "jit__lambda": pytest.approx(0.003)},
                            {"jit_decode_step": pytest.approx(0.004)}]
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "jit_decode_step"
    assert len(bd["idle_gaps"]) <= 10


def test_module_belongs_to_the_span_that_holds_most_of_it():
    tr = hand_built()
    # the device clock a little ahead: the second decode starts 0.3 ms
    # before its step span
    tr["modules"][2] = [0, "jit_decode_step(7)", 19.7 * MS, 23.7 * MS]
    red = trace.reduce(tr)
    assert red["steps"][1] == {"jit_decode_step": pytest.approx(0.004)}


def test_recorded_chip_trace():
    tr = json.loads((TESTDATA / "qwen2-0.5b.long-decode.trace.json")
                    .read_text())
    red = trace.reduce(tr)
    assert 0 < red["busy_s"] < red["window_s"] == pytest.approx(
        red["work_s"])
    assert len(red["steps"]) == 3
    for step in red["steps"]:
        # one decode step of about 51.7 ms on the chip, and 32 per-slot
        # length reads (jit_dynamic_slice), one per active slot
        assert step["jit_decode_step"] == pytest.approx(0.0517, rel=0.01)
        assert trace.is_decode("jit_decode_step")
    assert red["modules"]["jit_dynamic_slice"][0] >= 96
    # the host's per-slot reads leave the device idle in small gaps
    assert red["idle_gaps"][0][0] == "step"
    assert trace.module_name("jit_decode_step(1109)") == "jit_decode_step"
