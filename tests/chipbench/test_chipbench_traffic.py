"""Traffic: the same pool of work for every seed, in the seed's order,
and every prompt length in its cell's buckets."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic

BASE = Path(__file__).resolve().parents[2] / "chipbench"
CELLS = [("long-decode", "qwen2-0.5b.long-decode"),
         ("chat", "chatglm3-6b.chat")]


def load(kind, name):
    return json.loads((BASE / kind / f"{name}.json").read_text())


@pytest.mark.parametrize("mix_name,cell_name", CELLS)
def test_plan_is_deterministic_per_seed(mix_name, cell_name):
    mix, cell = load("traffic", mix_name), load("cells", cell_name)
    a = traffic.plan(mix, cell, 2**33 + 7, 45)
    b = traffic.plan(mix, cell, 2**33 + 7, 45)
    c = traffic.plan(mix, cell, 12, 45)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.output_lens, b.output_lens)
    assert not np.array_equal(a.prompt_lens, c.prompt_lens)
    # every seed offers the same sizes, in another order
    assert sorted(a.prompt_lens) == sorted(c.prompt_lens)
    assert sorted(a.output_lens) == sorted(c.output_lens)
    if mix["loop"] == "open":
        assert np.array_equal(a.offsets_s, b.offsets_s)
        ga, gc = np.diff(a.offsets_s, prepend=0), np.diff(c.offsets_s,
                                                          prepend=0)
        assert sorted(ga) == pytest.approx(sorted(gc))
        # each gap stays with its request: a rotation of one sequence
        r = int(np.argmax(np.isclose(gc, ga[0]) &
                          (c.prompt_lens == a.prompt_lens[0]) &
                          (c.output_lens == a.output_lens[0])))
        assert np.allclose(np.roll(gc, -r), ga)
        assert np.array_equal(np.roll(c.prompt_lens, -r), a.prompt_lens)
        # the last arrival lands before the window's close, at one time
        close = cell["lead_s"] + 45
        assert a.offsets_s[-1] == pytest.approx(c.offsets_s[-1])
        assert a.offsets_s[-1] <= close
        rate = len(a.offsets_s) / close
        assert rate == pytest.approx(cell["rate_rps"], rel=0.2)
    else:
        assert a.clients == cell["clients"]


@pytest.mark.parametrize("mix_name,cell_name", CELLS)
def test_lengths_land_in_buckets_and_fit_the_cache(mix_name, cell_name):
    mix, cell = load("traffic", mix_name), load("cells", cell_name)
    p, o, _ = traffic.pool(mix)
    assert set(p.tolist()) <= set(mix["prompt"]["buckets"])
    assert o.min() >= mix["output"]["min"] and \
        o.max() <= mix["output"]["max"]
    assert (p + o).max() < cell["max_seq"]
    # a heavy tail: the pool reaches its largest bucket
    assert p.max() == max(mix["prompt"]["buckets"])


def test_prompt_tokens_are_seeded():
    a = traffic.prompt_tokens(2**33, 5, 64, 1000)
    assert np.array_equal(a, traffic.prompt_tokens(2**33, 5, 64, 1000))
    assert not np.array_equal(a, traffic.prompt_tokens(2**33, 6, 64, 1000))
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 1000


@pytest.mark.parametrize("kind", ["poisson", "bursty", "diurnal"])
def test_arrival_times_copy_matches_the_program(kind):
    from repro.serving.engine import arrival_times
    assert np.array_equal(traffic.arrival_times(kind, 200, 3.0, seed=4),
                          arrival_times(kind, 200, 3.0, seed=4))
