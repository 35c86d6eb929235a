"""The comparison that decides ``correct``: the float8 control fails it
at a size a test run can hold, and a run whose timed path is broken
underneath comes out not correct."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.compile_meter import CompileMeter

from chipbench_fixtures import CHECK


@pytest.mark.parametrize("seed", [1, 2, 2**33])
def test_control_fails_where_the_program_passes(tiny_bench, seed):
    root, base = tiny_bench
    c = harness.load_cell(root, "tiny.closed", False, base)
    eng = harness.set_up(c, seed)
    rec = harness.serve(c, eng, seed, 0.8, CompileMeter())
    chosen = harness.finished_sample(c, rec, seed)
    prompts = [np.asarray(s.req.prompt) for s in chosen]
    outputs = [np.asarray(s.req.output, np.int32) for s in chosen]
    served, ctl = c.ref.gaps(c.m, seed, prompts, outputs, control=True)
    assert sum(len(o) for o in outputs) >= CHECK["min_tokens"]
    assert max(g.max() for g in served) <= CHECK["logit_gap"]
    assert max(g.max() for g in ctl) > CHECK["logit_gap"]


def _altered_token(eng):
    """Every decoded token replaced where it is produced."""
    decode = eng._decode

    def bad(p, cache, toks):
        cache, logits = decode(p, cache, toks)
        return cache, logits.at[:, 5].add(1e3)
    eng._decode = bad


def _state_unchanged(eng):
    """The decode step hands back the cache as it was before the step: a
    copy taken before the call, which holds whether or not the step
    donates (and so deletes) the cache it is given."""
    decode = eng._decode

    def bad(p, cache, toks):
        before = jax.tree.map(jnp.copy, cache)
        _, logits = decode(p, cache, toks)
        return before, logits
    eng._decode = bad


def _state_unchanged_donated(eng):
    """As ``_state_unchanged``, on a decode step that donates its cache."""
    eng._decode = jax.jit(eng.model.decode_step, donate_argnums=(1,))
    _state_unchanged(eng)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _state_unchanged_donated])
def test_a_broken_timed_path_is_not_correct(tiny_bench, monkeypatch,
                                            fault):
    root, base = tiny_bench
    make = harness.program.make_engine

    def broken(*a, **k):
        eng = make(*a, **k)
        fault(eng)
        return eng
    monkeypatch.setattr(harness.program, "make_engine", broken)
    result, lines = harness.run_cell(
        root, "tiny.closed", 5, 0.8, False, t_proc=time.perf_counter(),
        require_tpu=False, base=base)
    assert result["correct"] is False
    assert result["failed"] >= 1
    assert result["checks"]["logit_gap"]["value"] > CHECK["logit_gap"]
