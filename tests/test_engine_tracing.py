"""The serving engine's own instrumentation, on the CPU at reduced size
with 4 slots: its host spans in a profiler trace (nesting and arguments),
the host-sync counter, the admission stamp, the model's named scopes in
the compiled programs, and served tokens that do not change with the
profiler on."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_reduced
from repro.models.model import Model
from repro.serving.engine import Request, ServingEngine

SLOTS = 4


@pytest.fixture(scope="module")
def qwen():
    cfg = get_reduced("qwen2_0_5b")
    return cfg, Model(cfg, remat="none").init(jax.random.PRNGKey(0))


def _requests(n, new=6, seed=0, uid0=100):
    rng = np.random.default_rng(seed)
    return [Request(uid=uid0 + i,
                    prompt=rng.integers(1, 250, size=8).astype(np.int32),
                    max_new_tokens=new + i % 3) for i in range(n)]


def _engine(qwen, **kw):
    cfg, params = qwen
    return ServingEngine(cfg, params, slots=SLOTS, max_seq=64, **kw)


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return [list(r.output) for r in reqs]


def _engine_spans(trace_dir):
    pb = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(pb[0]).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events if e.name.startswith("engine.")]
    return sorted(out, key=lambda x: (x[1], -x[2]))


def test_spans_nest_and_carry_their_arguments(qwen, tmp_path):
    eng = _engine(qwen)
    _drain(eng, _requests(SLOTS, seed=1, uid0=0))      # compile outside
    reqs = _requests(6)
    first = eng.stats.decode_steps
    with jax.profiler.trace(str(tmp_path)):
        _drain(eng, reqs)
    spans = _engine_spans(tmp_path)
    steps = [x for x in spans if x[0] == "engine.step"]
    assert [x[3]["step"] for x in steps] == list(
        range(first, eng.stats.decode_steps))
    assert steps[0][3]["active"] == 0
    for a, b in zip(steps, steps[1:]):
        assert a[2] <= b[1]
    admits = [x for x in spans if x[0] == "engine.admit"]
    by_uid = {r.uid: r for r in reqs}
    assert sorted(x[3]["uid"] for x in admits) == sorted(by_uid)
    assert {x[3]["slot"] for x in admits} <= set(range(SLOTS))
    for adm in admits:
        uid = adm[3]["uid"]
        assert adm[3]["prompt_len"] == len(by_uid[uid].prompt)
        assert sum(st[1] <= adm[1] and adm[2] <= st[2]
                   for st in steps) == 1
        kids = [x for x in spans if adm[1] <= x[1] and x[2] <= adm[2]
                and x is not adm]
        assert [x[0] for x in kids] == ["engine.prefill", "engine.splice",
                                        "engine.first_token"]
        assert all(x[3]["uid"] == uid for x in kids)
        assert kids[1][3]["slot"] == adm[3]["slot"]
    retired = 0
    for st in steps:
        n = st[3]["step"]
        parts = [x for x in spans if x[0] in (
            "engine.decode", "engine.sample", "engine.retire")
            and st[1] <= x[1] and x[2] <= st[2]]
        assert [x[0] for x in parts] == ["engine.decode", "engine.sample",
                                         "engine.retire"]
        assert all(x[3]["step"] == n for x in parts)
        retired += parts[2][3]["retired"]
    assert retired == len(reqs)


@pytest.mark.parametrize("record_plans", [False, True])
def test_host_syncs_count_every_read(qwen, record_plans):
    eng = _engine(qwen, record_plans=record_plans)
    for r in _requests(SLOTS, new=20):
        eng.submit(r)
    counts = [eng.stats.host_syncs]
    for _ in range(5):
        eng.step()
        counts.append(eng.stats.host_syncs)
    steps = np.diff(counts).tolist()
    # one read per admission (its first token) and one per decode step
    # (the argmax), however many slots are active: the slots' cache
    # lengths are counted on the host, plan table or not
    assert steps[0] == SLOTS + 1
    assert steps[1:] == [1] * 4


def test_a_slot_retiring_by_count_skips_its_length_read(qwen):
    eng = _engine(qwen)
    eng.submit(Request(uid=1, prompt=np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=2))
    eng.step()
    # the first token's argmax, then the step's argmax: the engine
    # reads no length from the device, so retiring costs no read
    assert eng.stats.host_syncs == 2
    assert eng.slot_req[0] is None


def test_admission_stamp_lies_between_submit_and_first_token(qwen):
    eng = _engine(qwen)
    reqs = _requests(2 * SLOTS + 1)
    _drain(eng, reqs)
    for r in reqs:
        assert r.submitted_s <= r.admitted_s <= r.first_token_s <= r.done_s
    # requests beyond the slots waited in the queue for a retirement
    first_out = min(r.done_s for r in reqs[:SLOTS])
    assert all(r.admitted_s >= first_out for r in reqs[SLOTS:])


@pytest.mark.parametrize("arch", ["qwen2_0_5b", "deepseek_v3_671b"])
def test_compiled_programs_carry_the_named_scopes(arch):
    cfg = get_reduced(arch)
    model = Model(cfg, remat="none")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(SLOTS, 64))
    toks = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)

    def found(text):
        paths = re.findall(r'op_name="([^"]*)"', text)
        return {p for path in paths for p in path.split("/")} & \
            {"attention", "kv_write", "mlp"}

    decode = jax.jit(model.decode_step).lower(
        params, cache, toks).compile().as_text()
    assert found(decode) == {"attention", "kv_write", "mlp"}
    # kv_write nests inside attention
    assert "/attention/kv_write/" in decode
    prefill = jax.jit(lambda p, b: model.prefill(p, b, 64)).lower(
        params, {"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32)})
    assert found(prefill.compile().as_text()) == {"attention", "mlp"}


def test_served_tokens_do_not_change_with_the_profiler_on(qwen, tmp_path):
    plain = _drain(_engine(qwen), _requests(SLOTS + 2, seed=3))
    with jax.profiler.trace(str(tmp_path)):
        traced = _drain(_engine(qwen), _requests(SLOTS + 2, seed=3))
    assert traced == plain
