import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models.model import Model
from repro.serving import invariants
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import PagedCacheConfig, PagedKVCache


def _requests(n, rng):
    return [Request(uid=i,
                    prompt=rng.integers(1, 250, size=int(rng.integers(4, 12))
                                        ).astype(np.int32),
                    max_new_tokens=5) for i in range(n)]


def test_continuous_batching_matches_sequential():
    """Greedy outputs must be independent of slot count / batching."""
    cfg = get_reduced("qwen2_0_5b")
    params = Model(cfg, remat="none").init(jax.random.PRNGKey(0))
    outs = {}
    for slots in (1, 3):
        rng = np.random.default_rng(7)
        eng = ServingEngine(cfg, params, slots=slots, max_seq=64)
        reqs = _requests(5, rng)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        outs[slots] = [tuple(r.output) for r in reqs]
    assert outs[1] == outs[3]


def test_engine_throughput_and_latency_fields():
    cfg = get_reduced("qwen1_5_32b")
    params = Model(cfg, remat="none").init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=2, max_seq=48)
    rng = np.random.default_rng(0)
    reqs = _requests(3, rng)
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained()
    assert stats.tokens_out == sum(r.max_new_tokens for r in reqs)
    assert all(r.first_token_s is not None and r.done_s is not None
               for r in reqs)


def test_paged_cache_alloc_free_invariants():
    cfg = PagedCacheConfig(n_pages=16, page_tokens=8, n_kv_heads=2,
                           head_dim=16, max_pages_per_seq=4)
    cache = PagedKVCache(cfg, max_seqs=3)
    assert cache.alloc_seq(0, prompt_len=20)     # 3 pages
    k = jnp.ones((20, 2, 16))
    cache.write_prompt(0, k, k)
    assert cache.pages_in_use == 3
    cache.append_token(np.array([0]), jnp.ones((1, 2, 16)),
                       jnp.ones((1, 2, 16)))
    assert int(cache.lens[0]) == 21
    cache.free_seq(0)
    assert cache.pages_in_use == 0
    # exhaustion: can't allocate more pages than the pool holds
    assert cache.alloc_seq(1, prompt_len=32)
    assert not cache.alloc_seq(2, prompt_len=32 * 8)


# ------------------------------------------- host mirror of cache lengths
MIRROR_SLOTS, MIRROR_SEQ = 2, 32
# (prompt length, max_new_tokens): request 0 retires by its count,
# request 1 at max_seq - 1 (4 steps after a 27-token prompt), request 2
# by EOS (chosen from its own tokens below); more requests than slots,
# so slots are reused, and the last runs on alone beside a free slot
MIRROR_REQS = ((6, 3), (27, 20), (10, 12), (6, 3), (10, 9))


def _mirror_requests():
    rng = np.random.default_rng(11)
    return [Request(uid=i, prompt=rng.integers(1, 250, n).astype(np.int32),
                    max_new_tokens=new)
            for i, (n, new) in enumerate(MIRROR_REQS)]


def _serve_reading_device_lengths(model, params, decode, prefill, eos):
    """The engine's schedule written out plainly, reading each slot's
    cache length from the device: FIFO admission into free slots, one
    batched decode step while any slot is held, and per slot, after its
    first token and after each decode, the tests count, EOS, length.
    Returns {uid: (tokens, step count when it retired, reason)}."""
    cache = model.init_cache(MIRROR_SLOTS, MIRROR_SEQ)
    queue, held = _mirror_requests(), [None] * MIRROR_SLOTS
    nxt = np.zeros(MIRROR_SLOTS, np.int32)
    out, left, step = {}, {}, 0

    def retire_if_done(s, tok):
        why = ("count" if left[s] <= 0 else
               "eos" if tok == eos else
               "length" if int(cache["len"][s]) >= MIRROR_SEQ - 1
               else None)
        if why:
            done.append((held[s].uid, why))
            held[s] = None
            cache["len"] = cache["len"].at[s].set(0)

    while queue or any(r is not None for r in held):
        done = []
        for s in range(MIRROR_SLOTS):
            if held[s] is None and queue:
                r = held[s] = queue.pop(0)
                one, logits = prefill(
                    params, {"tokens": jnp.asarray(r.prompt[None])})
                cache = jax.tree.map(
                    lambda full, o: full.at[:, s].set(o[:, 0])
                    if full.ndim >= 2 and full.shape[1] == MIRROR_SLOTS
                    else full, cache, one)
                cache["len"] = cache["len"].at[s].set(one["len"][0])
                nxt[s] = int(jnp.argmax(logits[0, :model.cfg.vocab_size]))
                out[r.uid], left[s] = [int(nxt[s])], r.max_new_tokens - 1
                retire_if_done(s, nxt[s])
        if any(r is not None for r in held):
            cache, logits = decode(params, cache, jnp.asarray(nxt))
            step += 1
            toks = np.asarray(
                jnp.argmax(logits[:, :model.cfg.vocab_size], -1))
            for s, r in enumerate(held):
                if r is None:
                    continue
                nxt[s] = toks[s]
                out[r.uid].append(int(toks[s]))
                left[s] -= 1
                retire_if_done(s, toks[s])
        for uid, why in done:
            out[uid] = (out[uid], step, why)
    return out


@pytest.mark.parametrize("arch", ["qwen2_0_5b", "qwen2_moe_a2_7b",
                                  "rwkv6_7b", "zamba2_7b"])
def test_host_lengths_match_the_device_and_retire_as_it_would(arch):
    """The engine keeps every slot's cache length on the host: after
    each step it equals the device's ``cache["len"]``, free slots
    included, and each request retires at the step and with the tokens
    of a loop that reads the lengths from the device."""
    cfg = get_reduced(arch)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    decode = jax.jit(model.decode_step)
    prefill = jax.jit(lambda p, b: model.prefill(p, b, MIRROR_SEQ))
    plain = _serve_reading_device_lengths(model, params, decode, prefill,
                                          None)
    # EOS: a later token of request 2 that requests 0 and 1 never emit
    eos = next(t for t in plain[2][0][1:]
               if t not in plain[0][0] + plain[1][0])
    want = _serve_reading_device_lengths(model, params, decode, prefill,
                                         eos)
    assert [want[u][2] for u in range(3)] == ["count", "length", "eos"]

    eng = ServingEngine(cfg, params, slots=MIRROR_SLOTS,
                        max_seq=MIRROR_SEQ, eos_token=eos)
    reqs = _mirror_requests()
    for r in reqs:
        eng.submit(r)
    got = {}
    while eng.step():
        invariants.check_step(eng.sched)
        np.testing.assert_array_equal(eng.sched.lens,
                                      np.asarray(eng.cache["len"]))
        got.update((r.uid, (r.output, eng.stats.decode_steps))
                   for r in reqs if r.done_s is not None
                   and r.uid not in got)
    assert got == {u: (toks, step) for u, (toks, step, _) in want.items()}


# ------------------------------------------- one retirement rule
@pytest.fixture(scope="module")
def qwen():
    cfg = get_reduced("qwen2_0_5b")
    return cfg, Model(cfg, remat="none").init(jax.random.PRNGKey(0))


def _first_token(qwen, prompt):
    eng = ServingEngine(*qwen, slots=2, max_seq=MIRROR_SEQ)
    req = Request(uid=0, prompt=prompt, max_new_tokens=2)
    eng.submit(req)
    eng.step()
    return req.output[0]


@pytest.mark.parametrize("why", ["count", "eos", "length"])
def test_a_request_retires_at_its_first_token(qwen, why):
    """A request whose count, EOS or length is reached by its first
    token retires at admission with that one token and no decode step,
    as the plan-only path records it."""
    n = MIRROR_SEQ - 1 if why == "length" else 8
    prompt = np.arange(1, n + 1, dtype=np.int32)
    eos = _first_token(qwen, prompt) if why == "eos" else None
    eng = ServingEngine(*qwen, slots=2, max_seq=MIRROR_SEQ,
                        eos_token=eos)
    req = Request(uid=0, prompt=prompt,
                  max_new_tokens=1 if why == "count" else 5)
    eng.submit(req)
    assert eng.step()
    assert len(req.output) == 1 and req.done_s is not None
    assert eng.stats.decode_steps == 0 and eng.stats.tokens_out == 1
    assert eng.slot_req == [None, None] and eng.sched.n_finished == 1
    np.testing.assert_array_equal(eng.sched.lens, [0, 0])
    np.testing.assert_array_equal(np.asarray(eng.cache["len"]), [0, 0])
    assert not eng.step()


def test_a_one_token_request_beside_others_gets_one_token(qwen):
    eng = ServingEngine(*qwen, slots=2, max_seq=MIRROR_SEQ)
    reqs = [Request(uid=i, prompt=np.arange(1, 7 + i, dtype=np.int32),
                    max_new_tokens=new) for i, new in enumerate((1, 4, 1))]
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained()
    assert [len(r.output) for r in reqs] == [1, 4, 1]
    assert stats.tokens_out == 6 and stats.drained


def test_prefix_tokens_need_a_plan_only_engine():
    with pytest.raises(ValueError, match="prefix_tokens needs plan_only"):
        ServingEngine(get_reduced("qwen2_0_5b"), None, record_plans=True,
                      prefix_tokens=8)
