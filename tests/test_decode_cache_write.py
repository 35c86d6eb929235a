"""The decode step reads the stacked KV cache and writes only the new
token: its logits and cache against the old algorithm written out here
(write the token into the cache, then attend over the whole cache), the
structure of the decode program, and the served tokens of an engine.

Families with a stacked attention cache (dense, vlm, moe with and
without dense layers, MLA, audio self-attention), bf16 and INT8 KV, on
the CPU at reduced size."""
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_reduced
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.models import tuning as TU
from repro.models.model import Model
from repro.serving.engine import Request, ServingEngine

B, S, TE = 3, 40, 6
LENS = (0, 3, S - 3)        # a free slot, a short one, one near the end
STEPS = 3                   # the last writes position S - 1
# bf16 tolerance: four ulps (eps 2**-7) of the largest magnitude compared;
# INT8 entries quantize such a value, so they may differ by 127 * 2**-5
BF16_TOL = 4 * 2.0 ** -7
INT8_TOL = 127 * BF16_TOL


def _close(got, want, msg=""):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=0, err_msg=msg,
                               atol=BF16_TOL * np.abs(want).max())

CASES = [(a, q) for a in ARCH_IDS
         if get_reduced(a).family in ("dense", "vlm", "moe", "audio")
         for q in ((False,) if get_reduced(a).mla else (False, True))]


# ------------------------------------------------ the old algorithm
def _attend_cache(q, k, v, n_live, k_scale=None, v_scale=None):
    """Masked attention over the first ``n_live[b]`` cache positions, in
    the decode step's precision (bf16 operands, f32 sums)."""
    Bq, H, D = q.shape
    KH = v.shape[2]
    qg = q.reshape(Bq, KH, H // KH, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    if k_scale is not None:
        s = s * k_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None]
    live = jnp.arange(v.shape[1])[None, :] < n_live[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, None], s, L.NEG_INF), -1)
    if v_scale is not None:
        p = p * v_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None]
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(jnp.bfloat16),
                   v.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return o.reshape(Bq, H, -1).astype(jnp.bfloat16)


def _old_gqa(p, h, cfg, c, pos):
    q = jnp.einsum("bd,dhk->bhk", h, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", h, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", h, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = L.apply_rope(q[:, None], pos[:, None], cfg)[:, 0]
    k = L.apply_rope(k[:, None], pos[:, None], cfg)[:, 0]
    b = jnp.arange(h.shape[0])
    if "k_scale" in c:
        ks = jnp.max(jnp.abs(k), -1) / 127.0 + 1e-8
        vs = jnp.max(jnp.abs(v), -1) / 127.0 + 1e-8
        c = {"k": c["k"].at[b, pos].set(
                 jnp.round(k / ks[..., None]).astype(jnp.int8)),
             "v": c["v"].at[b, pos].set(
                 jnp.round(v / vs[..., None]).astype(jnp.int8)),
             "k_scale": c["k_scale"].at[b, pos].set(ks.astype(jnp.float16)),
             "v_scale": c["v_scale"].at[b, pos].set(vs.astype(jnp.float16))}
        o = _attend_cache(q, c["k"], c["v"], pos + 1, c["k_scale"],
                          c["v_scale"])
    else:
        c = {"k": c["k"].at[b, pos].set(k.astype(c["k"].dtype)),
             "v": c["v"].at[b, pos].set(v.astype(c["v"].dtype))}
        o = _attend_cache(q, c["k"], c["v"], pos + 1)
    return jnp.einsum("bhk,hkd->bd", o, p["wo"]), c


def _old_mla(p, h, cfg, c, pos):
    m = cfg.mla
    nope, rd = m.qk_nope_head_dim, m.qk_rope_head_dim
    cq = L.apply_norm(p["q_norm"], h @ p["wdq"], cfg)
    q = jnp.einsum("br,rhk->bhk", cq, p["wuq"])
    q_nope = q[..., :nope]
    q_rope = L.apply_rope(q[..., nope:][:, None], pos[:, None], cfg,
                          dim=rd)[:, 0]
    ckv = L.apply_norm(p["kv_norm"], h @ p["wdkv"], cfg)
    kr = L.apply_rope((h @ p["wkr"])[:, None, None, :], pos[:, None], cfg,
                      dim=rd)[:, 0, 0]
    b = jnp.arange(h.shape[0])
    c = {"ckv": c["ckv"].at[b, pos].set(ckv.astype(c["ckv"].dtype)),
         "kr": c["kr"].at[b, pos].set(kr.astype(c["kr"].dtype))}
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, p["wuk"])
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, c["ckv"],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhk,bsk->bhs", q_rope, c["kr"],
                      preferred_element_type=jnp.float32)) / math.sqrt(
                          nope + rd)
    live = jnp.arange(c["ckv"].shape[1])[None, :] <= pos[:, None]
    pr = jax.nn.softmax(jnp.where(live[:, None], s, L.NEG_INF), -1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pr.astype(jnp.bfloat16), c["ckv"])
    o = jnp.einsum("bhr,rhk->bhk", o_lat, p["wuv"])
    return jnp.einsum("bhk,hkd->bd", o, p["wo"]), c


def old_decode_step(params, cache, tokens, *, cfg):
    """One decode step as it was before the scan stopped carrying the
    cache: each layer writes its token into the cache, then attends over
    every position up to it."""
    x = params["embed"][tokens].astype(jnp.bfloat16)
    pos = cache["len"]
    if cfg.family == "audio":
        x = x + params["pos_embed"][pos].astype(x.dtype)
    out = {"len": pos + 1}
    stacks = [("layers", cfg.family == "moe")]
    if cfg.family == "moe" and cfg.moe.first_dense_layers:
        stacks.insert(0, ("dense_layers", False))
    attend = _old_mla if cfg.mla else _old_gqa
    for name, is_moe in stacks:
        n = jax.tree.leaves(cache[name])[0].shape[0]
        written = []
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params[name])
            h = L.apply_norm(lp["ln1"], x, cfg)
            a, c = attend(lp["attn"], h, cfg,
                          jax.tree.map(lambda a: a[i], cache[name]), pos)
            x = x + a
            written.append(c)
            if cfg.family == "audio":
                h = L.apply_norm(lp["ln_x"], x, cfg)
                q = jnp.einsum("bd,dhk->bhk", h, lp["xattn"]["wq"])
                o = _attend_cache(q, cache["cross"]["k"][i],
                                  cache["cross"]["v"][i],
                                  jnp.full((x.shape[0],), TE))
                x = x + jnp.einsum("bhk,hkd->bd", o, lp["xattn"]["wo"])
            h = L.apply_norm(lp["ln2"], x, cfg)
            if is_moe:
                y, _ = MOE.apply_moe(lp["moe"], h[:, None], cfg,
                                     capacity=x.shape[0])
                x = x + y[:, 0]
            else:
                x = x + L.apply_mlp(lp["mlp"], h, cfg)
        out[name] = jax.tree.map(lambda *a: jnp.stack(a), *written)
    if cfg.family == "audio":
        out["cross"] = cache["cross"]
    x = L.apply_norm(params["final_norm"], x[:, None], cfg)[:, 0]
    return out, T.lm_head(params, cfg, x)


# ------------------------------------------------------------ helpers
def _filled_cache(model, key):
    """A cache whose every position holds seeded values (so a read of an
    unwritten position shows), at lengths ``LENS``."""
    cache = model.init_cache(B, S, TE)
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(key, len(leaves))

    def fill(a, k):
        if a.dtype == jnp.int32:          # the lengths, set below
            return a
        if a.dtype == jnp.int8:
            return jax.random.randint(k, a.shape, -127, 128, jnp.int8)
        if a.dtype == jnp.float16:
            return jax.random.uniform(k, a.shape, jnp.float16, 0.005, 0.02)
        return jax.random.normal(k, a.shape, a.dtype)
    cache = jax.tree.unflatten(tree, [fill(a, k) for a, k in
                                      zip(leaves, keys)])
    cache["len"] = jnp.asarray(LENS, jnp.int32)
    return cache


def _check_write(before, after, want, pos):
    """``after`` equals ``before`` bit for bit except at [l, b, pos[b]],
    where it holds the old algorithm's entries."""
    at = np.zeros((B, S), bool)
    at[np.arange(B), pos] = True
    for name in ("layers", "dense_layers"):
        if name not in before:
            continue
        for key in before[name]:
            old = np.asarray(before[name][key])
            new = np.asarray(after[name][key])
            ref = np.asarray(want[name][key])
            np.testing.assert_array_equal(new[:, ~at], old[:, ~at],
                                          err_msg=f"{name}/{key}")
            if new.dtype == np.int8:
                assert np.abs(new[:, at].astype(int)
                              - ref[:, at].astype(int)).max() <= INT8_TOL
            else:
                _close(new[:, at], ref[:, at], f"{name}/{key}")


# ------------------------------------------------------------- tests
@pytest.mark.parametrize("arch,int8", CASES)
def test_decode_matches_writing_then_attending(arch, int8):
    """Slots at lengths 0, 3 and S - 3, three steps: the logits equal the
    old algorithm's to bf16 tolerance, and the step writes the cache at
    each slot's position and nowhere else."""
    cfg = get_reduced(arch)
    with TU.tuning_context(TU.Tuning(kv_cache_quant=int8)):
        model = Model(cfg, remat="none")
        params = model.init(jax.random.PRNGKey(0))
        cache = _filled_cache(model, jax.random.PRNGKey(1))
        step = jax.jit(model.decode_step)
        old = jax.jit(functools.partial(old_decode_step, cfg=cfg))
        rng = np.random.default_rng(2)
        for _ in range(STEPS):
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size, B),
                               jnp.int32)
            pos = np.asarray(cache["len"])
            new, logits = step(params, cache, toks)
            want, want_logits = old(params, cache, toks)
            _close(logits, want_logits)
            _check_write(cache, new, want, pos)
            np.testing.assert_array_equal(new["len"], pos + 1)
            if "cross" in cache:
                np.testing.assert_array_equal(new["cross"]["k"],
                                              cache["cross"]["k"])
            cache = new


def _scan_outputs(jaxpr):
    """Output shapes of every scan in ``jaxpr``, nested ones included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out += [v.aval.shape for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _scan_outputs(getattr(sub, "jaxpr", sub))
    return out


@pytest.mark.parametrize("arch", ["qwen2_0_5b", "deepseek_v3_671b",
                                  "whisper_tiny"])
def test_layer_scan_emits_no_cache_sized_output(arch):
    """The layer scan hands out the new token's entries, never a tensor
    with the cache's sequence length."""
    model = Model(get_reduced(arch), remat="none")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(B, S, TE))
    toks = jax.ShapeDtypeStruct((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(model.decode_step)(params, cache, toks)
    shapes = _scan_outputs(jaxpr.jaxpr)
    assert shapes, "the decode step scans its layers"
    assert all(S not in s for s in shapes), shapes


def test_launcher_decode_step_donates_the_cache_and_writes_it_in_place():
    """The launcher's decode step donates the cache, and every cache leaf
    is aliased to an output: the step writes it in place."""
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_decode_step

    run = RunConfig(model=get_reduced("qwen2_0_5b"),
                    shape=ShapeConfig("d", "decode", S, B), remat="none")
    mesh = make_host_mesh(data=1, model=1)
    built = build_decode_step(run, mesh)
    with mesh:
        lowered = jax.jit(built.fn, in_shardings=built.in_shardings,
                          out_shardings=built.out_shardings,
                          donate_argnums=built.donate_argnums).lower(
                              *built.abstract_inputs)
    donated = [a.donated for a in jax.tree.leaves(lowered.args_info[0][1])]
    assert donated and all(donated)
    n_cache = len(jax.tree.leaves(built.abstract_inputs[1]))
    text = lowered.compile().as_text()
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert alias and alias.group(1).count("may-alias") == n_cache


@pytest.fixture(scope="module")
def qwen():
    cfg = get_reduced("qwen2_0_5b")
    return cfg, Model(cfg, remat="none").init(jax.random.PRNGKey(0))


def _requests():
    rng = np.random.default_rng(5)
    return [Request(uid=i, prompt=rng.integers(1, 250, 4 + 3 * i)
                    .astype(np.int32), max_new_tokens=3 + 2 * (i % 4))
            for i in range(7)]


def _serve(eng, reqs):
    """Serve ``reqs`` over three slots: admissions and retirements."""
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert eng.stats.prefills == len(reqs) and eng.stats.drained
    assert [len(r.output) for r in reqs] == [r.max_new_tokens
                                             for r in reqs]
    return [list(r.output) for r in reqs]


def test_engine_serves_the_old_algorithms_tokens(qwen):
    """The engine serves the tokens of the same engine stepping the old
    algorithm."""
    cfg, params = qwen
    new = ServingEngine(cfg, params, slots=3, max_seq=48)
    old = ServingEngine(cfg, params, slots=3, max_seq=48)
    old._decode = jax.jit(functools.partial(old_decode_step, cfg=cfg))
    assert _serve(new, _requests()) == _serve(old, _requests())


def test_engine_reads_no_cache_it_donated(qwen):
    """With the decode step's cache donated, the engine's run reads no
    deleted buffer (admission, retirement and the next step all use the
    step's output) and serves the same tokens."""
    cfg, params = qwen
    plain = ServingEngine(cfg, params, slots=3, max_seq=48)
    donated = ServingEngine(cfg, params, slots=3, max_seq=48)
    decode = jax.jit(donated.model.decode_step, donate_argnums=(1,))
    given = []

    def step(p, cache, toks):
        given.append(cache["layers"]["k"])
        return decode(p, cache, toks)
    donated._decode = step
    assert _serve(donated, _requests()) == _serve(plain, _requests())
    assert given and all(k.is_deleted() for k in given)
