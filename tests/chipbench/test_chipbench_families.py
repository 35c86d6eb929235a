"""An architecture family is a piece of the benchmark found by name: the
configuration's ``model`` group names it, and a new one goes in as a new
file under ``families/``, with no edit to any file that is there."""
import json
import shutil
import time
import zlib
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import harness, program, spec, weights, work

import mla_moe_preset
import test_chipbench_spec
from chipbench_fixtures import TINY_MODEL

HERE = Path(__file__).resolve().parent

# crc32 of every leaf of weights.make_all(TINY_MODEL, seed), drawn before
# the layout moved into families/dense_gqa.py; lm_head from the same
# group untied and without biases.
PINNED = {
    7: {"embed": 730896497, "final_norm/scale": 884259342,
        "layers/attn/bk": 4199787604, "layers/attn/bq": 1763528669,
        "layers/attn/bv": 1307016228, "layers/attn/wk": 2011569567,
        "layers/attn/wo": 2984110582, "layers/attn/wq": 3610847319,
        "layers/attn/wv": 2104326213, "layers/ln1/scale": 3717287426,
        "layers/ln2/scale": 4180948635, "layers/mlp/wi_gate": 446836970,
        "layers/mlp/wi_up": 1323834484, "layers/mlp/wo": 1277479932,
        "lm_head": 4190762695},
    2**33 + 5: {"embed": 1557421976, "final_norm/scale": 329955864,
                "layers/attn/bk": 2111043822, "layers/attn/bq": 1572194088,
                "layers/attn/bv": 3695032728, "layers/attn/wk": 3149043441,
                "layers/attn/wo": 2656349897, "layers/attn/wq": 297570397,
                "layers/attn/wv": 3020550947,
                "layers/ln1/scale": 3352024557,
                "layers/ln2/scale": 1998914998,
                "layers/mlp/wi_gate": 3709555562,
                "layers/mlp/wi_up": 3411033762,
                "layers/mlp/wo": 1046983047, "lm_head": 2113381618},
}

# deepseek-v3's reduced preset (configs/deepseek_v3_671b.reduced()) as a
# configuration file: the published keys at the preset's sizes, and the
# model group: MLA, 8 routed experts with 2 a token and one shared, one
# leading dense layer, the multi-token-prediction block.
MOE_MODEL = {
    "family": "mla_moe_preset", "layers": 2, "dense_layers": 1,
    "d_model": 64, "heads": 4, "d_ff": 96, "vocab": 256,
    "vocab_padded": 256, "vocab_pad_multiple": 16, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "experts": 8, "experts_per_token": 2,
    "expert_d_ff": 48, "shared_experts": 1, "mtp": True,
    "rope_theta": 10000.0, "norm_eps": 1e-5, "tied_embeddings": False,
    "dtype": "bfloat16"}
MOE_PUBLISHED = {
    "num_hidden_layers": 2, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "intermediate_size": 96, "vocab_size": 256,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 48,
    "n_shared_experts": 1, "num_nextn_predict_layers": 1,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
MOE_FILE = dict(
    MOE_PUBLISHED, name="deepseek-v3-preset", source="test", reduced=[],
    changed={}, model_type="deepseek_v3", model=MOE_MODEL,
    published_keys={
        "layers": "num_hidden_layers", "dense_layers": "first_k_dense_replace",
        "d_model": "hidden_size", "heads": "num_attention_heads",
        "d_ff": "intermediate_size", "vocab": "vocab_size",
        "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim",
        "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
        "experts": "n_routed_experts",
        "experts_per_token": "num_experts_per_tok",
        "expert_d_ff": "moe_intermediate_size",
        "shared_experts": "n_shared_experts", "rope_theta": "rope_theta",
        "norm_eps": "rms_norm_eps", "tied_embeddings": "tie_word_embeddings"})


def _checksums(tree):
    return {"/".join(k.key for k in path):
            zlib.crc32(np.asarray(leaf).tobytes())
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _set_family(base, name, model=TINY_MODEL):
    cfg = base / "configs" / "tiny.json"
    f = json.loads(cfg.read_text())
    f["model"] = dict(model, family=name)
    cfg.write_text(json.dumps(f))


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_dense_weights_are_drawn_as_before(seed):
    want = PINNED[seed]
    got = _checksums(weights.make_all(TINY_MODEL, seed))
    assert got == {k: v for k, v in want.items() if k != "lm_head"}
    untied = dict(TINY_MODEL, tied_embeddings=False, qkv_bias=False)
    got = _checksums(weights.make_all(untied, seed))
    assert got == {k: v for k, v in want.items()
                   if not k.startswith("layers/attn/b")}


def test_a_family_added_as_a_file_is_found_and_run(tiny_bench):
    root, base = tiny_bench
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()
              and p.name != "tiny.json"}
    shutil.copy(base / "families" / "dense_gqa.py",
                base / "families" / "dense_gqa_copy.py")
    _set_family(base, "dense_gqa_copy")
    c = harness.load_cell(root, "tiny.closed", False, base)
    assert spec.family_of(c.m).__file__ == str(
        base / "families" / "dense_gqa_copy.py")
    result, _ = harness.run_cell(
        root, "tiny.closed", 2**33 + 9, 0.8, False,
        t_proc=time.perf_counter(), require_tpu=False, base=base)
    assert result["correct"] is True and result["failed"] == 0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_family_name_stands_for_one_file(tmp_path):
    code = (spec.HERE / "families" / "dense_gqa.py").read_bytes()
    for d, text in (("a", code), ("b", code), ("c", code + b"# other\n")):
        (tmp_path / d / "families").mkdir(parents=True)
        (tmp_path / d / "families" / "one_name.py").write_bytes(text)
    first = spec.family("one_name", tmp_path / "a")
    assert spec.family("one_name", tmp_path / "b") is first
    assert spec.family_of({"family": "one_name"}) is first
    with pytest.raises(ValueError, match=r"c/families/one_name\.py"):
        spec.family("one_name", tmp_path / "c")


@pytest.mark.parametrize("family,match", [
    (None, r"configs/tiny\.json"),
    ("no_such_family", r"families/no_such_family\.py")])
def test_a_missing_or_unknown_family_raises_naming_the_file(
        tiny_bench, family, match):
    root, base = tiny_bench
    if family is None:
        cfg = base / "configs" / "tiny.json"
        f = json.loads(cfg.read_text())
        del f["model"]["family"]
        cfg.write_text(json.dumps(f))
    else:
        _set_family(base, family)
    with pytest.raises((KeyError, FileNotFoundError), match=match):
        harness.load_cell(root, "tiny.closed", False, base)
    # a bare model group, found by its name alone
    m = {k: v for k, v in TINY_MODEL.items() if k != "family"}
    if family is not None:
        m["family"] = family
    with pytest.raises((KeyError, FileNotFoundError),
                       match="families" if family else "family"):
        work.decode_step(m, [3])


def test_a_latent_attention_expert_family_goes_in_as_files(tiny_bench):
    root, base = tiny_bench
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    shutil.copy(HERE / "mla_moe_preset.py",
                base / "families" / "mla_moe_preset.py")
    (base / "configs" / "deepseek-v3-preset.json").write_text(
        json.dumps(MOE_FILE))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = {"name": "deepseek-v3-preset", "source": "test",
             "file": "chipbench/configs/deepseek-v3-preset.json",
             "reduced": [], "why": "test"}
    bench["configs"].append(entry)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the spec's own checks take it as they take the benchmark's configs
    test_chipbench_spec.check_names(bench)
    test_chipbench_spec.check_model_group(entry, root, base)
    m = spec.config("deepseek-v3-preset", base)["model"]
    cfg = program.model_config("deepseek-v3-preset", m)
    assert cfg.moe.first_dense_layers == 1 and cfg.mla.kv_lora_rank == 16
    params = program.make_params(cfg, m, 3)
    assert params["layers"]["moe"]["wi_gate"].shape == (1, 8, 64, 48)
    assert params["dense_layers"]["attn"]["wuk"].shape == (1, 16, 4, 16)
    # the work counts come from the family, worked by hand below
    flops, nbytes = work.decode_step(m, [5, 9])
    assert flops > 0 and nbytes > 0
    assert (flops, nbytes) == mla_moe_preset.decode_step(m, [5, 9])
    assert work.prefill(m, 32) == mla_moe_preset.prefill(m, 32) > 0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_latent_attention_expert_counts_by_hand():
    """The preset family's counts, worked by hand for MOE_MODEL. A layer's
    attention: wdq 64*32 + wuq 32*4*24 + wdkv 64*16 + wkr 64*8 + wuk and
    wuv 16*4*32 + wo 4*16*64 = 12,800; the dense layer's MLP 3*64*96 =
    18,432; the MoE layer's router 64*8 and 2 routed + 1 shared experts
    of 3*64*48: 28,160; so 72,192 matrix parameters a token, and a head of
    64*256 = 16,384. Latent cache: 2 layers * (16 + 8) * 2 B = 96 B a
    token."""
    m = MOE_MODEL
    mm, head = 31_232 + 40_960, 16_384
    assert mla_moe_preset.latent_bytes_per_token(m) == 96
    # decode over slots at 5 and 9: QK 2 (16 + 8) and PV 2 * 16 FLOPs a
    # key, head and layer, over 6 + 10 keys
    flops, nbytes = mla_moe_preset.decode_step(m, [5, 9])
    assert flops == 2 * (mm + head) * 2 + 2 * 4 * (48 + 32) * 16
    # weights once (the MTP block is not served), float32 norm gains
    # (ln1, ln2, q_norm 32, kv_norm 16 a layer; the final norm), two
    # gathered embedding rows, the latent cache read and written
    norms = 4 * (2 * (2 * 64 + 32 + 16) + 64)
    assert nbytes == (mm + head) * 2 + norms + 2 * 64 * 2 + 96 * (14 + 2)
    # prefill of 32 tokens: per query-key pair, head and layer, QK on
    # the expanded keys 2 (16 + 8) and PV 2 * 16; causal
    assert mla_moe_preset.prefill(m, 32) == (
        2 * mm * 32 + 2 * head + 2 * 4 * (48 + 32) * 32 * 33 / 2)
