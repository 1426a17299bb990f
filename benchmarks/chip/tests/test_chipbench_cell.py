"""What a configuration file serves: its ``program`` overrides applied to
the registry's ModelConfig, held to every published key ``HF_KEYS`` knows,
and the sizes the reference reads at smoke size."""

import json
import sys
from dataclasses import fields, make_dataclass, replace
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import cell  # noqa: E402

cell._program_on_path()
from repro.configs.base import get_config, get_smoke_config  # noqa: E402

CONFIGS = sorted(p.stem for p in (CHIP / "configs").glob("*.json"))
DENSE = ["phi3_mini_3p8b", "deepseek_7b_15l"]

# DeepSeek-V2-Lite's config.json (huggingface.co/deepseek-ai/DeepSeek-V2-Lite)
DSV2_LITE = {
    "arch": "deepseek_v2_lite_16b", "hidden_size": 2048,
    "intermediate_size": 10944, "num_attention_heads": 16,
    "num_key_value_heads": 16, "num_hidden_layers": 27, "vocab_size": 102400,
    "rope_theta": 10000, "tie_word_embeddings": False, "rms_norm_eps": 1e-06,
    "n_routed_experts": 64, "num_experts_per_tok": 6,
    "moe_intermediate_size": 1408, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "max_position_embeddings": 163840, "hidden_act": "silu"}


def _file(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_serves_what_it_states(name):
    cfile = _file(name)
    cfg = cell.served_config(cfile)
    for key in cfile.get("reduced", []):
        if key in cell.HF_KEYS:
            assert cell.hf_value(cfg, key) == cfile[key], key


@pytest.mark.parametrize("name", DENSE)
def test_dense_cells_serve_as_before(name):
    cfile = _file(name)
    assert cell.served_config(cfile) == replace(
        get_config(cfile["arch"]), n_layers=cfile["num_hidden_layers"])


@pytest.mark.parametrize("name", DENSE)
def test_dense_smoke_reference_sizes_as_before(name):
    cfile = _file(name)
    smoke = get_smoke_config(cfile["arch"])
    got = cell.ref_sizes(smoke, cfile)
    want = {"num_attention_heads": smoke.n_heads,
            "num_key_value_heads": smoke.kv_heads,
            "num_hidden_layers": smoke.n_layers,
            "rms_norm_eps": cfile["rms_norm_eps"],
            "rope_theta": smoke.attn.rope_theta, "vocab_size": smoke.vocab}
    assert {k: got[k] for k in want} == want


def test_moe_and_latent_smoke_reference_sizes():
    smoke = get_smoke_config("deepseek_v2_lite_16b")
    got = cell.ref_sizes(smoke, {"arch": "deepseek_v2_lite_16b",
                                 "rms_norm_eps": 1e-6})
    want = {"hidden_size": 64, "num_hidden_layers": 3, "vocab_size": 512,
            "n_routed_experts": 4, "num_experts_per_tok": 2,
            "moe_intermediate_size": 32, "n_shared_experts": 1,
            "first_k_dense_replace": 1, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "rms_norm_eps": 1e-6}
    assert {k: got[k] for k in want} == want


def test_state_space_smoke_reference_sizes():
    smoke = get_smoke_config("jamba_v0_1_52b")
    got = cell.ref_sizes(smoke, {"arch": "jamba_v0_1_52b"})
    want = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
            "mamba_head_dim": 16, "attn_layer_period": 8,
            "attn_layer_offset": 4, "num_experts": 4,
            "num_experts_per_tok": 2, "expert_layer_period": 2,
            "expert_layer_offset": 1, "num_key_value_heads": 2}
    assert {k: got[k] for k in want} == want


def test_published_keys_are_held_to_the_served_config():
    assert cell.served_config(DSV2_LITE) == get_config("deepseek_v2_lite_16b")
    with pytest.raises(cell.CellError, match="kv_lora_rank"):
        cell.served_config(dict(DSV2_LITE, kv_lora_rank=256))
    # a stated departure is not held
    cell.served_config(dict(DSV2_LITE, kv_lora_rank=256,
                            assumed={"kv_lora_rank": "why it differs"}))


def test_program_overrides_apply_nested():
    cfile = dict(DSV2_LITE, num_hidden_layers=6, num_experts_per_tok=2,
                 reduced=["num_hidden_layers", "num_experts_per_tok"],
                 published={"num_hidden_layers": 27,
                            "num_experts_per_tok": 6},
                 program={"n_layers": 6, "moe.top_k": 2})
    cfg = cell.served_config(cfile)
    assert (cfg.n_layers, cfg.moe.top_k, cfg.moe.num_experts) == (6, 2, 64)
    # the override is what is served: the file must state it
    with pytest.raises(cell.CellError, match="num_experts_per_tok"):
        cell.served_config(dict(cfile, num_experts_per_tok=6))
    with pytest.raises(cell.CellError, match="no field"):
        cell.served_config(dict(cfile, program={"moe.experts": 8}))
    with pytest.raises(cell.CellError, match="has no mla"):
        cell.served_config(dict(cfile, arch="deepseek_moe_16b",
                                program={"mla.kv_lora": 8}))


def test_a_reduced_key_differs_from_its_published_value():
    cfile = dict(DSV2_LITE, reduced=["num_hidden_layers"],
                 published={"num_hidden_layers": 27})
    with pytest.raises(cell.CellError, match="does not differ"):
        cell.served_config(cfile)


def test_a_share_of_the_routed_experts(monkeypatch):
    """A program whose MoE layer holds a share of the experts: the file
    states the experts held here, the router keeps the published count."""
    import repro.configs.base as base
    Here = make_dataclass("MoEShare", [("num_experts_here", int, 0)],
                          bases=(base.MoECfg,), frozen=True)
    dsv2 = get_config("deepseek_v2_lite_16b")
    share = replace(dsv2, moe=Here(**{f.name: getattr(dsv2.moe, f.name)
                                      for f in fields(base.MoECfg)}))
    monkeypatch.setattr(base, "get_config", lambda arch: share)
    cfile = dict(DSV2_LITE, n_routed_experts=8,
                 reduced=["n_routed_experts"],
                 published={"n_routed_experts": 64},
                 program={"moe.num_experts_here": 8})
    cfg = cell.served_config(cfile)
    assert (cfg.moe.num_experts, cfg.moe.num_experts_here) == (64, 8)
    with pytest.raises(cell.CellError, match="n_routed_experts"):
        cell.served_config(dict(cfile, program={}))
