"""workcount.py against hand counts: one decode and one prefill shape of
Phi-3-mini (d 3072, 32 heads of 96, d_ff 8192, 32 layers, vocab 32064),
the dense cells' counts against the dense-only rule they were read with
before counts followed layer kinds, and sparse-expert, latent-attention
and windowed layers worked from their published sizes."""

import json
import sys
from dataclasses import make_dataclass, replace
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import cell  # noqa: E402
import workcount  # noqa: E402

cell._program_on_path()
from repro.configs.base import AttnCfg, MoECfg, get_config  # noqa: E402

PHI3 = get_config("phi3_mini_3p8b")
# q, k, v, o: 4 x 3072 x 3072; gate, in, out: 3 x 3072 x 8192
LAYER = 4 * 3072 * 3072 + 3 * 3072 * 8192


def test_phi3_is_the_published_model():
    assert (PHI3.n_layers, PHI3.d_model, PHI3.n_heads, PHI3.kv_heads,
            PHI3.head_dim, PHI3.d_ff, PHI3.vocab) == \
        (32, 3072, 32, 32, 96, 8192, 32064)


def test_layer_params():
    assert workcount.layer_matmul_params(PHI3) == LAYER == 113_246_208


def test_decode_one_row():
    # one row attending to 100 keys: 2 ops per weight, 4 x 3072 per key
    # (scores and values), per layer; then the LM head over 32064 words
    want = 32 * (2 * LAYER + 4 * 3072 * 100) + 2 * 3072 * 32064
    assert want == 7_484_080_128
    assert workcount.decode_flops(PHI3, [100]) == want


def test_prefill_full_and_suffix():
    # 256 tokens, causal: token p sees p + 1 keys, 256 * 257 / 2 in all
    keys = 256 * 257 // 2
    want = 256 * 32 * 2 * LAYER + 32 * 4 * 3072 * keys + 2 * 3072 * 32064
    assert workcount.prefill_flops(PHI3, 256, 256) == want
    # the last 64 tokens behind a 192-token hit see keys 193..256
    keys = sum(range(193, 257))
    want = 64 * 32 * 2 * LAYER + 32 * 4 * 3072 * keys + 2 * 3072 * 32064
    assert workcount.prefill_flops(PHI3, 256, 64) == want


def test_decode_bytes():
    # bf16: every layer's weights and its two norms, the final norm, the
    # LM head, 2 embedding rows; K and V of 32 layers x 3072 per token
    weights = 2 * (32 * (LAYER + 2 * 3072) + 3072 + 3072 * 32064
                   + 2 * 3072)
    kv = 2 * 32 * 3072 * 2
    assert kv == 393_216                  # 384 KiB per token
    want = weights + kv * (100 + 50) + kv * 2
    assert workcount.decode_bytes(PHI3, [100, 50]) == want


# ------------------------------------------------- the dense cells unchanged
def _dense_rule(cfg):
    """The counts the dense cells were read with before counts followed
    layer kinds: every layer q/k/v/o plus a SwiGLU of ``d_ff``."""
    L, d, H, KVH, hd, f, V = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                              cfg.kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab)
    layer = 2 * d * H * hd + 2 * d * KVH * hd + 3 * d * f
    head = 2 * d * V

    def decode_flops(ctx_rows):
        return sum(L * (2 * layer + 4 * H * hd * c) + head for c in ctx_rows)

    def prefill_flops(n, computed):
        start = n - computed
        keys = (n * (n + 1) - start * (start + 1)) / 2
        return computed * L * 2 * layer + L * 4 * H * hd * keys + head

    def decode_bytes(ctx_rows):
        kv = 2 * L * KVH * hd * 2
        rows = len(ctx_rows)
        weights = 2 * (L * (layer + 2 * d) + d + d * V + rows * d)
        return weights + kv * sum(ctx_rows) + kv * rows

    return decode_flops, prefill_flops, decode_bytes


@pytest.mark.parametrize("name", ["phi3_mini_3p8b", "deepseek_7b_15l"])
def test_dense_cells_count_as_before(name):
    cfg = cell.served_config(json.loads(
        (CHIP / "configs" / f"{name}.json").read_text()))
    dflops, pflops, dbytes = _dense_rule(cfg)
    for rows in ([1], [100], [600] * 6, [1920, 64, 300, 1200, 17, 5, 9, 2]):
        assert workcount.decode_flops(cfg, rows) == dflops(rows)
        assert workcount.decode_bytes(cfg, rows) == dbytes(rows)
    for n, computed in ((64, 64), (1536, 1536), (2048, 480.0), (1664, 1)):
        assert workcount.prefill_flops(cfg, n, computed) == \
            pflops(n, computed)


# ------------------------------------------------------- other layer kinds
# DeepSeekMoE 16B (arXiv:2401.06066): d 2048, 16 heads of 128 (MHA), 28
# layers, the first dense (d_ff 10944), then 64 routed experts of 1408,
# top 6, and 2 shared; vocab 102400
ATTN = 4 * 2048 * 16 * 128                       # q, k, v, o
DENSE = 3 * 2048 * 10944
MOE = 2048 * 64 + 3 * 2048 * 1408 * (6 + 2)      # router, 6 routed + 2 shared


def test_moe_flops():
    cfg = get_config("deepseek_moe_16b")
    assert (ATTN, DENSE, MOE) == (16_777_216, 67_239_936, 69_337_088)
    assert workcount.layer_matmul_params(cfg, 0) == ATTN + DENSE
    assert workcount.layer_matmul_params(cfg, 1) == ATTN + MOE
    params = 28 * ATTN + DENSE + 27 * MOE
    want = 2 * params + 28 * 4 * 16 * 128 * 100 + 2 * 2048 * 102400
    assert workcount.decode_flops(cfg, [100]) == want
    keys = 256 * 257 // 2
    want = 256 * 2 * params + 28 * 4 * 16 * 128 * keys + 2 * 2048 * 102400
    assert workcount.prefill_flops(cfg, 256, 256) == want


def test_moe_bytes_need_the_experts_read():
    cfg = get_config("deepseek_moe_16b")
    with pytest.raises(workcount.Uncounted):
        workcount.decode_bytes(cfg, [100, 50])
    # 10 distinct routed experts read per MoE layer, the 2 shared, router
    moe = 2048 * 64 + 3 * 2048 * 1408 * (10 + 2)
    weights = 2 * (28 * (ATTN + 2 * 2048) + DENSE + 27 * moe + 2048
                   + 2048 * 102400 + 2 * 2048)
    kv = 2 * 16 * 128 * 2                        # per layer and token
    want = weights + 28 * kv * (100 + 50) + 28 * kv * 2
    assert workcount.decode_bytes(cfg, [100, 50], experts_read=10) == want


def test_moe_counts_the_experts_held_here():
    cfg = get_config("deepseek_moe_16b")
    Here = make_dataclass("Here", [("num_experts_here", int, 8)],
                          bases=(MoECfg,), frozen=True)
    held = replace(cfg, moe=Here(**{f: getattr(cfg.moe, f) for f in (
        "num_experts", "top_k", "d_ff_expert", "num_shared", "every",
        "first_dense")}))
    # 8 of 64 experts here: 6 x 8 / 64 routed experts' work a token
    moe = 2048 * 64 + 3 * 2048 * 1408 * (6 * 8 / 64 + 2)
    assert workcount.layer_matmul_params(held, 1) == ATTN + moe


# DeepSeek-V2-Lite (arXiv:2405.04434): d 2048, 16 heads, MLA with
# kv_lora 512, qk_nope 128, qk_rope 64, v_head 128; 27 layers, first dense
MLA = (2048 * 16 * (128 + 64)          # q
       + 2048 * (512 + 64)             # latent and rotary key
       + 512 * 16 * (128 + 128)        # W^UK, W^UV
       + 16 * 128 * 2048)              # o


def test_mla_flops():
    cfg = get_config("deepseek_v2_lite_16b")
    assert MLA == 13_762_560
    params = 27 * MLA + DENSE + 26 * MOE
    # decode attends in the latent space: score over 512 + 64, value 512
    per_key = 2 * 16 * (512 + 64 + 512)
    want = 2 * params + 27 * per_key * 100 + 2 * 2048 * 102400
    assert workcount.decode_flops(cfg, [100]) == want
    # prefill expands keys and values: score over 128 + 64, value 128
    per_key = 2 * 16 * (128 + 64 + 128)
    keys = 256 * 257 // 2
    want = 256 * 2 * params + 27 * per_key * keys + 2 * 2048 * 102400
    assert workcount.prefill_flops(cfg, 256, 256) == want


def test_mla_cache_bytes():
    cfg = get_config("deepseek_v2_lite_16b")
    assert workcount.kv_bytes_per_token(cfg) == 27 * (512 + 64) * 2


def test_window_caps_the_keys():
    # layers alternate local (window 8) and global over 4 layers
    cfg = replace(PHI3, n_layers=4,
                  attn=AttnCfg(window=8, pattern=("l", "g")))
    key = 4 * 32 * 96
    base = 2 * 4 * LAYER + 2 * 3072 * 32064
    assert workcount.decode_flops(cfg, [100]) == base + key * (8 + 100) * 2
    # 20 positions: a local layer sees 1..8 then 8 a position
    local = 8 * 9 // 2 + 12 * 8
    want = 20 * 2 * 4 * LAYER + key * 2 * (local + 20 * 21 // 2) \
        + 2 * 3072 * 32064
    assert workcount.prefill_flops(cfg, 20, 20) == want


def test_state_space_layers_are_not_counted():
    with pytest.raises(workcount.Uncounted):
        workcount.decode_flops(get_config("jamba_v0_1_52b"), [100])
