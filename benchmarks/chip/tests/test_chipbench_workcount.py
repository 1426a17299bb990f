"""workcount.py against hand counts for one decode and one prefill shape of
Phi-3-mini (d 3072, 32 heads of 96, d_ff 8192, 32 layers, vocab 32064)."""

import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import workcount  # noqa: E402

PHI3 = SimpleNamespace(n_layers=32, d_model=3072, n_heads=32, kv_heads=32,
                       head_dim=96, d_ff=8192, vocab=32064)
# q, k, v, o: 4 x 3072 x 3072; gate, in, out: 3 x 3072 x 8192
LAYER = 4 * 3072 * 3072 + 3 * 3072 * 8192


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
