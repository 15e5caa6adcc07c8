"""The dense plug-in's work counts against FLOPs and bytes worked out by
hand, each configuration's counts taken through the plug-in that it names."""
import json
import os

import pytest

import run

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def counts():
    """The plug-in both configurations name (they are dense decoders)."""
    archs = {_config(n)["arch"] for n in ("deepseek-7b-l10", "qwen3-8b-l12")}
    assert archs == {"dense"}
    return run.load_arch(_config("deepseek-7b-l10"))


def model(name):
    return _config(name)["model"]


# DeepSeek-LLM-7B cut to 10 layers: d 4096, 32 heads of 128 (MHA), ff 11008,
# vocab 102400.  One layer: 4096*128*(2*32+2*32) + 3*4096*11008
#   = 67,108,864 + 135,266,304 = 202,375,168 weights.
DS_LAYER = 202_375_168
# Qwen3-8B cut to 12 layers: 32 query and 8 KV heads of 128, ff 12288, vocab
# 151936.  One layer: 4096*128*(64+16) + 3*4096*12288
#   = 41,943,040 + 150,994,944 = 192,937,984.
QW_LAYER = 192_937_984


def test_layer_weights(counts):
    assert counts.layer_params(model("deepseek-7b-l10")) == DS_LAYER
    assert counts.layer_params(model("qwen3-8b-l12")) == QW_LAYER


def test_deepseek_decode_one_slot_at_1000(counts):
    """One token for one slot whose cache holds 1000 positions.

    FLOPs: 2*10*DS_LAYER (4,047,503,360) + logits 2*4096*102400
    (838,860,800) + attention 4*10*32*128*1001 (164,003,840).
    Bytes: weights 2*(10*DS_LAYER + 4096*102400) (4,886,364,160) + KV at
    the live length and the new row 1001*163,840 (164,003,840) + one
    embedding row 2*4096 (8,192)."""
    f, b = counts.decode_flops_bytes(model("deepseek-7b-l10"), [1000], 1,
                                     None)
    assert f == 4_047_503_360 + 838_860_800 + 164_003_840 == 5_050_368_000
    assert b == 4_886_364_160 + 164_003_840 + 8_192 == 5_050_376_192


def test_deepseek_prefill_first_chunk(counts):
    """512 prompt positions from 0: linear 2*10*DS_LAYER*512, attention
    4*10*32*128 over 512*513/2 causal pairs, logits of the last position."""
    f = counts.prefill_flops(model("deepseek-7b-l10"), 0, 512)
    assert f == 2_072_321_720_320 + 21_516_779_520 + 838_860_800
    assert f == 2_094_677_360_640


def test_qwen_prefill_third_chunk(counts):
    """512 positions from 1024: each attends to 1024 earlier positions and
    its causal part of the chunk, 512*1024 + 512*513/2 = 655,616 pairs."""
    f = counts.prefill_flops(model("qwen3-8b-l12"), 1024, 512)
    linear = 2 * 12 * QW_LAYER * 512            # 2,370,821,947,392
    attn = 4 * 12 * 32 * 128 * 655_616          # 128,899,350,528
    logits = 2 * 4096 * 151_936                 # 1,244,659,712
    assert linear == 2_370_821_947_392 and attn == 128_899_350_528
    assert f == linear + attn + logits == 2_500_965_957_632


def test_qwen_decode_two_slots_four_steps(counts):
    """Slots at 100 and 3000 positions, T=4: every step reads the weights
    once (2*(12*QW_LAYER + 4096*151936) = 5,875,171,328 bytes) and each
    slot's KV at its live length, 49,152 bytes a position (12 layers, K and
    V, 8 heads of 128, bf16)."""
    m = model("qwen3-8b-l12")
    assert counts.kv_bytes_per_token(m) == 49_152
    f, b = counts.decode_flops_bytes(m, [100, 3000], 4, None)
    weights = 4 * 5_875_171_328
    kv = sum((ln + t + 1) * 49_152 for ln in (100, 3000) for t in range(4))
    rows = 8 * 2 * 4096
    assert b == weights + kv + rows == 24_111_218_688
    assert f == 49_443_241_984
