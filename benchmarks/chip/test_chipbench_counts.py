"""FLOP and byte counts against hand counts for both configurations, and
the table of peaks."""
import json

import pytest

from chipbench import counts, dims
from chipbench import spec
from chipbench.spec import BENCH_DIR


def _dims(name):
    return dims.from_config(json.loads(
        (BENCH_DIR / "configs" / f"{name}.json").read_text()))


NEMO = _dims("mistral-nemo-12b-l12")
GRANITE = _dims("granite-moe-3b-a800m")


def test_nemo_hand_counts():
    # q and o: 5120 x 4096 each; k and v: 5120 x 1024 each
    assert counts.attn_params(NEMO) == 2 * 5120 * 4096 + 2 * 5120 * 1024
    assert counts.ffn_params_touched(NEMO) == 3 * 5120 * 14336
    assert counts.head_params(NEMO) == 5120 * 131072
    per_layer = 52_428_800 + 220_200_960
    assert counts.layer_flops(NEMO) == 2 * per_layer
    # a decode token at position 999 attends 1000 keys in 12 layers
    want = 12 * (2 * per_layer + 4 * 32 * 128 * 1000) + 2 * 671_088_640
    assert counts.token_flops(NEMO, 999, True) == want


def test_granite_counts_top8_routed_not_40_computed():
    routed = 1536 * 40 + 8 * 3 * 1536 * 512
    assert counts.ffn_params_touched(GRANITE) == routed
    computed = 1536 * 40 + 40 * 3 * 1536 * 512   # what dropless computes
    assert computed / routed > 4.9
    attn = 2 * 1536 * 1536 + 2 * 1536 * 512
    assert counts.layer_flops(GRANITE) == 2 * (attn + routed)
    # tied embedding is the LM head: 49155 x 1536
    assert counts.head_params(GRANITE) == 49155 * 1536
    # the whole model is 3.30 B parameters; a token touches 0.88 B
    total = 49155 * 1536 + 32 * (attn + 1536 * 40 + 40 * 3 * 1536 * 512)
    assert 3.29e9 < total < 3.31e9
    active = counts.head_params(GRANITE) + 32 * (attn + routed)
    assert 0.87e9 < active < 0.89e9


def test_decode_attention_counts():
    f, b = counts.decode_attn(NEMO, 100)
    # K and V of 100 tokens: 100 x 8 x 128 x 2 x 2 bytes a layer, plus q,
    # out (32 x 128 x 2 bytes each) and the new k, v (8 x 128 x 2 each)
    per_layer = 100 * 8 * 128 * 4 + 2 * 4096 * 2 + 2 * 1024 * 2
    assert b == 12 * per_layer
    assert f == 12 * 4 * 32 * 128 * 101
    # 4 FLOPs per K/V byte per query group of 4: deeply memory-bound
    t, bound = counts.roofline_s(f, b, {"bf16_flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_prefill_attention_counts():
    assert counts.causal_pairs(0, 4) == 1 + 2 + 3 + 4
    assert counts.causal_pairs(64, 128) == sum(range(65, 129))
    f, b = counts.prefill_attn(GRANITE, 0, 1024)
    assert f == 32 * 4 * 24 * 64 * (1024 * 1025 // 2)
    assert b == 32 * (1024 * 512 * 2 * 2 + 1024 * 2 * 1536 * 2)
    _, bound = counts.roofline_s(f, b, {"bf16_flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9})
    assert bound == "compute"


def test_prefill_flops_add_up_over_chunks():
    whole = counts.prefill_flops(NEMO, 0, 300, 300)
    parts = sum(counts.prefill_flops(NEMO, a, min(a + 64, 300), 300)
                for a in range(0, 300, 64))
    assert whole == parts
    # the LM head runs once, for the last prompt position
    assert whole - counts.prefill_flops(NEMO, 0, 300, 301) == \
        2 * counts.head_params(NEMO)


def test_peak_table_is_keyed_by_device_kind():
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2**30
    assert "TPU v5e" in table["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        spec.peak_for("cpu")
