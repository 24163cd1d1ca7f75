"""Work counts against numbers worked by hand, for both configurations;
shared prompt pages count once."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from sartbench import roofline  # noqa: E402
from sartbench.spec import load_config  # noqa: E402

QWEN = load_config("qwen2-0.5b")["model"]


def r1_model():
    return load_config("r1-distill-14b-l8")["model"]


def test_layer_flops_by_hand():
    # qwen2-0.5b: q 896x896, k and v 896x128 each, o 896x896, MLP 3 x
    # 896x4864 -> 14,909,440 weights, two FLOPs each
    assert roofline.layer_matmul_flops(QWEN) == 29_818_880
    # r1-distill-14b: q and o 5120x5120, k and v 5120x1024, MLP 3 x
    # 5120x13824 -> 275,251,200 weights
    assert roofline.layer_matmul_flops(r1_model()) == 550_502_400


def test_kv_bytes_per_token_by_hand():
    # K and V, kv heads x head_dim, bf16, per layer
    assert roofline.kv_token_bytes(QWEN) * 24 == 12_288
    assert roofline.kv_token_bytes(r1_model()) * 8 == 32_768


def test_shared_prompt_pages_count_once():
    # rows A and B share prompt pages 5 and 6, then A writes page 9 (8
    # tokens) and B page 10 (4 tokens): 16 + 16 + 8 + 4
    tables = np.array([[5, 6, 9, 99], [5, 6, 10, 99]])
    assert roofline.distinct_tokens(tables, np.array([40, 36]), 16) == 44
    # a shared partial page counts the most tokens any row attends
    tables = np.array([[5, 6, 7], [5, 6, 7]])
    assert roofline.distinct_tokens(tables, np.array([35, 38]), 16) == 38
    # rows of no length read nothing
    assert roofline.distinct_tokens(tables, np.array([0, 0]), 16) == 0


def test_decode_attention_work_by_hand():
    tables = np.array([[5, 6, 9], [5, 6, 10]])
    flops, nbytes = roofline.decode_attention_work(
        QWEN, tables, np.array([40, 36]), 16)
    # 4 x 14 heads x 64 x (40 + 36) attended keys, 24 layers
    assert flops == 24 * 4 * 14 * 64 * 76 == 6_537_216
    # 44 distinct tokens x 512 B of K+V, plus q in and out (2 rows x 14 x
    # 64 x 2 B, twice), 24 layers
    assert nbytes == 24 * (44 * 512 + 7_168) == 712_704


def test_prefill_attention_work_by_hand():
    # a 32-row lane at position 64 attends 64 + 1 ... 64 + 32 keys
    flops, nbytes = roofline.prefill_attention_work(QWEN, 64, 32)
    assert flops == 24 * 4 * 14 * 64 * 2576
    assert nbytes == 24 * (96 * 512 + 2 * 32 * 14 * 64 * 2)


def test_step_model_flops_by_hand():
    got = roofline.step_model_flops(QWEN, 2, 76, [(64, 32)])
    decode_rows = 2 * (24 * 29_818_880 + 2 * 896 * 151_936)
    decode_attn = 24 * 4 * 14 * 64 * 76
    lane = 32 * 24 * 29_818_880 + 2 * 896 * 151_936
    lane_attn = 24 * 4 * 14 * 64 * 2576
    assert got == decode_rows + decode_attn + lane + lane_attn \
        == 25_377_128_448


def test_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_least_time_takes_the_larger_bound():
    kind = "TPU v5 lite"
    assert roofline.least_seconds(197e12, 1.0, kind) == pytest.approx(1.0)
    assert roofline.least_seconds(1.0, 819e9, kind) == pytest.approx(1.0)
