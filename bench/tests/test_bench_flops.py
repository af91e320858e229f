"""FLOP, byte and parameter counts against hand counts for the two
MiniCPM-2B shapes of the benchmark."""
import json
from pathlib import Path

import pytest

from lib.flops import Dense, flash_attention_cost, least_time

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def shape(name):
    return Dense.from_config(json.loads((CONFIGS / name).read_text()))


def test_layer_params_by_hand():
    d = shape("serve-minicpm-2b.json")
    # q, k, v, o: 4 x 2304 x 2304; SwiGLU: 3 x 2304 x 5760
    assert d.layer_matmul_params() == 4 * 2304 * 2304 + 3 * 2304 * 5760 \
        == 61_046_784


@pytest.mark.parametrize("name,total", [
    # 30,689 x 2304 embedding + 4 x 61,051,392 (layer with its two norms)
    # + the final norm
    ("re-minicpm-2b.json", 70_707_456 + 4 * 61_051_392 + 2304),
    # 122,753 x 2304 embedding + 40 layers + the final norm
    ("serve-minicpm-2b.json", 282_822_912 + 40 * 61_051_392 + 2304),
])
def test_params_by_hand(name, total):
    assert shape(name).params() == total


def test_train_flops_by_hand():
    d = shape("re-minicpm-2b.json")
    n = 4 * 61_046_784 + 30_689 * 2304          # matmul weights + head
    attn = 4 * 2 * 2 * 2304 * (512 * 513 / 2)   # causal q.k and p.v
    assert d.train_flops(4, 512) == pytest.approx(
        3 * (2 * n * 4 * 512 + 4 * attn), rel=1e-12)


def test_decode_flops_by_hand():
    d = shape("serve-minicpm-2b.json")
    n = 40 * 61_046_784 + 122_753 * 2304
    assert d.decode_token_flops(600) == pytest.approx(
        2 * n + 40 * 2 * 2 * 2304 * 600, rel=1e-12)
    body = 2 * 40 * 61_046_784 * 8 * 512 + 2 * 122_753 * 2304 * 8
    assert d.prefill_flops(8, 512) == pytest.approx(
        body + 8 * 40 * 4 * 2304 * 512 * 513 / 2, rel=1e-12)


def test_flash_attention_cost_and_roofline():
    ops, nbytes = flash_attention_cost(8, 512, 36, 36, 64)
    assert ops == 8 * 36 * 4 * 64 * 512 * 513 / 2
    assert nbytes == 2 * 8 * 512 * 64 * (2 * 36 + 2 * 36)
    # 128 operations a byte at 512 positions and head size 64: under the
    # v5e ridge of 197e12 / 819e9 = 240, so the bytes bound it
    t, bound = least_time(ops, nbytes, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    t, bound = least_time(1e12, 1.0, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)
    t, bound = least_time(1.0, 819e9, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(1.0)
