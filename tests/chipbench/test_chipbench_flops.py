"""Operation and byte counts against hand counts for Yi-6B."""
import json
from pathlib import Path

import pytest

from chipbench import flops as F
from chipbench.model import Shape

CFG = json.loads((Path(__file__).parents[2] / "benchmarks" / "chip"
                  / "configs" / "yi6b-edge.json").read_text())
YI = Shape.from_config("yi-6b", CFG["models"]["yi-6b"]["config"])


def test_yi6b_shapes():
    assert (YI.d_model, YI.n_layers, YI.n_heads, YI.n_kv_heads,
            YI.head_dim, YI.d_ff, YI.vocab) == (4096, 32, 32, 4, 128,
                                                11008, 64000)


def test_matmul_params_by_hand():
    # q 4096x4096, k and v 4096x512 each, o 4096x4096, three 4096x11008
    layer = 16_777_216 + 2 * 2_097_152 + 16_777_216 + 3 * 45_088_768
    assert F.layer_matmul_params(YI) == layer == 173_015_040
    assert F.matmul_params(YI) == 32 * layer + 64000 * 4096
    assert F.matmul_params(YI) == 5_798_625_280


def test_kv_and_weight_bytes():
    assert F.kv_bytes_per_token(YI) == 32 * 2 * 4 * 128 * 2 == 65536
    norms = (2 * 32 + 1) * 4096 * 4
    assert F.weight_bytes(YI) == 2 * 5_798_625_280 + norms


def test_decode_step_counts():
    ctx = [100, 300]
    att = sum(4 * 32 * 32 * 128 * c for c in ctx)
    assert F.decode_step_flops(YI, ctx) == 2 * 2 * 5_798_625_280 + att
    kv = 65536 * (100 + 300) + 65536 * 2
    assert F.decode_step_bytes(YI, ctx) == (F.weight_bytes(YI) + kv
                                            + 2 * 4096 * 2)


def test_decode_is_memory_bound_on_v5e():
    ctx = [256] * 8
    least = F.decode_step_least_s(YI, ctx, 197e12, 819e9)
    assert least == pytest.approx(F.decode_step_bytes(YI, ctx) / 819e9)
    assert 0.014 < least < 0.016
