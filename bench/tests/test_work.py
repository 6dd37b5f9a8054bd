"""Operation and byte counts against hand-worked shapes."""
import json

import helpers  # noqa: F401  (puts bench/ on the path)
from harness import refnet, work

CFG = json.loads((helpers.BENCH / "configs" / "theta-mlp.json").read_text())
ATTN = json.loads((helpers.BENCH / "configs" / "theta-attn.json").read_text())
V5E = json.loads((helpers.BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def test_first_dense_layer_of_the_grid():
    # 40 rows x 11410 @ 11410 x 4000: 2*40*11410*4000 FLOPs; reads x, W, b
    # and writes y once at the stated bfloat16, 2 bytes.
    assert CFG["dtype"] == "bfloat16" and work.itemsize(CFG) == 2
    flops, nbytes = work.dense_cost(40, 11410, 4000, work.itemsize(CFG))
    assert flops == 3_651_200_000
    assert nbytes == 2 * (40 * 11410 + 11410 * 4000 + 4000 + 40 * 4000) == 92_520_800
    # Bandwidth bound on a v5e: 92.5 MB at 819 GB/s, not 3.65 GFLOP at 197 TFLOP/s.
    t = work.least_time(flops, nbytes, V5E)
    assert abs(t - 92_520_800 / 819e9) < 1e-15
    assert t > flops / 197e12
    # The same layer at float32 moves twice the bytes.
    assert work.dense_cost(40, 11410, 4000, 4)[1] == 185_041_600
    tiny = json.loads((helpers.BENCH / "tests" / "data" / "tiny-mlp.json").read_text())
    assert work.itemsize(tiny) == 4


def test_window_pack_at_grid_shape():
    # (N, J, K, F) = (40, 512, 10, 4): read mask 40*512 and features
    # 40*512*4, write features 40*10*4 and indices 40*10 at 4 bytes, and a
    # one-byte valid mask 40*10.
    assert work.window_pack_bytes(40, 512, 10, 4) == 4 * (20480 + 81920 + 1600 + 400) + 400
    assert work.window_pack_bytes(40, 512, 128, 4) == 4 * (20480 + 81920 + 20480 + 5120) + 5120


def test_policy_shapes_follow_the_paper():
    assert refnet.state_dim(CFG) == 11410 == CFG["state_dim"]
    assert refnet.state_dim(ATTN) == 128 * 4 + 1 + 4 == ATTN["state_dim"]
    layers = refnet.dense_layers(CFG, 40)
    assert (40, 11410, 4000) in layers and (40, 4000, 1000) in layers
    assert (40, 1000, 512) in layers
    # Action stream: 768 -> 512 -> W*T*M = 10*6*2.
    assert (40, 512, 120) in layers


def test_flops_per_decision_row():
    mlp = sum(2 * k * n for _, k, n in refnet.dense_layers(CFG, 1))
    assert work.flops_per_row(CFG) == mlp
    assert mlp > 2 * 11410 * 4000
    # Attention: 2 layers of QK^T and PV over 129 tokens, 4 heads of 16.
    dense = sum(2 * m * k * n for m, k, n in refnet.dense_layers(ATTN, 1))
    assert work.flops_per_row(ATTN) == dense + 2 * 4 * 4 * 129 * 129 * 16


def test_attention_cost():
    flops, nbytes = work.attention_cost(*work.attention_shape(ATTN, 40),
                                        work.itemsize(ATTN))
    assert flops == 4 * 160 * 129 * 129 * 16
    assert nbytes == 2 * 4 * 160 * 129 * 16
