"""``work/sdar_moe.py`` against counts made by hand from the published sizes
of SDAR-30B-A3B-Chat as ``sdar_30b_a3b.gen64`` runs it."""

import pytest

import run
from benchmark.work import sdar_moe as work

CONFIG = run.load_json("configs", "sdar_30b_a3b.json")
S, G, B, T, ROWS = 256, 64, 4, 2, 128
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# a token and layer (multiply-add = 2): hidden 2,048; 32 / 4 heads of 128;
# router 128; 8 of 128 experts, three products of 2,048 x 768 each
PROJECTIONS = 2 * (2048 * 4096 * 2 + 2 * 2048 * 512)
ROUTER = 2 * 2048 * 128
EXPERTS = 8 * 3 * 2 * 2048 * 768
PAIR = 4 * 128 * 32  # scores and context, a visible (query, key) pair
HEAD = 2 * 2048 * 151936


def test_flops_per_row_are_the_hand_count():
    token_layer = PROJECTIONS + ROUTER + EXPERTS
    assert token_layer == 113_770_496  # the issue's 113.8 MFLOP
    # query i of the prompt sees the (i // 4 + 1) * 4 positions through its
    # block; a block's 4 queries see S + 4 (b + 1) positions, in 3 passes
    prompt_pairs = sum((i // B + 1) * B for i in range(S))
    assert prompt_pairs == S * S // 2 + S * B // 2
    loop_pairs = sum((T + 1) * B * (S + B * (b + 1)) for b in range(G // B))
    want = (6 * token_layer * (S + (T + 1) * G)
            + 6 * PAIR * (prompt_pairs + loop_pairs) + T * G * HEAD)
    assert work.flops_per_row(CONFIG, {"S": S}) == want
    assert abs(ROWS * want / 50.46e12 - 1) < 1e-3  # 50 TFLOP a call
    assert work.passes_per_call(CONFIG) == 48


def test_least_seconds_count_weights_once_a_pass_and_leave_the_kernels_out():
    least = work.matmul_least_seconds(CONFIG, {"S": S}, ROWS, 197e12, 819e9)
    phases = work.phases(CONFIG, {"S": S})
    assert [p.what for p in phases[:3]] == ["prompt", "denoise_0", "commit_0"]
    assert sum(p.times for p in phases) == 1 + 48
    outside = {(ph.what.split("_")[0], p.what) for ph in phases
               for p in ph.products if not p.in_matmul_class}
    # the flash kernel holds the prompt's attention only; the grouped-product
    # kernel every pass's experts
    assert outside == {("prompt", "attention_scores_context"),
                       ("prompt", "moe_routed"), ("denoise", "moe_routed"),
                       ("commit", "moe_routed")}
    inside = (6 * (PROJECTIONS + ROUTER) * (S + (T + 1) * G) + T * G * HEAD
              + 6 * PAIR * sum((T + 1) * B * (S + B * (b + 1))
                               for b in range(G // B))) * ROWS
    assert least["flops"] == pytest.approx(inside)
    # the head's 0.62 GB is read 32 times, a layer's projections 49 times
    head_bytes = 2048 * 151936 * 2 * T * (G // B)
    assert least["bytes"] > head_bytes + 49 * 6 * 18_874_368 * 2
    assert least["seconds"] == pytest.approx(
        least["compute_bound_s"] + least["bandwidth_bound_s"])


def test_generation_roofline_is_set_by_streaming_weights():
    least = work.generation_least_seconds(CONFIG, {"S": S}, ROWS, PEAKS)
    assert least["seconds"] == pytest.approx(least["prompt_s"]
                                             + least["loop_s"])
    # the prompt's pass is the MXU's (22.7 TFLOP), every later pass HBM's:
    # six layers' experts, 7.25 GB, are 8.85 ms of a pass
    assert least["prompt_s"] == pytest.approx(least["compute_bound_s"])
    assert least["loop_s"] == pytest.approx(least["bandwidth_bound_s"])
    assert 0.11 < least["prompt_s"] < 0.12
    expert_bytes = 6 * 128 * 3 * 2048 * 768 * 2
    assert least["loop_s"] > 48 * expert_bytes / PEAKS["hbm_bytes_per_s"]
    assert 0.50 < least["loop_s"] < 0.56
    assert least["flops"] == pytest.approx(
        ROWS * work.flops_per_row(CONFIG, {"S": S}))
    # a pass of 512 tokens x 8 picks reaches every expert; a bucket of one
    # row (32 pairs) a quarter of them, and reads that share of the weights
    assert work.experts_touched(CONFIG, 4096) > 127.9
    assert 27 < work.experts_touched(CONFIG, 32) < 29
    one = work.generation_least_seconds(CONFIG, {"S": S}, 1, PEAKS)
    assert one["loop_s"] < 0.5 * least["loop_s"]
