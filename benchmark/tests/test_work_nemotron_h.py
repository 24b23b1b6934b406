"""``work/nemotron_h.py`` against counts made by hand from the published
sizes of NVIDIA-Nemotron-3-Nano-30B-A3B as ``nemotron3_nano`` cuts it."""

import pytest

import run
from benchmark.work import nemotron_h as work

CONFIG = run.load_json("configs", "nemotron3_nano.json")
S = 4096

# a token, a block (multiply-add = 2): hidden 2,688; Mamba 64 heads x 64,
# state 128, 8 groups, conv 4 over 6,144 channels, chunks of 128; attention
# 32 / 2 heads of 128; experts 1,856 wide, shared 3,712, router 128
MAMBA = (2 * 2688 * 10304 + 2 * 4096 * 2688          # in_proj, out_proj
         + 2 * 128 * 128 * 8                         # C Bᵀ a group
         + 3 * 2 * 128 * 64 * 64                     # diag, states, off
         + 2 * 32 * 32 * 4096 * 128 // S             # carry over 32 chunks
         + 2 * 6144 * 4)                             # depthwise conv
ATTENTION = (2 * 2688 * 4096 * 2 + 2 * 2 * 2688 * 256
             + 4 * S * 128 * 32 // 2)                # causal half
EXPERTS = (2 * 2688 * 128 + 2 * 2 * 2688 * 3712
           + 1.5 * 2 * 2 * 2688 * 1856)              # 6 x 32 / 128 evaluations
HEAD = 2 * 2688 * 32768


def test_flops_per_row_are_the_hand_count():
    per_token = 4 * MAMBA + 4 * EXPERTS + ATTENTION
    assert per_token == 687_013_888
    # the issue's 686 MFLOP a token (it left the carry and the conv out)
    assert abs(per_token / 686e6 - 1) < 0.005
    assert work.flops_per_row(CONFIG, {"S": S}) == S * per_token + HEAD
    by_name = {p.what: p.flops for p in work.products_per_row(CONFIG, {"S": S})}
    assert by_name["moe_routed"] == 4 * S * 1.5 * 2 * 2 * 2688 * 1856
    assert by_name["attention_scores_context"] == S * 4 * S * 128 * 32 / 2
    assert by_name["mamba_in_proj"] == 4 * S * 2 * 2688 * 10304


def test_least_seconds_leaves_the_pallas_kernels_products_out():
    peak, bandwidth, rows = 197e12, 819e9, 16
    least = work.matmul_least_seconds(CONFIG, {"S": S}, rows, peak, bandwidth)
    products = work.products_per_row(CONFIG, {"S": S})
    outside = {p.what for p in products if not p.in_matmul_class}
    assert outside == {"attention_scores_context", "moe_routed"}
    inside = sum(p.flops for p in products if p.in_matmul_class) * rows
    assert least["flops"] == pytest.approx(inside)
    assert least["flops"] < rows * work.flops_per_row(CONFIG, {"S": S})
    assert least["seconds"] == pytest.approx(
        least["compute_bound_s"] + least["bandwidth_bound_s"])
    # the projections are compute-bound, the scan's products and the conv
    # bandwidth-bound: at least the operations' time, and some over
    assert least["seconds"] >= least["flops"] / peak
    assert least["bandwidth_bound_s"] > 0 < least["compute_bound_s"]
    # weights are read once a bucket: twice the rows is under twice the bytes
    twice = work.matmul_least_seconds(CONFIG, {"S": S}, 2 * rows, peak,
                                      bandwidth)
    assert twice["bytes"] < 2 * least["bytes"]
    assert twice["flops"] == pytest.approx(2 * least["flops"])


def test_the_held_share_sets_the_expected_evaluations():
    all_held = dict(CONFIG, n_routed_experts=128)
    routed = {p.what: p.flops for p in work.products_per_row(all_held,
                                                             {"S": S})}
    assert routed["moe_routed"] == 4 * S * 6 * 2 * 2 * 2688 * 1856
