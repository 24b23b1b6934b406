"""``work/keye_vl2.py`` against counts made by hand from the published sizes
of Keye-VL-2.0-30B-A3B's language model as ``keye_vl2_30b_a3b.s65536_gen128``
runs it."""

import pytest

import run
from benchmark.work import keye_vl2 as work

CONFIG = run.load_json("configs", "keye_vl2_30b_a3b.json")
S, G, LAYERS, TOPK = 65536, 128, 4, 2048
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# a token and layer (multiply-add = 2): hidden 2,048; 32 / 4 heads of 128;
# the indexer's 16 heads of 64, one key head of 64, 16 weights; router 128;
# 8 of 128 experts, three products of 2,048 x 768 each
PROJECTIONS = 2 * (2048 * 4096 * 2 + 2 * 2048 * 512)
INDEX_PROJECTIONS = 2 * 2048 * (1024 + 64 + 16)
ROUTER = 2 * 2048 * 128
EXPERTS = 8 * 3 * 2 * 2048 * 768
SCORED = 16 * (2 * 64 + 2)  # an index head's dot, weight and sum, a pair
CHOSEN = 4 * 128 * 32  # scores and context, a chosen (query, key) pair
HEAD = 2 * 2048 * 151936


def test_the_configuration_counts_the_published_model():
    """The deployment note's parameters: the cell's four layers with both
    ends, and the whole model's 48."""
    assert work.parameters(CONFIG) == 3_123_858_944
    assert work.parameters(dict(CONFIG, num_hidden_layers=48)) \
        == 30_640_656_384
    # keys and values 2 x 4 x 128 and one index key of 64, a layer
    assert work.cache_row_elements(CONFIG) == LAYERS * (2 * 4 * 128 + 64)
    assert work.passes_per_call(CONFIG) == G - 1


def test_flops_per_row_are_the_hand_count():
    token_layer = PROJECTIONS + INDEX_PROJECTIONS + ROUTER + EXPERTS
    prompt_chosen = TOPK * (TOPK + 1) // 2 + (S - TOPK) * TOPK
    assert work.chosen_pairs(TOPK, 0, S) == prompt_chosen
    assert work.chosen_pairs(TOPK, 10, 20) == sum(range(11, 21))
    assert work.chosen_pairs(TOPK, 2040, 2060) == sum(
        min(t + 1, TOPK) for t in range(2040, 2060))
    decode_seen = sum(S + t for t in range(1, G))
    want = (LAYERS * (token_layer * (S + G - 1)
                      + SCORED * (S * (S + 1) // 2 + decode_seen)
                      + CHOSEN * (prompt_chosen + (G - 1) * TOPK))
            + G * HEAD)
    assert work.flops_per_row(CONFIG, {"S": S}) == pytest.approx(want,
                                                                 rel=1e-12)
    # a layer of the 64K prompt: the indexer over the causal pairs some
    # 4.4 TFLOP, attention over the chosen keys some 2.2
    assert SCORED * S * (S + 1) / 2 / 1e12 == pytest.approx(4.47, abs=0.01)
    assert CHOSEN * prompt_chosen / 1e12 == pytest.approx(2.17, abs=0.01)


def test_least_seconds_leave_every_kernel_out():
    least = work.matmul_least_seconds(CONFIG, {"S": S}, 1, 197e12, 819e9)
    phases = work.phases(CONFIG, {"S": S})
    assert [p.what for p in phases[:2]] == ["prompt", "decode_1"]
    assert len(phases) == G
    outside = {p.what for ph in phases for p in ph.products
               if not p.in_matmul_class}
    # the indexer's kernel, the two attention kernels (one a pass's kind)
    # and the grouped product: custom calls, outside is_matmul's class
    assert outside == {"index_scores", "selected_attention", "moe_routed"}
    # at one row a call a product of one row (every decode pass's, the
    # head) is a multiply-reduce loop fusion, outside the class too
    inside = LAYERS * (PROJECTIONS + INDEX_PROJECTIONS + ROUTER) * S
    assert least["flops"] == pytest.approx(inside)
    assert least["seconds"] == pytest.approx(
        least["compute_bound_s"] + least["bandwidth_bound_s"])
    two = work.matmul_least_seconds(CONFIG, {"S": S}, 2, 197e12, 819e9)
    assert two["flops"] == pytest.approx(2 * (
        LAYERS * (PROJECTIONS + INDEX_PROJECTIONS + ROUTER) * (S + G - 1)
        + G * HEAD))
    assert two["seconds"] == pytest.approx(
        two["compute_bound_s"] + two["bandwidth_bound_s"])


def test_generation_roofline_and_the_kernels_work():
    least = work.generation_least_seconds(CONFIG, {"S": S}, 1, PEAKS)
    assert least["seconds"] == pytest.approx(least["prompt_s"]
                                             + least["loop_s"])
    # the prompt pass is the MXU's (57 TFLOP a call, nearly all of it)
    assert least["prompt_s"] == pytest.approx(least["compute_bound_s"])
    assert least["loop_s"] == pytest.approx(least["bandwidth_bound_s"])
    assert least["flops"] == pytest.approx(
        work.flops_per_row(CONFIG, {"S": S}))
    # a decode pass reads the head (0.62 GB), the experts 8 picks reach,
    # the index keys it scores and 2,048 rows of keys and values a layer
    index = work.indexer_work(CONFIG, {"S": S}, 1, PEAKS, "decode")
    attention = work.selected_attention_work(CONFIG, {"S": S}, 1, PEAKS,
                                             "decode")
    assert index["bytes"] == pytest.approx(2 * LAYERS * sum(
        (16 * 64 + 16 * 2) + (S + t) * 64 for t in range(1, G)))
    assert attention["bytes"] == pytest.approx(2 * LAYERS * (G - 1) * (
        2 * 32 * 128 + TOPK * 2 * 4 * 128))
    prompt = work.selected_attention_work(CONFIG, {"S": S}, 1, PEAKS)
    # the blockwise form computes every causal pair of every head: 16 times
    # the chosen pairs' work at 64K
    assert prompt["computed_flops"] / prompt["flops"] == pytest.approx(
        S * (S + 1) / 2 / (TOPK * (TOPK + 1) / 2 + (S - TOPK) * TOPK))
    assert 15 < prompt["computed_flops"] / prompt["flops"] < 17
