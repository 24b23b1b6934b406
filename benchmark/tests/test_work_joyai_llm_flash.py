"""``work/joyai_llm_flash.py`` against the arithmetic of issue 34, made by
hand from the published sizes of JoyAI-LLM-Flash as
``joyai_llm_flash.s4096_gen128`` runs it."""

import pytest

import run
from benchmark.work import joyai_llm_flash as work

CONFIG = run.load_json("configs", "joyai_llm_flash.json")
S, G, ROWS = 4096, 128, 16
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# a layer's attention without its norms (the issue's 26,347,520 less 2,048):
# W_dq, W_uq, W_dkv, W_uk + W_uv, W_o
ATTENTION = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 2 * 512 * 4096
             + 4096 * 2048)
DENSE = 3 * 2048 * 7168
ROUTER, SHARED, EXPERT = 2048 * 256, 3 * 2048 * 768, 3 * 2048 * 768
HEAD = 2048 * 129280


def test_the_parameter_counts_are_the_issues():
    assert ATTENTION + 2048 == 26_347_520
    assert ATTENTION + 2048 + 4096 + ROUTER + 256 + SHARED == 31_594_752
    assert ATTENTION + 2048 + 4096 + DENSE == 70_391_808
    held = 70_391_808 + 8 * (31_594_752 + 32 * EXPERT) + 2 * HEAD + 2048
    assert held == 2_060_642_304  # 4.12 GB = 3.84 GiB in bfloat16
    assert abs(held * 2 / 2 ** 30 - 3.838) < 1e-3


def test_flops_per_row_are_the_hand_count():
    # a prompt token: 9 layers' projections and causal attention (the mean
    # query sees (S + 1) / 2 keys, 192 + 128 numbers a head each), the dense
    # layer, 8 x (router, shared expert, ONE expected held pick of the 8)
    pair = 2 * 32 * (192 + 128)
    prompt = (9 * 2 * ATTENTION * S + 9 * pair * S * (S + 1) / 2
              + 2 * DENSE * S + 8 * 2 * (ROUTER + SHARED + EXPERT) * S
              + 2 * HEAD)
    assert abs(prompt / S / 1.10e9 - 1) < 0.01  # the issue's 1.10 GFLOP
    # a decode pass: the same products for one token, the absorbed scores
    # (576) and context (512) against the s + t filled latents, the head
    absorbed = 2 * 32 * (576 + 512)
    decode = sum(9 * 2 * ATTENTION + 9 * absorbed * (S + t) + 2 * DENSE
                 + 8 * 2 * (ROUTER + SHARED + EXPERT) + 2 * HEAD
                 for t in range(1, G))
    assert work.flops_per_row(CONFIG, {"S": S}) == pytest.approx(
        prompt + decode, rel=1e-12)
    assert abs(ROWS * prompt / 72e12 - 1) < 0.01  # 72 TFLOP a prompt pass
    assert work.passes_per_call(CONFIG) == 127
    kernel = work.attention_kernel_work(CONFIG, {"S": S}, ROWS)
    assert abs(kernel["flops"] / 2.75e12 - 1) < 0.01  # a layer's flash
    assert abs(9 * kernel["flops"] / 24.7e12 - 1) < 0.01


def test_a_decode_pass_streams_the_weights_it_touches_and_the_latents_once():
    # 128 picks over 256 experts reach 39 % of the held 32
    reached = work.held_experts_reached(CONFIG, ROWS * 8)
    assert abs(reached - 0.394) < 1e-3
    touched = {"attention": 9 * ATTENTION * 2, "head": HEAD * 2,
               "dense_shared": (DENSE + 8 * SHARED) * 2,
               "experts": 8 * 32 * reached * EXPERT * 2}
    for name, want in (("attention", 0.47e9), ("head", 0.53e9),
                       ("dense_shared", 0.16e9), ("experts", 0.95e9)):
        assert abs(touched[name] / want - 1) < 0.03, name
    assert abs(sum(touched.values()) / 2.1e9 - 1) < 0.02
    read = work.latent_read_work(CONFIG, {"S": S}, ROWS)
    assert abs(9 * read["bytes"] / 0.69e9 - 1) < 0.01  # 16 x 4,160 x 576
    least = work.generation_least_seconds(CONFIG, {"S": S}, ROWS, PEAKS)
    assert least["seconds"] == pytest.approx(least["prompt_s"]
                                             + least["loop_s"])
    # the prompt pass is the MXU's, every decode pass HBM's
    assert least["prompt_s"] == pytest.approx(least["compute_bound_s"])
    assert least["loop_s"] == pytest.approx(least["bandwidth_bound_s"])
    assert 0.36 < least["prompt_s"] < 0.38  # the issue's 0.37 s
    assert 3.4e-3 < least["loop_s"] / 127 < 3.5e-3  # 3.4 ms a pass
    assert least["flops"] == pytest.approx(
        ROWS * work.flops_per_row(CONFIG, {"S": S}))
    # the loop's state: one tensor of latents a layer, 0.65 GiB
    assert abs(9 * ROWS * (S + G) * 576 * 2 / 2 ** 30 - 0.6526) < 1e-3


def test_the_kernels_are_left_out_of_the_matmul_class():
    phases = work.phases(CONFIG, {"S": S})
    assert [p.what for p in phases[:2]] == ["prompt", "decode_1"]
    assert len(phases) == G and phases[-1].what == f"decode_{G - 1}"
    outside = {(ph.what.split("_")[0], p.what) for ph in phases
               for p in ph.products if not p.in_matmul_class}
    # the flash kernel holds the prompt's attention only; the grouped-product
    # kernel every pass's routed experts
    assert outside == {("prompt", "attention_scores_context"),
                       ("prompt", "moe_routed"), ("decode", "moe_routed")}
    least = work.matmul_least_seconds(CONFIG, {"S": S}, ROWS, 197e12, 819e9)
    assert least["seconds"] == pytest.approx(
        least["compute_bound_s"] + least["bandwidth_bound_s"])
    # the head's 0.53 GB is read 128 times, a layer's attention 128 times
    assert least["bytes"] > G * (HEAD + 9 * ATTENTION) * 2
    whole = work.generation_least_seconds(CONFIG, {"S": S}, ROWS, PEAKS)
    assert least["flops"] < whole["flops"]
