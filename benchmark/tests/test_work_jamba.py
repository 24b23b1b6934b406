"""``work/jamba.py`` against the arithmetic of issue 39, made by hand from
the published sizes of AI21-Jamba2-3B as ``jamba2_3b.s128_gen128`` runs it."""

import pytest

import run
from benchmark.work import jamba as work

CONFIG = run.load_json("configs", "jamba2_3b.json")
S, G, ROWS = 128, 128, 128
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

H, D, N, R, F, VOCAB = 2560, 5120, 16, 160, 8192, 65536
# a Mamba mixer's matrices: W_in, W_x, W_dt, W_out
MAMBA = H * 2 * D + D * (R + 2 * N) + R * D + D * H
# an attention mixer's: W_q, W_o, W_k, W_v (ONE key-value head of 128)
ATTENTION = 2 * H * H + 2 * H * 128
FFN = 3 * H * F
HEAD = VOCAB * H


def test_the_parameter_counts_are_the_issues():
    mixer = MAMBA + D * 4 + D + 192 + D + D * N + D  # conv, norms, b_dt, A, D
    assert mixer == 41_241_792
    assert mixer + FFN + 2 * H == 104_161_472
    assert ATTENTION == 13_762_560 and ATTENTION + FFN + 2 * H == 76_682_240
    whole = 26 * 104_161_472 + 2 * 76_682_240 + HEAD + H
    assert whole == 3_029_337_472 == work.parameters(CONFIG)
    assert abs(whole * 2 / 2 ** 30 - 5.643) < 1e-3  # 6.06 GB = 5.64 GiB
    assert CONFIG["reduced"] == [] and CONFIG["num_hidden_layers"] == 28
    assert "3,029,337,472" in CONFIG["deployment"]


def test_flops_per_row_are_the_hand_count():
    # a prompt token: 26 Mamba layers' four products, 2 attention layers'
    # projections, 28 feed-forwards: the issue's 5.72 GFLOP; then causal
    # scores and context (the mean query sees (S + 1) / 2 keys, 2 x 128
    # numbers a head each) and the tied head at the last position
    token = 26 * 2 * MAMBA + 2 * 2 * ATTENTION + 28 * 2 * FFN
    assert abs(token / 5.72e9 - 1) < 0.002
    pair = 2 * 20 * (128 + 128)
    prompt = token * S + 2 * pair * S * (S + 1) / 2 + 2 * HEAD
    assert abs(ROWS * token * S / 93.8e12 - 1) < 0.002  # 93.8 TFLOP a pass
    decode = sum(token + 2 * pair * (S + t) + 2 * HEAD for t in range(1, G))
    assert work.flops_per_row(CONFIG, {"S": S}) == pytest.approx(
        prompt + decode, rel=1e-12)
    assert work.passes_per_call(CONFIG) == 127
    # a decode pass of 128 rows: 0.78 TFLOP, 3.9 ms at the peak
    assert abs(ROWS * (token + 2 * HEAD) / 0.775e12 - 1) < 0.01


def test_a_decode_pass_streams_the_weights_and_the_state_in_and_out():
    # a row's recurrent state: 26 x (16 x 5,120 float32 + 3 x 5,120 bfloat16)
    state = 26 * (N * D * 4 + 3 * D * 2)
    assert work.recurrent_state_elements(CONFIG) * 2 == state
    assert abs(26 * N * D * 4 / 8.52e6 - 1) < 0.001  # the issue's 8.52 MB
    assert 26 * N * D * 4 * ROWS == 1_090_519_040    # the gauge in the cell
    weights = (26 * MAMBA + 2 * ATTENTION + 28 * FFN + HEAD) * 2
    assert abs(weights / 6.05e9 - 1) < 0.002
    least = work.generation_least_seconds(CONFIG, {"S": S}, ROWS, PEAKS)
    # the prompt pass is compute-bound, every decode pass bandwidth-bound
    assert least["prompt_s"] == pytest.approx(least["compute_bound_s"])
    assert least["loop_s"] == pytest.approx(least["bandwidth_bound_s"])
    assert abs(least["prompt_s"] / 0.4757 - 1) < 0.002
    # a pass: the weights once, the state in AND out, and the rest (the
    # caches' filled part, the activations' one write and read, the scan's
    # small operands): under a tenth more
    floor = (weights + 2 * state * ROWS) / PEAKS["hbm_bytes_per_s"]
    assert floor * 127 < least["loop_s"] < 1.1 * floor * 127
    assert least["seconds"] == pytest.approx(least["prompt_s"]
                                             + least["loop_s"])
    # the state is the issue's quarter of the loop's bytes (the loop is
    # bandwidth-bound: its seconds are its bytes)
    loop_bytes = least["loop_s"] * PEAKS["hbm_bytes_per_s"]
    assert 0.24 < 2 * state * ROWS * 127 / loop_bytes < 0.28


def test_the_scans_work_is_their_operands_once():
    scan = work.selective_scan_work(CONFIG, {"S": S}, ROWS, PEAKS)
    assert scan["updates"] == 26 * ROWS * S * D * N  # 1.34 G a layer
    assert abs(scan["updates"] / 26 / 1.342e9 - 1) < 0.001
    # u, delta, z in and the result out (bfloat16), B and C (float32), the
    # leaving state; A, D and the bias once a layer
    layer = ROWS * S * (4 * D * 2 + 2 * N * 4) + ROWS * N * D * 4 \
        + D * N * 4 + 2 * D * 2
    assert scan["bytes"] == 26 * layer
    assert abs(ROWS * S * 4 * D * 2 / 0.671e9 - 1) < 0.001  # the issue's 0.67
    assert scan["seconds"] == pytest.approx(26 * layer / 819e9)
    assert 0.020 < scan["seconds"] < 0.024


def test_the_kernels_are_left_out_of_the_matmul_class():
    out_of_class = {p.what for phase in work.phases(CONFIG, {"S": S})
                    for p in phase.products if not p.in_matmul_class}
    assert out_of_class == {"selective_scan", "mamba_conv",
                            "attention_scores_context"}
    # a decode pass's scores and context are XLA's dots (the grouped dense
    # form): in the class, reading the filled caches
    decode = work.phases(CONFIG, {"S": S})[1]
    scores = [p for p in decode.products
              if p.what == "attention_scores_context"]
    assert len(scores) == 2 and all(p.in_matmul_class for p in scores)
    assert scores[0].activations == 2 * 20 * 128 + (S + 1) * 2 * 128
    least = work.matmul_least_seconds(CONFIG, {"S": S}, ROWS,
                                      PEAKS["bf16_flops_per_s"],
                                      PEAKS["hbm_bytes_per_s"])
    whole = work.generation_least_seconds(CONFIG, {"S": S}, ROWS, PEAKS)
    assert least["flops"] < whole["flops"] and least["bytes"] < whole["bytes"]
    # the scan, the convolution and flash attention count no operation: the
    # difference is the prompt pass's scores and context alone
    pair = 2 * 20 * 256
    assert whole["flops"] - least["flops"] == pytest.approx(
        ROWS * 2 * pair * S * (S + 1) / 2)
