"""The FLOP and byte functions against numbers worked by hand."""

import json
import os

import pytest

from benchmark.work import encoder as work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_bert_base_flops_by_hand():
    c, s, h, f = _config("bert_base"), 512, 768, 3072
    # one layer, one row: four h x h projections, the feed-forward pair,
    # and the two attention products over all heads
    layer = 2 * s * (4 * h * h + 2 * h * f) + 4 * s * s * h
    assert layer == 8_053_063_680
    head = 2 * h * h + 2 * h * 2
    assert work.flops_per_row(c, {"S": s}) == 12 * layer + head
    assert work.flops_per_row(c, {"S": 128}) == pytest.approx(22.35e9, rel=1e-3)


def test_vit_b16_flops_by_hand():
    c, s, h, f = _config("vit_b16"), 197, 768, 3072
    assert work.seq_len(c, {}) == s
    layer = 2 * s * (4 * h * h + 2 * h * f) + 4 * s * s * h
    assert layer == 2_907_909_120
    patches = 2 * 196 * (3 * 16 * 16) * h
    head = 2 * h * 1000
    assert work.flops_per_row(c, {}) == 12 * layer + patches + head


def test_matmul_bytes_and_least_time_by_hand():
    c = _config("bert_base")
    one_layer = dict(c, num_hidden_layers=1)
    rows, s, h, f, hd = 4, 128, 768, 3072, 64
    got = work.matmul_least_seconds(one_layer, {"S": s}, rows, 197e12, 819e9)
    # bf16, each operand and result once; weights once a bucket
    proj = 4 * (rows * (s * h + s * h) + h * h)
    scores = 12 * rows * (s * hd + s * s + hd * s)
    context = 12 * rows * (s * s + s * hd + s * hd)
    ffn = rows * (s * h + s * f) + h * f + rows * (s * f + s * h) + f * h
    head = rows * (h + h) + h * h + rows * (h + 2) + h * 2
    assert got["bytes"] == 2 * (proj + scores + context + ffn + head)
    assert got["flops"] == rows * work.flops_per_row(one_layer, {"S": s})
    # the attention products move more bytes than the peak ratio allows
    # (bytes / 819e9 > flops / 197e12), the projections do not
    t_scores = 2 * scores / 819e9
    assert t_scores > 2.0 * rows * 12 * s * hd * s / 197e12
    assert got["bandwidth_bound_s"] >= t_scores
    assert got["seconds"] == pytest.approx(
        got["compute_bound_s"] + got["bandwidth_bound_s"])
