"""The reader of what the program set before the window: the value after."""

import run
from benchmark.readers import registry_after

FAMILIES = {
    "smt_onnx_weight_argument_bytes": {
        "labelnames": ["fn"], "series": [{"labels": ["onnx.a"],
                                          "value": 3 * 2 ** 30}]},
    "smt_onnx_attention_lowering_total": {
        "labelnames": ["fn", "kind"],
        "series": [{"labels": ["onnx.a", "flash"], "value": 1.0}]},
    "smt_stage_duration_seconds": {
        "labelnames": ["stage", "method", "cold"],
        "series": [{"labels": ["ONNXModel", "place_weights", "0"],
                    "sum": 1.25, "count": 1},
                   {"labels": ["ONNXModel", "transform", "0"],
                    "sum": 9.0, "count": 30}]},
}


def _read(metric, families):
    spec = run.load_json("metrics", metric + ".json")
    return registry_after.read({"families_after": families,
                                "families_before": {}}, spec["params"])


def test_the_three_metrics_read_the_value_after_the_window():
    assert _read("weight_argument_gib", FAMILIES) == 3.0
    assert _read("place_weights_s", FAMILIES) == 1.25
    # the kernel ran everywhere: the dense series is absent, and reads 0
    assert _read("attention_dense_lowerings", FAMILIES) == 0
    dense = dict(FAMILIES, smt_onnx_attention_lowering_total={
        "labelnames": ["fn", "kind"],
        "series": [{"labels": ["onnx.a", "dense"], "value": 2.0}]})
    assert _read("attention_dense_lowerings", dense) == 2.0


def test_a_program_without_the_gauge_the_counter_or_the_span_is_silent():
    parent = {"smt_stage_duration_seconds": {
        "labelnames": ["stage", "method", "cold"],
        "series": [{"labels": ["ONNXModel", "transform", "0"],
                    "sum": 9.0, "count": 30}]}}
    for metric in ("weight_argument_gib", "place_weights_s",
                   "attention_dense_lowerings"):
        assert _read(metric, parent) is None
