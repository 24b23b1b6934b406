"""The two readers of the program's own registry (``span_ms_per_call``,
``counter_per_call``) and the seven metrics that use them: on hand-made
snapshots, then through the whole command at toy sizes on the CPU."""

import json
import os
import statistics

import pytest

import run
from benchmark.readers import counter_per_call, span_ms_per_call

CELL = "bert_tiny.rehearsal"
SEVEN = ["host_prepare_ms", "dispatch_ms", "dispatch_overhead_ms",
         "reply_wait_ms", "transform_self_ms", "upload_mb_per_call",
         "download_mb_per_call"]
T = ["ONNXModel", "transform"]


def _families(spans: dict, counters: dict) -> dict:
    """``{(stage, method): (seconds, samples)}`` and ``{family: value}`` as a
    registry snapshot holds them, the span split over both ``cold`` values."""
    series = []
    for (stage, method), (seconds, samples) in spans.items():
        series.append({"labels": [stage, method, "1"], "sum": 0.25 * seconds,
                       "count": 0, "counts": []})
        series.append({"labels": [stage, method, "0"], "sum": 0.75 * seconds,
                       "count": samples, "counts": []})
    out = {"smt_stage_duration_seconds": {
        "type": "histogram", "labelnames": ["stage", "method", "cold"],
        "series": series}}
    for name, value in counters.items():
        out[name] = {"type": "counter", "labelnames": [],
                     "series": [{"labels": [], "value": value}]}
    return out


def _record(before, after):
    return {"families_before": _families(*before),
            "families_after": _families(*after)}


def test_span_reader_takes_the_window_only_adds_and_subtracts():
    before = ({("ONNXModel", "transform"): (1.0, 1),
               ("ONNXModel", "dispatch"): (0.5, 1),
               ("ProfiledJit", "execute"): (0.25, 1)}, {})
    after = ({("ONNXModel", "transform"): (1.0 + 4 * 0.5, 5),
              ("ONNXModel", "dispatch"): (0.5 + 8 * 0.010, 9),  # two buckets
              ("ProfiledJit", "execute"): (0.25 + 8 * 0.008, 9)}, {})
    record = _record(before, after)
    per_call = span_ms_per_call.read(
        record, {"add": [["ONNXModel", "dispatch"]], "per": T})
    assert per_call == pytest.approx(20.0)  # two 10 ms dispatches a call
    overhead = span_ms_per_call.read(
        record, {"add": [["ONNXModel", "dispatch"]],
                 "subtract": [["ProfiledJit", "execute"]], "per": T})
    assert overhead == pytest.approx(4.0)


@pytest.mark.parametrize("params", [
    {"add": [["ONNXModel", "dispatch"]], "per": T},
    {"add": [T], "subtract": [["ONNXModel", "fetch"]], "per": T},
    {"add": [T], "per": ["ONNXModel", "no_such_span"]},
])
def test_span_reader_is_silent_where_the_program_lacks_a_span(params):
    """The parent commit has ``ONNXModel.transform`` and none of the phases:
    no number, and no error."""
    record = _record(({tuple(T): (1.0, 1)}, {}), ({tuple(T): (3.0, 5)}, {}))
    assert span_ms_per_call.read(record, params) is None


def test_counter_reader_scales_and_divides_and_is_silent_without_it():
    params = {"family": "smt_onnx_upload_bytes_total", "per": T,
              "scale": 1e-6}
    before = ({tuple(T): (1.0, 1)}, {"smt_onnx_upload_bytes_total": 2e6})
    after = ({tuple(T): (3.0, 5)}, {"smt_onnx_upload_bytes_total": 10e6})
    assert counter_per_call.read(_record(before, after), params) == \
        pytest.approx(2.0)
    bare = _record(({tuple(T): (1.0, 1)}, {}), ({tuple(T): (3.0, 5)}, {}))
    assert counter_per_call.read(bare, params) is None
    no_calls = _record(before, before)
    assert counter_per_call.read(no_calls, params) is None


@pytest.fixture(scope="module")
def traced():
    """A traced run of the toy cell with every per-layer metric asked for:
    the seven list the three real cells, which the toy cell is not."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    patch = pytest.MonkeyPatch()
    patch.setattr(run, "metric_entries", lambda trace, cell: per_layer)
    try:
        return run.run_cell(CELL, seed=2_147_484_025, seconds=0.5, trace=True,
                            rehearse=True)
    finally:
        patch.undo()


def test_a_traced_run_prints_the_seven_beside_the_old(traced):
    assert traced["correct"] is True
    assert set(SEVEN) <= set(traced["metrics"])
    assert "compiles_in_window" in traced["metrics"]
    for name in SEVEN:
        assert traced["metrics"][name]["value"] >= 0, name


def test_the_phases_add_up_to_the_call(traced):
    value = {k: traced["metrics"][k]["value"] for k in SEVEN}
    total = (value["host_prepare_ms"] + value["dispatch_ms"]
             + value["reply_wait_ms"] + value["transform_self_ms"])
    call = statistics.mean(traced["call_ms"])
    # what a call holds outside ``transform`` (the driver reading the fetched
    # columns, ``log_stage_call``) is some 0.1 ms under the Python tracer:
    # 3 % of this toy's 3 ms call, 0.02 % of a real cell's
    assert total <= call
    assert call - total < 0.02 * call + 0.2
    assert 0 <= value["dispatch_overhead_ms"] <= value["dispatch_ms"]
    assert value["transform_self_ms"] < 0.5 * call


def test_the_byte_metrics_equal_the_toy_shapes(traced):
    cell = run.load_json("workloads", CELL + ".json")
    bucket, s = cell["traffic"]["bucket"], cell["traffic"]["dims"]["S"]
    config = run.load_json("configs", cell["config"] + ".json")
    hidden, labels = config["hidden_size"], config["num_labels"]
    value = {k: traced["metrics"][k]["value"] for k in SEVEN}
    assert value["upload_mb_per_call"] == pytest.approx(bucket * s * 8 / 1e6)
    assert value["download_mb_per_call"] == pytest.approx(
        bucket * (labels + hidden) * 4 / 1e6)


def test_the_seven_list_the_three_cells_and_no_others():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]][:3]
    new = bench["per_layer"][-len(SEVEN):]
    assert [m["name"] for m in new] == SEVEN
    for m in new:
        assert m["workloads"] == cells and m["moves"] == "rows_per_s"
        assert (m["better"], m["source"]) == ("lower", "program_counter")
