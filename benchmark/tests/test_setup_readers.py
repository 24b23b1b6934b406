"""The account of ``setup_s`` (ISSUE 36): the reader of the program's set-up
spans on hand-made snapshots, then all eight parts through the whole command
at toy sizes on the CPU, where they have to add up."""

import json
import os

import pytest

import run
from benchmark.readers import registry_after, setup_span_s

CELL = "bert_tiny.rehearsal"
# the seconds of a set-up, part by part; with ``compile_cache_hits``, a
# count, they are the eight metrics that move ``setup_s``
EIGHT = ["model_build_s", "graph_import_s", "place_weights_s",
         "program_trace_s", "program_compile_s", "first_call_rest_s",
         "setup_outside_program_s"]
# what this PR appended to ``per_layer`` (``place_weights_s`` is PR 27's)
NINE = [n for n in EIGHT if n != "place_weights_s"] + [
    "compile_cache_hits", "profiled_path_fallbacks",
    "program_temporaries_gib"]


def _record(spans: dict, setup_s: float = 20.0, **families) -> dict:
    """``{(stage, method, cold): (seconds, samples)}`` as a snapshot."""
    series = [{"labels": list(key), "sum": seconds, "count": samples,
               "counts": []} for key, (seconds, samples) in spans.items()]
    return {"setup_s": setup_s, "families_before": {}, "families_after": {
        "smt_stage_duration_seconds": {
            "type": "histogram", "labelnames": ["stage", "method", "cold"],
            "series": series}, **families}}


SPANS = {
    ("ModelZoo", "build_model_bytes", "0"): (3.0, 1),
    ("ONNXModel", "transform", "1"): (9.0, 1),
    ("ONNXModel", "transform", "0"): (30.0, 100),
    ("ONNXModel", "parse", "1"): (1.0, 1),
    ("ONNXModel", "register_program", "1"): (0.25, 1),
    ("ONNXModel", "place_weights", "1"): (0.5, 1),
    ("ProfiledJit", "lower", "1"): (2.0, 1),
    ("ProfiledJit", "compile", "1"): (4.0, 1),
    # a series made with its family and never sampled
    ("ProfiledJit", "compile", "0"): (0.0, 0),
}


def _read(metric: str, record: dict):
    spec = run.load_json("metrics", metric + ".json")
    reader = {"setup_span_s": setup_span_s,
              "registry_after": registry_after}[spec["reader"]]
    return reader.read(record, spec["params"])


def test_the_reader_adds_subtracts_and_starts_from_setup_s():
    record = _record(SPANS)
    assert _read("graph_import_s", record) == 1.25
    # the cold call alone, not the hundred warm ones
    assert _read("first_call_rest_s", record) == 9.0 - 7.75
    assert _read("setup_outside_program_s", record) == 20.0 - 3.0 - 9.0
    both = setup_span_s.read(record, {"add": [["ONNXModel", "transform"]]})
    assert both == 39.0  # without a third element: both values of cold


@pytest.mark.parametrize("metric,missing", [
    ("graph_import_s", ("ONNXModel", "register_program", "1")),
    ("first_call_rest_s", ("ProfiledJit", "lower", "1")),
    ("first_call_rest_s", ("ONNXModel", "transform", "1")),
    ("setup_outside_program_s", ("ModelZoo", "build_model_bytes", "0")),
])
def test_the_reader_is_silent_where_a_span_has_no_sample(metric, missing):
    """The parent commit has ``ONNXModel.transform`` and ``place_weights``
    and none of the new spans: no number, and no error. A series with no
    sample (both values of ``cold`` are made with the family) is no span."""
    spans = dict(SPANS)
    del spans[missing]
    assert _read(metric, _record(spans)) is None
    spans[missing] = (0.0, 0)
    assert _read(metric, _record(spans)) is None


def test_the_counts_read_a_healthy_zero_and_are_silent_on_the_parent():
    def counter(labelnames, series):
        return {"type": "counter", "labelnames": labelnames, "series": [
            {"labels": labels, "value": v} for labels, v in series]}

    cache, fallback = "smt_compile_cache_total", \
        "smt_profiled_jit_fallback_total"
    compiled = _record(SPANS, **{
        cache: counter(["fn", "result"], [(["onnx.a", "miss"], 1.0)]),
        fallback: counter(["fn", "why"], [])})
    assert _read("compile_cache_hits", compiled) == 0
    assert _read("profiled_path_fallbacks", compiled) == 0
    loaded = _record(SPANS, **{
        cache: counter(["fn", "result"], [(["onnx.a", "hit"], 1.0)]),
        fallback: counter(["fn", "why"],
                          [(["onnx.a", "call_refused"], 1.0)])})
    assert _read("compile_cache_hits", loaded) == 1.0
    assert _read("profiled_path_fallbacks", loaded) == 1.0
    parent = _record(SPANS)
    for metric in ("compile_cache_hits", "profiled_path_fallbacks",
                   "program_temporaries_gib"):
        assert _read(metric, parent) is None
    held = _record(SPANS, smt_program_memory_bytes={
        "type": "gauge", "labelnames": ["fn", "kind"], "series": [
            {"labels": ["onnx.a", "arguments"], "value": 2.0 ** 33},
            {"labels": ["onnx.a", "temporaries"], "value": 3.5 * 2 ** 30}]})
    assert _read("program_temporaries_gib", held) == 3.5


@pytest.fixture(scope="module")
def traced():
    """A traced run of the toy cell with every per-layer metric asked for
    (the toy cell is in no metric's ``workloads``), and ``setup_s`` itself,
    which a traced run's line leaves out, beside them. A process of the
    real command runs one set-up; this one has run other tests', so the run
    gets a registry of its own, and a toy program that an earlier test left
    behind is collected first (a live one would be shared, not loaded)."""
    import gc

    from synapseml_tpu.observability.metrics import (MetricsRegistry,
                                                     set_registry)

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        asked = json.load(f)["per_layer"] + [{"name": "setup_s"}]
    gc.collect()
    patch = pytest.MonkeyPatch()
    patch.setattr(run, "metric_entries", lambda trace, cell: asked)
    previous = set_registry(MetricsRegistry())
    try:
        return run.run_cell(CELL, seed=2_147_484_061, seconds=0.5, trace=True,
                            rehearse=True)
    finally:
        set_registry(previous)
        patch.undo()


def test_a_traced_run_prints_the_whole_account(traced):
    assert traced["correct"] is True
    assert set(EIGHT) | set(NINE) <= set(traced["metrics"])
    for name in EIGHT:
        assert traced["metrics"][name]["value"] >= 0, name
        assert traced["metrics"][name]["unit"] == "s"
    assert traced["metrics"]["profiled_path_fallbacks"]["value"] == 0
    assert traced["metrics"]["compiles_in_window"]["value"] == 0
    assert traced["metrics"]["compile_cache_hits"]["value"] >= 0


def test_the_eight_add_up_to_setup_s(traced):
    """By construction (each span is added once and subtracted once), so to
    rounding; what the account is WORTH is how small the two remainders are."""
    parts = [traced["metrics"][k]["value"] for k in EIGHT]
    assert sum(parts) == pytest.approx(traced["metrics"]["setup_s"]["value"],
                                       abs=1e-6)


def test_the_nine_list_every_cell_and_move_what_the_issue_says():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NINE:
        assert by_name[name]["workloads"] == cells, name
        spec = run.load_json("metrics", name + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == by_name[name][key], (name, key)
    assert [m["name"] for m in bench["per_layer"][-len(NINE):]] == [
        "model_build_s", "graph_import_s", "program_trace_s",
        "program_compile_s", "compile_cache_hits", "first_call_rest_s",
        "setup_outside_program_s", "profiled_path_fallbacks",
        "program_temporaries_gib"]
    assert {by_name[n]["moves"] for n in NINE[:7]} == {"setup_s"}
    assert by_name["profiled_path_fallbacks"]["moves"] == "call_p90_ms"
    assert by_name["program_temporaries_gib"]["moves"] == "rows_per_s"
