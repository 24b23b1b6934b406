"""The metrics the benchmark reads from what the ONNX executor notes of a
traced program (``ops.NOTE_FAMILIES``, published by
``OnnxFunction._record_notes``): for every metric file that names such a
family, the tiny model that reaches the family, traced on the CPU, leaves it
in the registry with the label names the metric file reads and a series of
its own program."""

import glob
import json
import os

import jax
import numpy as np
import pytest

from synapseml_tpu.models import zoo
from synapseml_tpu.observability.metrics import MetricsRegistry, get_registry
from synapseml_tpu.onnx import ops
from synapseml_tpu.onnx.importer import OnnxFunction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the family's metric name -> its label names, as the table declares them
PUBLISHED = {family.name: family.labelnames for family in (
    declare(MetricsRegistry()) for declare in ops.NOTE_FAMILIES.values())}


def _metric(path):
    with open(path) as f:
        return json.load(f)


METRICS = [m for m in map(_metric, sorted(glob.glob(
    os.path.join(ROOT, "benchmark", "metrics", "*.json"))))
    if m.get("params", {}).get("family") in PUBLISHED]
# the tiny model whose trace reaches a family
REACHES = {
    "smt_onnx_attention_lowering_total": "JambaTiny",
    "smt_onnx_selective_scan_lowering_total": "JambaTiny",
    "smt_onnx_loop_trips": "JambaTiny",
    "smt_onnx_loop_state_bytes": "JambaTiny",
    "smt_onnx_gated_delta_lowering_total": "OlmoHybridTiny",
    "smt_onnx_recurrent_state_bytes": "OlmoHybridTiny",
    "smt_onnx_sparse_attention_lowering_total": "KeyeVL2Tiny",
}


def test_the_benchmark_reads_the_families_it_is_known_to_read():
    assert {m["params"]["family"] for m in METRICS} == set(REACHES)


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_a_metric_finds_its_family_in_the_registry_after_a_trace(metric):
    family = metric["params"]["family"]
    fn = OnnxFunction(zoo.build_model_bytes(REACHES[family], seed=0),
                      dtype_policy="bfloat16")
    jax.eval_shape(fn._run_positional, np.zeros((8, 16), np.int64))
    got = get_registry().snapshot()["families"][family]
    names = got["labelnames"]
    assert tuple(names) == PUBLISHED[family] and names[0] == "fn"
    assert set(metric["params"].get("where", {})) <= set(names)
    assert any(series["labels"][0] == fn._jit.name
               for series in got["series"])
