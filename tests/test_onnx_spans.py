"""What ``ONNXModel.transform`` records about itself (ISSUE 25): phase spans
in the registry and in a profiler capture, byte and row counters at the
host/device boundary, ONNX node names on the ops it stages.

One table of two and a half buckets (20 rows at ``batch_size`` 8) of the
zoo's BERTTiny at S=16, fetching ``logits`` [N,2] and ``pooled`` [N,128] and
leaving ``sequence`` [N,16,128] on the device, so every count below follows
from the shapes.
"""

import glob
import os

import jax
import numpy as np
import pytest

from synapseml_tpu.core import Table
from synapseml_tpu.models.zoo import build_model_bytes
from synapseml_tpu.observability import profiling, spans
from synapseml_tpu.observability.metrics import MetricsRegistry, set_registry
from synapseml_tpu.onnx import ONNXModel
from synapseml_tpu.onnx.importer import OnnxFunction

ROWS, BUCKET, S = 20, 8, 16
BUCKETS = 3  # 8 + 8 + 4 padded by 4
PHASES = ("gather", "pad", "dispatch", "fetch", "assemble")


@pytest.fixture(scope="module")
def model_bytes():
    return build_model_bytes("BERTTiny", seed=0)


@pytest.fixture(scope="module")
def table():
    ids = np.random.default_rng(0).integers(0, 1000, (ROWS, S))
    return Table({"input_ids": ids.astype(np.int64)})


@pytest.fixture(scope="module")
def model(model_bytes, table):
    m = ONNXModel(model_bytes=model_bytes,
                  feed_dict={"input_ids": "input_ids"},
                  fetch_dict={"logits": "logits", "pooled": "pooled"},
                  batch_size=BUCKET, dtype_policy="bfloat16")
    m.transform(table)  # the compile
    return m


@pytest.fixture
def fresh_registry():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


@pytest.fixture(scope="module")
def one_call(model, table):
    """The registry after one warm ``transform`` of the table."""
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        out = model.transform(table)
        assert out["logits"].shape == (ROWS, 2)
        return reg.snapshot()["families"]
    finally:
        set_registry(prev)


def _samples(families, stage, method, cold=None):
    """Samples of one span, under one value of ``cold`` or under both."""
    fam = families.get("smt_stage_duration_seconds", {"series": []})
    return sum(s["count"] for s in fam["series"]
               if s["labels"][:2] == [stage, method]
               and cold in (None, s["labels"][2]))


# what runs once a model (``parse``, ``place_weights``,
# ``register_program``) or once a program (``lower``, ``compile``): ISSUE 36
SET_UP = (("ONNXModel", "parse"), ("ONNXModel", "place_weights"),
          ("ONNXModel", "register_program"), ("ProfiledJit", "lower"),
          ("ProfiledJit", "compile"))


@pytest.mark.parametrize("stage,method,expected", [
    ("ONNXModel", "transform", 1),
    ("ONNXModel", "gather", 1),
    ("ONNXModel", "pad", BUCKETS),
    ("ONNXModel", "dispatch", BUCKETS),
    ("ProfiledJit", "execute", BUCKETS),
    ("ONNXModel", "fetch", BUCKETS),
    ("ONNXModel", "assemble", 1),
    *[(stage, method, 0) for stage, method in SET_UP],  # a warm call: none
])
def test_each_span_samples_once_a_bucket_or_once_a_call(one_call, stage,
                                                        method, expected):
    assert _samples(one_call, stage, method) == expected
    assert _samples(one_call, stage, method, "1") == 0  # all of it warm


@pytest.fixture(scope="module")
def first_calls(table):
    """Snapshots of a registry of its own after: the first ``transform`` of
    a fresh model of a graph no other test compiles (three classes), its
    second, and the first of a second model of the same graph (another
    seed: the two share one ``_SharedProgram``)."""
    def fresh(seed):
        return ONNXModel(
            model_bytes=build_model_bytes("BERTTiny", seed=seed,
                                          num_classes=3),
            feed_dict={"input_ids": "input_ids"},
            fetch_dict={"logits": "logits", "pooled": "pooled"},
            batch_size=BUCKET, dtype_policy="bfloat16")

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        models, snaps = [], []
        for seed in (1, None, 2):  # None: the first model again
            models.append(models[0] if seed is None else fresh(seed))
            assert models[-1].transform(table)["logits"].shape == (ROWS, 3)
            snaps.append(reg.snapshot()["families"])
        assert models[2].fn._program is models[0].fn._program
        return snaps
    finally:
        set_registry(prev)


@pytest.mark.parametrize("stage,method,first,second,second_model", [
    ("ModelZoo", "build_model_bytes", 1, 0, 1),
    ("ONNXModel", "parse", 1, 0, 1),
    ("ONNXModel", "place_weights", 1, 0, 1),
    ("ONNXModel", "register_program", 1, 0, 1),
    ("ProfiledJit", "lower", 1, 0, 0),
    ("ProfiledJit", "compile", 1, 0, 0),
    ("ONNXModel", "transform", 1, 1, 1),
    ("ONNXModel", "gather", 1, 1, 1),
    ("ONNXModel", "pad", BUCKETS, BUCKETS, BUCKETS),
    ("ONNXModel", "dispatch", BUCKETS, BUCKETS, BUCKETS),
    ("ProfiledJit", "execute", BUCKETS, BUCKETS, BUCKETS),
    ("ONNXModel", "fetch", BUCKETS, BUCKETS, BUCKETS),
    ("ONNXModel", "assemble", 1, 1, 1),
])
def test_a_first_call_is_cold_with_its_phases_and_holds_the_set_up(
        first_calls, stage, method, first, second, second_model):
    """What each of three calls ADDS: a model's first call under
    ``cold="1"`` with every phase inside it, its second under ``cold="0"``
    and nothing of a set-up, a second model of the same graph its own import
    and upload and no second load of the program."""
    a, b, c = first_calls
    # the model file is built outside every stage span: never cold
    cold = "0" if stage == "ModelZoo" else "1"
    other = "1" if cold == "0" else "0"
    assert _samples(a, stage, method, cold) == first
    assert _samples(a, stage, method, other) == 0
    assert _samples(b, stage, method, "1") == _samples(a, stage, method, "1")
    assert _samples(b, stage, method, "0") - _samples(a, stage, method, "0") \
        == second
    assert _samples(c, stage, method, cold) - _samples(b, stage, method, cold) \
        == second_model
    assert _samples(c, stage, method, other) == _samples(b, stage, method,
                                                         other)


def test_the_first_calls_phases_tile_it(first_calls):
    """The cold series are one account: the five phases of the first call,
    and inside ``gather`` and ``dispatch`` the five of the set-up, leave of
    the call only what lies between spans."""
    dur = {tuple(s["labels"]): s["sum"] for s in
           first_calls[0]["smt_stage_duration_seconds"]["series"]}
    call = dur[("ONNXModel", "transform", "1")]
    phases = sum(dur[("ONNXModel", p, "1")] for p in PHASES)
    assert phases <= call and call - phases < 0.05
    set_up = sum(dur[(*pair, "1")] for pair in SET_UP)
    inside = dur[("ONNXModel", "gather", "1")] + \
        dur[("ONNXModel", "dispatch", "1")]
    assert set_up <= inside


@pytest.mark.parametrize("family,expected", [
    ("smt_onnx_padded_rows_total", BUCKETS * BUCKET - ROWS),
    # int64 ids as the host holds them, padding included
    ("smt_onnx_upload_bytes_total", BUCKETS * BUCKET * S * 8),
    ("smt_onnx_download_bytes_total", BUCKETS * BUCKET * (2 + 128) * 4),
    ("smt_onnx_unfetched_output_bytes_total", BUCKETS * BUCKET * S * 128 * 4),
])
def test_counters_equal_what_the_shapes_give(one_call, family, expected):
    assert [s["value"] for s in one_call[family]["series"]] == [expected]


def test_phase_spans_count_the_rows_they_handled(one_call):
    rows = {tuple(s["labels"]): s["value"]
            for s in one_call["smt_stage_rows_total"]["series"]}
    assert rows[("ONNXModel", "gather")] == ROWS
    assert rows[("ONNXModel", "pad")] == ROWS              # rows sliced
    assert rows[("ONNXModel", "dispatch")] == BUCKETS * BUCKET  # rows sent
    assert rows[("ONNXModel", "fetch")] == ROWS
    assert rows[("ONNXModel", "transform")] == ROWS


def _caller_line(trace_dir):
    """[(name, start, end)] of the host line that holds the annotations."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, "the capture wrote no trace"
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            if any(name.startswith("smt.") for name, _, _ in events):
                yield events


def test_a_profile_holds_the_phases_inside_transform_on_one_line(
        model, table, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        model.transform(table)
    lines = list(_caller_line(str(tmp_path)))
    assert len(lines) == 1, "all of one call's spans are on the caller's line"
    smt = [e for e in lines[0] if e[0].startswith("smt.")]
    (_, lo, hi), = [e for e in smt if e[0] == "smt.ONNXModel.transform"]
    inside = [name for name, a, b in smt if lo <= a and b <= hi]
    for phase in PHASES:
        want = 1 if phase in ("gather", "assemble") else BUCKETS
        assert inside.count(f"smt.ONNXModel.{phase}") == want, phase
    # the runtime's part of a dispatch lies inside the dispatch it is part of
    dispatches = [(a, b) for n, a, b in smt if n == "smt.ONNXModel.dispatch"]
    executes = [(a, b) for n, a, b in smt if n == "smt.ProfiledJit.execute"]
    assert len(executes) == BUCKETS
    for (a, b), (lo_d, hi_d) in zip(executes, dispatches):
        assert lo_d <= a and b <= hi_d


def test_spans_disable_leaves_no_annotation_and_no_sample(
        model, table, fresh_registry, tmp_path):
    spans.disable()
    try:
        with jax.profiler.trace(str(tmp_path)):
            model.transform(table)
    finally:
        spans.enable()
    assert list(_caller_line(str(tmp_path))) == []
    families = fresh_registry.snapshot()["families"]
    assert "smt_stage_duration_seconds" not in families
    assert not [name for name in families if name.startswith("smt_onnx_")]


def test_lowered_program_names_ops_by_onnx_node(model_bytes):
    fn = OnnxFunction(model_bytes, dtype_policy="bfloat16")
    text = jax.jit(fn._run_positional).lower(
        np.zeros((BUCKET, S), np.int64)).as_text(debug_info=True)
    # <op_type>.<node name>, under the program's own name (which the
    # benchmark finds its module by)
    assert "jit(_run_positional)/MatMul.MatMul_l1_f0/dot_general" in text
    assert "/Softmax.Softmax_l0_att_" in text
    assert "/Gather." in text and "/LayerNormalization." in text


@pytest.mark.parametrize("policy,form", [
    ("bfloat16", "erf_float32"), ("float32", "erfc")])
def test_the_trace_says_which_form_each_gelu_node_took(
        model_bytes, fresh_registry, policy, form):
    """BERTTiny's two layers hold one ``Gelu`` each: counted once a traced
    program by the form the input's type chose and by no other, nothing more
    on a second call of the same program."""
    fn = OnnxFunction(model_bytes, dtype_policy=policy)
    ids = np.zeros((3, 24), np.int64)  # a shape no other test compiled

    def counted():
        family = fresh_registry.snapshot()["families"].get(
            "smt_onnx_gelu_lowering_total", {"series": []})
        return {tuple(s["labels"]): s["value"] for s in family["series"]}

    fn({"input_ids": ids})
    assert counted() == {(fn._jit.name, form): 2}
    fn({"input_ids": ids})
    assert counted() == {(fn._jit.name, form): 2}


def test_phase_spans_leave_device_memory_alone(model, table, fresh_registry,
                                               monkeypatch):
    """On a backend with allocator statistics (here: faked) only the stage
    span sweeps them; the six spans inside it make no sweep and no
    ``smt_stage_hbm_*`` series."""
    sweeps = []

    def fake_memory_stats():
        sweeps.append(1)
        return [("tpu:0", {"bytes_in_use": 10, "peak_bytes_in_use": 20})]

    state = profiling._DeviceState()
    state.devices, state.has_memory_stats = [object()], True
    monkeypatch.setattr(profiling, "_DEV", state)
    monkeypatch.setattr(profiling, "memory_stats", fake_memory_stats)
    model.transform(table)
    assert len(sweeps) == 1
    families = fresh_registry.snapshot()["families"]
    for name in ("smt_stage_hbm_live_bytes", "smt_stage_hbm_peak_bytes"):
        assert [s["labels"] for s in families[name]["series"]] == \
            [["ONNXModel", "transform"]]
    assert [s["labels"] for s in families["smt_stage_flops_total"]["series"]] \
        == [["ONNXModel", "transform"]]
