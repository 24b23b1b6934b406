"""``nemotron_h``'s plain reference at toy sizes: it agrees with the program's
float32 executor, its control and three planted faults of the program come
out not correct on the rehearsal cell, and it reads the file's BFLOAT16
weights as the program's own parser does."""

import numpy as np
import pytest

import run
from benchmark.reference import nemotron_h
from benchmark.reference.onnx_initializers import read_initializers

CELL = "nemotron3_nano_tiny.rehearsal"
CONFIG = run.load_json("configs", "nemotron3_nano_tiny.json")


def _model_bytes(**kw):
    from synapseml_tpu.models.zoo import build_model_bytes

    return build_model_bytes(CONFIG["builder"], **{**CONFIG["builder_kwargs"],
                                                   **kw})


@pytest.mark.parametrize("length", [8, 24], ids=["one_chunk", "three_chunks"])
def test_reference_agrees_with_the_float32_executor(length):
    import jax
    from synapseml_tpu.onnx.importer import OnnxFunction

    model_bytes = _model_bytes(seed=7)
    feeds = {"input_ids": np.random.default_rng(3).integers(
        0, CONFIG["vocab_size"], (5, length))}
    ref = nemotron_h.Reference(CONFIG, read_initializers(model_bytes))
    with jax.default_matmul_precision("highest"):
        want = OnnxFunction(model_bytes, dtype_policy="float32")(feeds)
    got = ref.forward_blocks(feeds, block_rows=5)
    for name in ("logits", "pooled"):
        np.testing.assert_allclose(got[name], np.asarray(want[name]),
                                   rtol=2e-4, atol=2e-5)
    blocks = ref.forward_blocks(feeds, block_rows=1)
    np.testing.assert_allclose(blocks["logits"], got["logits"], rtol=1e-5,
                               atol=1e-6)


def test_a_share_of_the_experts_is_read_from_where_it_starts():
    """Experts 4-7 of 8 under the 8-wide router: the reference takes the
    share's first expert from the configuration, as the graph's node does."""
    import jax
    from synapseml_tpu.onnx.importer import OnnxFunction

    share = {"experts_held": 4, "first_expert": 4, "seed": 9}
    model_bytes = _model_bytes(**share)
    config = dict(CONFIG, builder_kwargs=share)
    feeds = {"input_ids": np.random.default_rng(4).integers(
        0, CONFIG["vocab_size"], (3, 16))}
    with jax.default_matmul_precision("highest"):
        want = OnnxFunction(model_bytes, dtype_policy="float32")(feeds)
    got = nemotron_h.Reference(config, read_initializers(model_bytes)
                               ).forward_blocks(feeds, 3)
    np.testing.assert_allclose(got["pooled"], np.asarray(want["pooled"]),
                               rtol=2e-4, atol=2e-5)
    elsewhere = nemotron_h.Reference(CONFIG, read_initializers(model_bytes)
                                     ).forward_blocks(feeds, 3)
    assert np.abs(elsewhere["pooled"] - got["pooled"]).max() > 1e-2


def test_lower_precisions_move_the_answer_in_order():
    model_bytes = _model_bytes(seed=7)
    feeds = {"input_ids": np.random.default_rng(5).integers(
        0, CONFIG["vocab_size"], (4, 24))}
    ref = nemotron_h.Reference(CONFIG, read_initializers(model_bytes))
    exact = ref.forward(feeds)["logits"]
    err = {p: float(np.linalg.norm(ref.forward(feeds, p)["logits"] - exact)
                    / np.linalg.norm(exact)) for p in ("bfloat16", "float8")}
    assert 0 < err["bfloat16"] < err["float8"] / 4
    with pytest.raises(ValueError):
        ref.forward(feeds, "int4")


def test_bfloat16_initializers_match_the_programs_parser():
    from synapseml_tpu.onnx.importer import OnnxFunction

    model_bytes = _model_bytes(seed=7)
    mine = read_initializers(model_bytes)
    theirs = OnnxFunction(model_bytes, dtype_policy="bfloat16").constants
    assert set(mine) == set(theirs)
    assert mine["l0_in_w"].dtype.name == "bfloat16"
    for name, value in theirs.items():
        np.testing.assert_array_equal(
            np.asarray(mine[name], np.float32),
            np.asarray(value).astype(np.float32), err_msg=name)


def test_the_sound_program_and_its_control():
    result = run.run_cell(CELL, seed=2_147_484_011, seconds=0.3, trace=False,
                          rehearse=True, with_control=True)
    assert result["correct"] is True
    assert result["control"]["correct"] is False


@pytest.mark.parametrize("fault", ["a_dropped_expert_pick",
                                   "a_conv_pad_that_sees_ahead",
                                   "decay_without_its_mask"])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    """The rehearsal cell with one operator of the executor broken."""
    import weakref

    from synapseml_tpu.onnx import importer, ops

    # a live model of the same graph would lend its sound program
    monkeypatch.setattr(importer, "_PROGRAMS", weakref.WeakValueDictionary())

    op_type = {"a_dropped_expert_pick": "ExpertFFN",
               "a_conv_pad_that_sees_ahead": "Conv",
               "decay_without_its_mask": "Trilu"}[fault]
    sound = ops.OPS[op_type]

    def broken(inputs, attrs, ctx):
        if fault == "a_dropped_expert_pick":  # the last pick adds nothing
            weight = inputs[2].at[..., -1].set(0.0)
            return sound([*inputs[:2], weight, *inputs[3:]], attrs, ctx)
        if fault == "a_conv_pad_that_sees_ahead":  # pads [3, 0] -> [2, 1]
            return sound(inputs, dict(attrs, pads=[2, 1]), ctx)
        return inputs[0]  # L = exp(segsum) with its upper triangle left in

    monkeypatch.setitem(ops.OPS, op_type, broken)
    result = run.run_cell(CELL, seed=2_147_484_012, seconds=0.3, trace=False,
                          rehearse=True)
    assert result["correct"] is False
    assert [k for k, row in result["compared"].items()
            if row["value"] is None or not row["value"] <= row["limit"]]
