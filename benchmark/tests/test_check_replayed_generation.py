"""The ``replayed_generation`` check on the tiny generating cell, the whole
command on the CPU: a sound run is correct; a tampered choice, a perturbed
weight and the float8 control are not."""

import numpy as np
import pytest

import run

CELL = "sdar_moe_tiny.rehearsal"


@pytest.fixture(scope="module")
def sound():
    return run.run_cell(CELL, seed=2_147_484_101, seconds=0.5, trace=False,
                        rehearse=True, with_control=True)


def test_a_sound_run_is_correct_with_every_number_beside_its_limit(sound):
    assert sound["correct"] is True and sound["attempted"] >= 1
    assert set(sound["compared"]) == {
        "chosen_logprob.rel_rms", "chosen_logprob.worst_row", "argmax_gap",
        "confidence_gap", "pooled.rel_rms", "pooled.worst_row",
        "schedule_mismatch", "mask_left", "repeat_mismatch", "nonfinite"}
    for row in sound["compared"].values():
        assert row["value"] <= row["limit"]


def test_the_float8_control_comes_out_not_correct(sound):
    assert sound["control"]["correct"] is False
    numbers, limits = sound["control"]["numbers"], sound["compared"]
    assert numbers["pooled.rel_rms"] > limits["pooled.rel_rms"]["limit"]


@pytest.mark.parametrize("fault", ["a_choice_tampered_with",
                                   "a_weight_perturbed", "a_mask_left",
                                   "a_pass_fixes_too_many"])
def test_a_fault_where_the_answer_is_made_is_not_correct(monkeypatch, fault):
    from synapseml_tpu.onnx.importer import OnnxFunction

    sound_call = OnnxFunction.__call__

    def broken(self, feeds):
        out = {k: np.array(v) for k, v in sound_call(self, feeds).items()}
        if fault == "a_choice_tampered_with":  # first row, last block
            out["tokens"][0, -1] = (out["tokens"][0, -1] + 1) % 254
        elif fault == "a_mask_left":
            out["tokens"][1, 2] = 255
        elif fault == "a_pass_fixes_too_many":
            out["unmask_pass"][2, :4] = 0
        return out

    monkeypatch.setattr(OnnxFunction, "__call__", broken)
    if fault == "a_weight_perturbed":
        from benchmark.checks import replayed_generation as check

        build = check.build_reference

        def perturbed(config, model_bytes):
            reference = build(config, model_bytes)
            weights = dict(reference.weights)
            weights["l0_v_w"] = np.asarray(weights["l0_v_w"]
                                           ).astype(np.float32) * 1.25
            reference.weights = weights
            return reference

        monkeypatch.setattr(check, "build_reference", perturbed)
    result = run.run_cell(CELL, seed=2_147_484_102, seconds=0.3, trace=False,
                          rehearse=True)
    assert result["correct"] is False
    over = {k for k, row in result["compared"].items()
            if row["value"] is None or row["value"] > row["limit"]}
    want = {"a_choice_tampered_with": "argmax_gap",
            "a_weight_perturbed": "pooled.rel_rms",
            "a_mask_left": "mask_left",
            "a_pass_fixes_too_many": "schedule_mismatch"}[fault]
    assert want in over
