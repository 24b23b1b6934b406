"""The ``replayed_decoding`` check: on made-up logits, a wrong id, a shifted
pass and a lower precision each fail one limit; on the tiny decoding cell,
the whole command on the CPU, a sound run is correct and the float8 control
and a fault where the answer is made are not."""

import numpy as np
import pytest

import run
from benchmark.checks import replayed_decoding as check

CELL = "joyai_flash_tiny.rehearsal"
LIMITS = {"chosen_logprob.rel_rms": 0.02, "chosen_logprob.worst_row": 0.03,
          "argmax_gap": 0.45}


def _made_up(rows=4, generate=16, vocab=512, seed=0):
    """Logits whose largest stands 0.5 standard deviations clear, the greedy
    ids and their log-probabilities."""
    logits = np.random.default_rng(seed).standard_normal(
        (rows, generate, vocab)).astype(np.float32)
    tokens = logits.argmax(-1)
    np.put_along_axis(logits, tokens[..., None],
                      np.sort(logits, -1)[..., -2:-1] + 0.5, axis=-1)
    return logits, tokens, check.logprob_of(logits, tokens)


def _over(numbers):
    return {k for k, limit in LIMITS.items() if not numbers[k] <= limit}


def test_a_sound_answer_is_within_every_limit():
    logits, tokens, logprob = _made_up()
    numbers = check.replay_numbers(logits, tokens, logprob)
    assert numbers["argmax_gap"] == 0 and _over(numbers) == set()
    assert set(numbers) == set(LIMITS)


@pytest.mark.parametrize("fault,fails", [
    ("a_wrong_id", "argmax_gap"),
    ("a_shifted_pass", "argmax_gap"),
    ("a_lower_precision", "chosen_logprob.rel_rms"),
    ("an_id_outside_the_vocabulary", "argmax_gap")])
def test_each_fault_fails_a_limit(fault, fails):
    logits, tokens, logprob = _made_up()
    if fault == "a_wrong_id":  # one id of one row, its own logprob kept
        tokens = tokens.copy()
        tokens[1, 5] = (tokens[1, 5] + 1) % logits.shape[-1]
    elif fault == "a_shifted_pass":  # every answer one pass late
        tokens, logprob = np.roll(tokens, 1, axis=1), np.roll(logprob, 1,
                                                             axis=1)
    elif fault == "a_lower_precision":  # the ids hold, the numbers do not
        logprob = logprob * (1 + 0.05 * np.random.default_rng(1)
                             .standard_normal(logprob.shape))
    else:
        tokens = tokens.copy()
        tokens[0, 0] = logits.shape[-1]
    numbers = check.replay_numbers(logits, tokens, logprob)
    assert fails in {k for k in LIMITS if not numbers.get(k, np.inf)
                     <= LIMITS[k]}


def test_the_drafts_numbers_carry_their_own_names():
    logits, tokens, logprob = _made_up(seed=2)
    numbers = check.replay_numbers(logits, tokens, logprob, "draft_")
    assert set(numbers) == {"draft_logprob.rel_rms",
                            "draft_logprob.worst_row", "draft_argmax_gap"}


@pytest.fixture(scope="module")
def sound():
    return run.run_cell(CELL, seed=2_147_484_201, seconds=0.5, trace=False,
                        rehearse=True, with_control=True)


def test_a_sound_run_is_correct_with_every_number_beside_its_limit(sound):
    assert sound["correct"] is True and sound["attempted"] >= 1
    assert set(sound["compared"]) == {
        "chosen_logprob.rel_rms", "chosen_logprob.worst_row", "argmax_gap",
        "pooled.rel_rms", "pooled.worst_row", "repeat_mismatch", "nonfinite"}
    for row in sound["compared"].values():
        assert row["value"] <= row["limit"]
    assert sound["metrics"]["rows_per_s"]["value"] > 0


def test_the_float8_control_comes_out_not_correct(sound):
    assert sound["control"]["correct"] is False
    numbers, limits = sound["control"]["numbers"], sound["compared"]
    assert numbers["pooled.rel_rms"] > limits["pooled.rel_rms"]["limit"]


@pytest.mark.parametrize("fault", ["a_choice_tampered_with",
                                   "a_weight_perturbed",
                                   "an_answer_changes_between_calls"])
def test_a_fault_where_the_answer_is_made_is_not_correct(monkeypatch, fault):
    from synapseml_tpu.onnx.importer import OnnxFunction

    sound_call, n = OnnxFunction.__call__, [0]

    def broken(self, feeds):
        out = {k: np.array(v) for k, v in sound_call(self, feeds).items()}
        n[0] += 1
        if fault == "a_choice_tampered_with":  # every id of the first row
            out["tokens"][0] = (out["tokens"][0] + 97) % 256
        elif fault == "an_answer_changes_between_calls" and n[0] == 5:
            out["pooled"][1] += 1e-3
        return out

    monkeypatch.setattr(OnnxFunction, "__call__", broken)
    if fault == "a_weight_perturbed":
        build = check.build_reference

        def perturbed(config, model_bytes):
            reference = build(config, model_bytes)
            weights = dict(reference.weights)
            weights["l0_uv_w"] = np.asarray(weights["l0_uv_w"]
                                            ).astype(np.float32) * 2.0
            reference.weights = weights
            return reference

        monkeypatch.setattr(check, "build_reference", perturbed)
    result = run.run_cell(CELL, seed=2_147_484_202, seconds=0.3, trace=False,
                          rehearse=True)
    assert result["correct"] is False
    over = {k for k, row in result["compared"].items()
            if row["value"] is None or row["value"] > row["limit"]}
    want = {"a_choice_tampered_with": "argmax_gap",
            "a_weight_perturbed": "pooled.rel_rms",
            "an_answer_changes_between_calls": "repeat_mismatch"}[fault]
    assert want in over
