"""The ``replayed_sparse_decoding`` check on made-up answers and a made-up
reference: ``prompt_pooled`` is judged at the rows ``replayed_decoding``
samples, beside its numbers, for the program and for the control."""

import types

import numpy as np
import pytest

from benchmark.checks import replayed_sparse_decoding as check

ROWS, S, G, VOCAB, HIDDEN = 6, 5, 4, 64, 8
LIMITS = {"chosen_logprob.rel_rms": 0.02, "chosen_logprob.worst_row": 0.03,
          "argmax_gap": 0.45, "pooled.rel_rms": 0.05,
          "pooled.worst_row": 0.05, "prompt_pooled.rel_rms": 0.05,
          "prompt_pooled.worst_row": 0.05, "repeat_mismatch": 0,
          "nonfinite": 0}
CELL = {"traffic": {"rows_per_table": ROWS},
        "check": {"rows_per_table": 3, "block_rows": 0, "control": "float8",
                  "limits": LIMITS}}
CONFIG = {"feed": {"input_ids": {"column": "input_ids"}},
          "fetch": {k: k for k in ("tokens", "chosen_logprob", "pooled",
                                   "prompt_pooled")}}


class _Reference:
    """What every row of the pool gives, the control's pooled numbers off
    by a share of ``control_off``."""

    def __init__(self, prompts, control_off):
        rng = np.random.default_rng(0)
        self.prompts, self.control_off = prompts, control_off
        self.logits = rng.standard_normal((ROWS, G, VOCAB))
        self.pooled = rng.standard_normal((ROWS, HIDDEN))
        self.prompt_pooled = rng.standard_normal((ROWS, HIDDEN))

    def replay(self, prompts, tokens, precision="float32", block_rows=0):
        rows = [int(np.flatnonzero((self.prompts == p).all(1))[0])
                for p in prompts]
        off = 1 + (self.control_off if precision == "float8" else 0)
        return {"logits": self.logits[rows], "pooled": self.pooled[rows],
                "prompt_pooled": self.prompt_pooled[rows] * off}


def _run(prompt_pooled_off=0.0, control_off=0.3):
    prompts = np.arange(ROWS * S).reshape(ROWS, S)
    reference = _Reference(prompts, control_off)
    tokens = reference.logits.argmax(-1)
    shifted = reference.logits - reference.logits.max(-1, keepdims=True)
    logprob = np.take_along_axis(
        shifted - np.log(np.exp(shifted).sum(-1, keepdims=True)),
        tokens[..., None], axis=-1)[..., 0]
    answer = {"tokens": tokens, "chosen_logprob": logprob,
              "pooled": reference.pooled,
              "prompt_pooled": reference.prompt_pooled
              * (1 + prompt_pooled_off)}
    driver = types.SimpleNamespace(pool=[{"input_ids": prompts}])
    return check.check(CELL, CONFIG, driver, [answer, answer], seed=3,
                       with_control=True, reference=reference)


def test_a_sound_answer_is_correct_and_the_control_fails_prompt_pooled():
    out = _run()
    assert out["correct"] is True and set(out["compared"]) == set(LIMITS)
    assert out["numbers"]["prompt_pooled.rel_rms"] == 0
    control = out["control"]
    assert control["correct"] is False
    assert control["numbers"]["prompt_pooled.rel_rms"] == pytest.approx(0.3)
    assert set(control["compared"]) == {
        "chosen_logprob.rel_rms", "chosen_logprob.worst_row",
        "pooled.rel_rms", "pooled.worst_row", "prompt_pooled.rel_rms",
        "prompt_pooled.worst_row"}


def test_a_prompt_pass_off_by_a_tenth_fails_prompt_pooled_alone():
    out = _run(prompt_pooled_off=0.1)
    failed = {k for k, row in out["compared"].items()
              if not row["value"] <= row["limit"]}
    assert out["correct"] is False
    assert failed == {"prompt_pooled.rel_rms", "prompt_pooled.worst_row"}
