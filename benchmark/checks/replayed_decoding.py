"""``correct`` for cells whose calls DECODE: a prompt pass, then one id a row a
pass, greedily, each choice feeding the next pass. An answer cannot be held
against ids the reference would have chosen (with seeded weights the largest
logit changes on rounding, and every later position with it), so the
reference is teacher-forced: for a seeded sample of rows of each pool table
(always the first and the last), ONE full causal forward of the plain
reference (``reference/<family>.py``: ``replay``; no cache, no loop) over the
prompt and the program's own ids but the last gives the logits every one of
the program's choices is judged by. Numbers, each beside its limit in the
cell's file:

- ``chosen_logprob.rel_rms`` / ``.worst_row``: the program's log-probability
  of each id it chose against the reference's log-softmax of that id at the
  same position (``compare.column_numbers``);
- ``argmax_gap``: the reference's largest logit less its logit of the id the
  program chose, as a share of the largest logit's own lead over the mean
  logit (some 4.5 standard deviations in a vocabulary of 129,280), largest
  over the sample: 0 where the reference would have made the same choice,
  hundredths at a near tie, up to 0.2 where a rounding flipped an expert
  pick at that position (seeded weights: a scaled sigmoid router is not
  peaked), about 1 for a choice from wrong logits (a wrong id, a pass's
  answer in another's slot);
- ``pooled.rel_rms`` / ``.worst_row``: the mean, over the positions the ids
  were chosen from, of the final norm's output: what judges the precision of
  the arithmetic;
- ``repeat_mismatch`` and ``nonfinite`` as in ``compare.py``.

Where the model file holds a prediction module and the configuration fetches
``draft_tokens`` and ``draft_logprob``, the same two numbers are taken of the
drafts against the module's own logits (``draft_logprob.*``,
``draft_argmax_gap``).
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from benchmark import compare
from benchmark.checks.sampled_rows import build_reference  # noqa: F401


def _log_softmax(logits: np.ndarray) -> np.ndarray:
    shifted = logits.astype(np.float64) - logits.max(-1, keepdims=True)
    return shifted - np.log(np.exp(shifted).sum(-1, keepdims=True))


def logprob_of(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """``log_softmax(logits [row, G, vocab])`` at ``tokens [row, G]``."""
    return np.take_along_axis(_log_softmax(logits), tokens[..., None],
                              axis=-1)[..., 0]


def replay_numbers(logits: np.ndarray, tokens: np.ndarray,
                   chosen_logprob: np.ndarray, prefix: str = ""
                   ) -> Dict[str, float]:
    """What the reference's ``logits [row, G, vocab]`` say of the rows'
    ``tokens`` and ``chosen_logprob`` (each ``[row, G]``)."""
    if tokens.min() < 0 or tokens.max() >= logits.shape[-1]:
        return {prefix + "argmax_gap": float("inf")}
    at_token = np.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    top = logits.max(-1)
    gap = (top - at_token) / (top - logits.mean(-1))
    out = {f"{prefix or 'chosen_'}logprob.{k}": v for k, v in
           compare.column_numbers(chosen_logprob,
                                  logprob_of(logits, tokens)).items()}
    out[prefix + "argmax_gap"] = float(gap.max())
    return out


def check(cell: dict, config: dict, driver, answers: list, seed: int,
          with_control: bool = False, reference=None) -> dict:
    spec = cell["check"]
    t_check = time.perf_counter()
    if reference is None:
        reference = build_reference(config, driver.model_bytes)
    n_tables = len(driver.pool)
    rng = np.random.default_rng(seed)
    (feed_column,) = [s["column"] for s in config["feed"].values()]
    prompts, got = [], {col: [] for col in config["fetch"]}
    for t in range(min(n_tables, len(answers))):
        rows = compare.sample_rows(cell["traffic"]["rows_per_table"],
                                   spec["rows_per_table"], rng)
        prompts.append(driver.pool[t][feed_column][rows])
        for col in got:
            got[col].append(answers[t][col][rows])
    prompts = np.concatenate(prompts)
    got = {k: np.concatenate(v) for k, v in got.items()}
    drafts = "draft_tokens" in got

    def numbers_of(its: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]):
        out = replay_numbers(ref["logits"], got["tokens"],
                             its["chosen_logprob"])
        for k, v in compare.column_numbers(its["pooled"],
                                           ref["pooled"]).items():
            out["pooled." + k] = v
        if drafts:
            out.update(replay_numbers(ref["draft_logits"],
                                      got["draft_tokens"],
                                      its["draft_logprob"], "draft_"))
        return out

    ref = reference.replay(prompts, got["tokens"], "float32",
                           spec["block_rows"])
    numbers = {"repeat_mismatch": compare.repeat_mismatch(answers, n_tables),
               "nonfinite": compare.nonfinite(answers)}
    numbers.update(numbers_of(got, ref))
    out = compare.judge(numbers, spec["limits"])
    out["numbers"] = numbers
    out["check_s"] = time.perf_counter() - t_check
    if with_control:
        # the control in the program's place, at the program's own ids: its
        # log-probability of the ids the program chose, and its pooled
        low = reference.replay(prompts, got["tokens"], spec["control"],
                               spec["block_rows"])
        its = {"chosen_logprob": logprob_of(low["logits"], got["tokens"]),
               "pooled": low["pooled"]}
        if drafts:
            its["draft_logprob"] = logprob_of(low["draft_logits"],
                                              got["draft_tokens"])
        control = {k: v for k, v in numbers_of(its, ref).items()
                   if not k.endswith("argmax_gap")}
        out["control"] = dict(compare.judge(control, {
            k: v for k, v in spec["limits"].items() if k in control}),
            numbers=control)
    return out
