"""``correct`` for cells whose calls GENERATE: the program's choices feed its
next pass, so an answer cannot be held against ids the reference would have
chosen (with seeded weights the largest logit changes on rounding, and every
later position with it). Instead the reference is put, pass by pass, into the
states the program itself went through, and what the program did there is
judged.

For a seeded sample of rows of each pool table (always the first and the
last) and, in each, the first block, the last block and two blocks drawn from
the seed, every pass of those blocks is replayed by the plain reference
(``reference/<family>.py``: ``replay``; one full forward a state, no cache,
the states of ``block_rows`` sampled rows a batch). Numbers, each beside its
limit in the cell's file:

- ``chosen_logprob.rel_rms`` / ``.worst_row``: the program's log-probability
  of each id it fixed against the reference's log-softmax of that id at the
  same state (``compare.column_numbers``);
- ``argmax_gap``: the reference's largest logit less its logit of the id the
  program fixed, in units of the logits' standard deviation over the
  vocabulary, largest over the sample: 0 where the reference would have made
  the same choice, a rounding's worth at a near tie, several units for a
  choice from wrong logits;
- ``confidence_gap``: the same for WHICH positions a pass fixed: the
  reference's confidence (log-probability of its candidate) of the most
  confident position the pass left masked, less that of the least confident
  it fixed, floored at 0, in the same units;
- ``pooled.rel_rms`` / ``.worst_row``: the mean of the final norm's output
  over the generated positions, against one full forward over the row's final
  ids: what judges the precision of the arithmetic;
- ``schedule_mismatch``: blocks, over every row of every table, in which some
  pass did not fix exactly ``block / passes`` positions;
- ``mask_left``: mask ids among the generated ``tokens``, over every call;
- ``repeat_mismatch`` and ``nonfinite`` as in ``compare.py``.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark import compare
from benchmark.checks.sampled_rows import build_reference  # noqa: F401


def _log_softmax(logits: np.ndarray) -> np.ndarray:
    shifted = logits.astype(np.float64) - logits.max(-1, keepdims=True)
    return shifted - np.log(np.exp(shifted).sum(-1, keepdims=True))


def replay_numbers(logits: np.ndarray, tokens: np.ndarray,
                   unmask_pass: np.ndarray, chosen_logprob: np.ndarray,
                   blocks: List[List[int]], block: int, mask_id: int
                   ) -> Dict[str, float]:
    """What the replayed ``logits [row, sampled block, pass, position,
    vocab]`` say of the rows' ``tokens``, ``unmask_pass`` and
    ``chosen_logprob`` (each ``[row, generated]``)."""
    logits = np.array(logits, np.float32)
    logits[..., mask_id] = -np.inf  # as a choice is made from them
    sigma = logits.std(-1, where=np.isfinite(logits))  # [row, block, pass,
    #                                                      position]
    logprob = _log_softmax(logits)
    got, want, argmax_gap, confidence_gap = [], [], 0.0, 0.0
    for r, row_blocks in enumerate(blocks):
        got_row, want_row = [], []
        for j, b in enumerate(row_blocks):
            span = slice(b * block, (b + 1) * block)
            fixed_at, ids = unmask_pass[r, span], tokens[r, span]
            for p in range(block):
                t = int(fixed_at[p])
                if not 0 <= t < logits.shape[2]:
                    return {"argmax_gap": float("inf")}
                want_row.append(logprob[r, j, t, p, ids[p]])
                got_row.append(chosen_logprob[r, b * block + p])
                gap = (logits[r, j, t, p].max() - logits[r, j, t, p, ids[p]]
                       ) / sigma[r, j, t, p]
                argmax_gap = max(argmax_gap, float(gap))
            for t in range(logits.shape[2]):
                confidence = logprob[r, j, t].max(-1)  # [position]
                left, fixed = fixed_at > t, fixed_at == t
                if left.any() and fixed.any():
                    gap = (confidence[left].max() - confidence[fixed].min()
                           ) / sigma[r, j, t].mean()
                    confidence_gap = max(confidence_gap, float(gap))
        got.append(got_row)
        want.append(want_row)
    out = {"chosen_logprob." + k: v for k, v in
           compare.column_numbers(np.asarray(got), np.asarray(want)).items()}
    out.update(argmax_gap=argmax_gap, confidence_gap=confidence_gap)
    return out


def schedule_mismatch(unmask_pass: np.ndarray, block: int, passes: int
                      ) -> int:
    """Blocks of ``unmask_pass [rows, generated]`` in which some pass fixed
    another number of positions than ``block / passes``."""
    per_block = unmask_pass.reshape(len(unmask_pass), -1, block)
    counts = np.stack([(per_block == t).sum(-1) for t in range(passes)], -1)
    return int((counts != block // passes).any(-1).sum())


def check(cell: dict, config: dict, driver, answers: list, seed: int,
          with_control: bool = False, reference=None) -> dict:
    spec = cell["check"]
    t_check = time.perf_counter()
    if reference is None:
        reference = build_reference(config, driver.model_bytes)
    block, passes = reference.block, reference.passes
    mask_id = reference.mask_id
    n_tables = len(driver.pool)
    rng = np.random.default_rng(seed)
    (feed_column,) = [s["column"] for s in config["feed"].values()]
    prompts, blocks, got = [], [], {col: [] for col in config["fetch"]}
    for t in range(min(n_tables, len(answers))):
        rows = compare.sample_rows(cell["traffic"]["rows_per_table"],
                                   spec["rows_per_table"], rng)
        prompts.append(driver.pool[t][feed_column][rows])
        for col in got:
            got[col].append(answers[t][col][rows])
        n_blocks = answers[t]["tokens"].shape[1] // block
        # a row's blocks: the first, the last, others drawn from the seed
        blocks += [compare.sample_rows(n_blocks, spec["blocks_per_row"],
                                       rng).tolist() for _ in rows]
    prompts = np.concatenate(prompts)
    got = {k: np.concatenate(v) for k, v in got.items()}

    def against(precision: str):
        out = reference.replay(prompts, got["tokens"], got["unmask_pass"],
                               blocks, precision, spec["block_rows"])
        return out["logits"], out["pooled"]

    ref_logits, ref_pooled = against("float32")
    first_round = answers[:n_tables]
    numbers = {
        "repeat_mismatch": compare.repeat_mismatch(answers, n_tables),
        "nonfinite": compare.nonfinite(answers),
        "mask_left": int(sum((a["tokens"] == mask_id).sum()
                             for a in answers)),
        "schedule_mismatch": sum(schedule_mismatch(a["unmask_pass"], block,
                                                   passes)
                                 for a in first_round)}
    numbers.update(replay_numbers(ref_logits, got["tokens"],
                                  got["unmask_pass"], got["chosen_logprob"],
                                  blocks, block, mask_id))
    for k, v in compare.column_numbers(got["pooled"], ref_pooled).items():
        numbers["pooled." + k] = v
    out = compare.judge(numbers, spec["limits"])
    out["numbers"] = numbers
    out["check_s"] = time.perf_counter() - t_check
    if with_control:
        # the control in the program's place, at the program's own states:
        # its log-probability of the ids the program fixed, and its pooled
        low_logits, low_pooled = against(spec["control"])
        low_logits[..., mask_id] = -np.inf
        low = _log_softmax(low_logits)
        its = np.array(got["chosen_logprob"], np.float64)
        for r, row_blocks in enumerate(blocks):
            for j, b in enumerate(row_blocks):
                for p in range(block):
                    g = b * block + p
                    its[r, g] = low[r, j, got["unmask_pass"][r, g], p,
                                    got["tokens"][r, g]]
        control = replay_numbers(ref_logits, got["tokens"],
                                 got["unmask_pass"], its, blocks, block,
                                 mask_id)
        control = {k: v for k, v in control.items()
                   if k.startswith("chosen_logprob.")}
        for k, v in compare.column_numbers(low_pooled, ref_pooled).items():
            control["pooled." + k] = v
        out["control"] = dict(compare.judge(control, {
            k: v for k, v in spec["limits"].items() if k in control}),
            numbers=control)
    return out
