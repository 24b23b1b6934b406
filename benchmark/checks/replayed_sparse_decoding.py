"""``correct`` for cells whose calls DECODE through attention over keys a
learned indexer chose: ``replayed_decoding``'s numbers, and the same two of
``prompt_pooled``.

At a position past ``topk`` a query keeps the ``topk`` keys of largest index
score, and a key whose score lies within a rounding of the query's threshold
is in or out of the set by that rounding. With seeded weights every query has
some, and each swap moves the layer's output far more than the rounding
itself did; the layers after it amplify the difference. So the numbers
``replayed_decoding`` takes at the positions the ids were chosen from are led
by which keys the rounding let in, under bfloat16 and under the float8
control alike, and tell the two apart by less than the rest of the check
wants. Up to position ``topk - 1`` every visible key is kept and no rounding
moves a set: there the program's output differs from the reference by its
arithmetic alone. The program hands out ``prompt_pooled`` (``[N, hidden]``,
the mean of the final norm's output over those positions) and the
reference's ``replay`` gives its own at the same rows:

- ``prompt_pooled.rel_rms`` / ``.worst_row``: what judges the precision of
  the arithmetic;
- the rest as in ``replayed_decoding``, where ``pooled`` judges the keys the
  program chose (attention over the wrong keys moves it by tenths).

The float8 control is judged by both sets of numbers.
"""

from __future__ import annotations

import numpy as np

from benchmark import compare
from benchmark.checks import replayed_decoding
from benchmark.checks.sampled_rows import build_reference  # noqa: F401

_OWN = "prompt_pooled."


class _Remembered:
    """A reference whose ``replay`` keeps what it gave, by precision."""

    def __init__(self, reference):
        self.reference, self.given = reference, {}

    def replay(self, prompts, tokens, precision: str = "float32",
               block_rows: int = 0):
        out = self.reference.replay(prompts, tokens, precision, block_rows)
        self.given[precision] = out
        return out


def _sampled(cell: dict, answers: list, n_tables: int, seed: int,
             column: str) -> np.ndarray:
    """``column`` of the answers at the rows ``replayed_decoding.check``
    samples with the same ``seed``."""
    rng = np.random.default_rng(seed)
    got = []
    for t in range(min(n_tables, len(answers))):
        rows = compare.sample_rows(cell["traffic"]["rows_per_table"],
                                   cell["check"]["rows_per_table"], rng)
        got.append(answers[t][column][rows])
    return np.concatenate(got)


def _numbers(got: np.ndarray, ref: np.ndarray) -> dict:
    return {_OWN + k: v for k, v in compare.column_numbers(got, ref).items()}


def check(cell: dict, config: dict, driver, answers: list, seed: int,
          with_control: bool = False, reference=None) -> dict:
    spec = cell["check"]
    if reference is None:
        reference = build_reference(config, driver.model_bytes)
    remembered = _Remembered(reference)
    inner = dict(cell, check=dict(spec, limits={
        k: v for k, v in spec["limits"].items() if not k.startswith(_OWN)}))
    out = replayed_decoding.check(inner, config, driver, answers, seed,
                                  with_control, remembered)
    got = _sampled(cell, answers, len(driver.pool), seed, "prompt_pooled")
    ref = remembered.given["float32"]["prompt_pooled"]
    numbers = dict(out["numbers"], **_numbers(got, ref))
    out.update(compare.judge(numbers, spec["limits"]), numbers=numbers)
    if with_control:
        control = dict(out["control"]["numbers"], **_numbers(
            remembered.given[spec["control"]]["prompt_pooled"], ref))
        out["control"] = dict(compare.judge(control, {
            k: v for k, v in spec["limits"].items() if k in control}),
            numbers=control)
    return out
