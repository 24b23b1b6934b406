"""The comparison that decides ``correct``: what the timed calls returned
against the plain reference, each number beside its limit.

Numbers, per fetched column ``<col>``:

- ``<col>.rel_rms``: Frobenius norm of (program - reference) over that of the
  reference, over the sampled rows: the steady number a lower precision moves;
- ``<col>.worst_row``: the largest row-wise error norm over the root mean
  square row norm of the reference: what one altered or misplaced row moves;

and over every call of the window:

- ``repeat_mismatch``: calls whose answer differs in any bit from the first
  answer to the same table (the program is deterministic: limit 0);
- ``nonfinite``: values that are not finite (limit 0).

The sampled rows are drawn from the seed and always hold each table's first
and last row, where a fault in slicing or padding would sit.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def sample_rows(n_rows: int, n_sample: int, rng: np.random.Generator
                ) -> np.ndarray:
    n_sample = min(n_sample, n_rows)
    inner = rng.choice(np.arange(1, n_rows - 1), size=max(n_sample - 2, 0),
                       replace=False) if n_rows > 2 else np.arange(0)
    return np.unique(np.concatenate([[0, n_rows - 1], inner]))[:n_sample]


def column_numbers(got: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    got = np.asarray(got, np.float64).reshape(len(ref), -1)
    ref = np.asarray(ref, np.float64).reshape(len(ref), -1)
    err = np.linalg.norm(got - ref, axis=1)
    ref_rows = np.linalg.norm(ref, axis=1)
    scale = float(np.sqrt(np.mean(ref_rows ** 2)))
    return {"rel_rms": float(np.sqrt(np.sum(err ** 2)) /
                             np.sqrt(np.sum(ref_rows ** 2))),
            "worst_row": float(err.max() / scale)}


def repeat_mismatch(answers: List[Dict[str, np.ndarray]], n_tables: int
                    ) -> int:
    return sum(
        any(not np.array_equal(a[col], answers[i % n_tables][col])
            for col in a)
        for i, a in enumerate(answers) if i >= n_tables)


def nonfinite(answers: List[Dict[str, np.ndarray]]) -> int:
    return int(sum(np.size(v) - np.count_nonzero(np.isfinite(v))
                   for a in answers for v in a.values()))


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` and whether every number named by a
    limit is there and within it. A limit without its number fails."""
    table = {name: {"value": numbers.get(name), "limit": limit}
             for name, limit in limits.items()}
    ok = all(row["value"] is not None and np.isfinite(row["value"])
             and row["value"] <= row["limit"] for row in table.values())
    return {"correct": bool(ok), "compared": table}
