"""``correct`` for cells whose calls return rows: every answer of every call
against the first answer to the same table, and a seeded sample of rows of
each pool table against the plain reference (``compare.py`` has the numbers).

The driver hands over ``model_bytes`` (the model file, which the reference
reads with its own parser) and ``pool`` (the inputs by column); ``answers``
are the fetched columns each call returned, in call order.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import compare


def build_reference(config: dict, model_bytes: bytes):
    from benchmark.reference.onnx_initializers import read_initializers

    family = importlib.import_module("benchmark.reference."
                                     + config["reference"])
    return family.Reference(config, read_initializers(model_bytes))


def check(cell: dict, config: dict, driver, answers: list, seed: int,
          with_control: bool = False, reference=None) -> dict:
    """Compare what the window's calls returned with the plain reference (and,
    for ``readings.py``, put the control through the same comparison)."""
    spec = cell["check"]
    t_check = time.perf_counter()
    if reference is None:
        reference = build_reference(config, driver.model_bytes)
    n_tables = len(driver.pool)
    rng = np.random.default_rng(seed)
    column_of = {spec_["column"]: name for name, spec_ in config["feed"].items()}
    got = {col: [] for col in config["fetch"]}
    feeds = {name: [] for name in config["feed"]}
    for t in range(min(n_tables, len(answers))):
        rows = compare.sample_rows(cell["traffic"]["rows_per_table"],
                                   spec["rows_per_table"], rng)
        for column, values in driver.pool[t].items():
            feeds[column_of[column]].append(values[rows])
        for col in got:
            got[col].append(answers[t][col][rows])
    feeds = {k: np.concatenate(v) for k, v in feeds.items()}
    got = {k: np.concatenate(v) for k, v in got.items()}
    ref = reference.forward_blocks(feeds, spec["block_rows"])
    numbers = {"repeat_mismatch": compare.repeat_mismatch(answers, n_tables),
               "nonfinite": compare.nonfinite(answers)}
    for col, onnx_name in config["fetch"].items():
        for k, v in compare.column_numbers(got[col], ref[onnx_name]).items():
            numbers[f"{col}.{k}"] = v
    out = compare.judge(numbers, spec["limits"])
    out["numbers"] = numbers
    out["check_s"] = time.perf_counter() - t_check
    if with_control:
        low = reference.forward_blocks(feeds, spec["block_rows"],
                                       precision=spec["control"])
        control = {f"{col}.{k}": v for col, name in config["fetch"].items()
                   for k, v in compare.column_numbers(low[name],
                                                      ref[name]).items()}
        out["control"] = dict(compare.judge(control, {
            k: v for k, v in spec["limits"].items() if k in control}),
            numbers=control)
    return out
