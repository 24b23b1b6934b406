"""The one generator of inputs: a cell's traffic file plus ``--seed`` gives
the pool of tables the window draws from.

A traffic mix is data: ``rows_per_table``, ``pool_tables`` and the symbolic
``dims`` of the configuration's feed columns. Every seed gives the same sizes
(so the seed never changes the work), other values, and another order of the
pool.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def size_of(token, config: dict, dims: Dict[str, int]) -> int:
    if isinstance(token, int):
        return token
    return int(dims[token] if token in dims else config[token])


def _column(spec: dict, rows: int, config: dict, dims: Dict[str, int],
            rng: np.random.Generator) -> np.ndarray:
    shape = (rows,) + tuple(size_of(t, config, dims) for t in spec["shape"])
    if spec["draw"] == "uniform_int":
        return rng.integers(0, size_of(spec["high"], config, dims), size=shape,
                            dtype=np.dtype(spec["dtype"]))
    if spec["draw"] == "standard_normal":
        return rng.standard_normal(shape, dtype=np.dtype(spec["dtype"]))
    raise ValueError(f"unknown draw {spec['draw']!r} in the configuration")


def make_pool(config: dict, traffic: dict, seed: int
              ) -> List[Dict[str, np.ndarray]]:
    """``pool_tables`` tables of ``rows_per_table`` rows, column name ->
    array, in an order the seed shuffles."""
    rng = np.random.default_rng(seed)
    dims = traffic.get("dims", {})
    pool = [{spec["column"]: _column(spec, traffic["rows_per_table"], config,
                                     dims, rng)
             for spec in config["feed"].values()}
            for _ in range(traffic["pool_tables"])]
    return [pool[i] for i in rng.permutation(len(pool))]
