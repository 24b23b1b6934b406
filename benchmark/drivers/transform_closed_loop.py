"""One caller in a closed loop on ``ONNXModel.transform``: a batch job that
waits for each table's reply before it sends the next.

The system under test is entered exactly as a user enters it: a ``Table`` in,
a ``Table`` out, the fetched columns read back as numpy. Everything a call
returned is kept, so that the comparison after the window is of what the
timed path produced.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List

import jax
import numpy as np

from benchmark.traffic import make_pool
from benchmark.trace_reduce import CALL_ANNOTATION


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.model = None
        self.model_bytes: bytes = b""
        self.pool: List[Dict[str, np.ndarray]] = []
        self.tables: list = []

    def setup(self) -> None:
        """Build the model file and the stage, make the pool, and warm the
        cell's one shape (the compile, or its load from the cache)."""
        from synapseml_tpu.models.zoo import build_model_bytes
        from synapseml_tpu.onnx import ONNXModel

        c = self.config
        self.model_bytes = build_model_bytes(c["builder"],
                                             **c["builder_kwargs"])
        self.model = ONNXModel(
            model_bytes=self.model_bytes,
            feed_dict={name: spec["column"]
                       for name, spec in c["feed"].items()},
            fetch_dict=dict(c["fetch"]),
            batch_size=self.traffic["bucket"], dtype_policy=c["policy"])
        self.new_pool(self.seed)
        self._call(self.tables[0])

    def new_pool(self, seed: int) -> None:
        from synapseml_tpu.core import Table

        self.seed = seed
        self.pool = make_pool(self.config, self.traffic, seed)
        self.tables = [Table(dict(columns)) for columns in self.pool]

    def _call(self, table) -> Dict[str, np.ndarray]:
        out = self.model.transform(table)
        return {col: np.asarray(out[col]) for col in self.config["fetch"]}

    def drive(self, seconds: float, after_call: Callable[[int], None]
              ) -> dict:
        """Calls until ``seconds`` have passed; the call in flight then is
        finished and counted, and the window ends with it."""
        calls, answers = [], []
        t0 = time.perf_counter()
        deadline = t0 + seconds
        i = 0
        while True:
            start = time.perf_counter()
            if start >= deadline:
                break
            table = self.tables[i % len(self.tables)]
            with jax.profiler.TraceAnnotation(CALL_ANNOTATION, call=i):
                answers.append(self._call(table))
            calls.append((start - t0, time.perf_counter() - t0, len(table)))
            after_call(i)
            i += 1
        return {"calls": calls, "window_s": time.perf_counter() - t0,
                "answers": answers, "bucket": self.traffic["bucket"]}

    def release(self) -> None:
        """Drop the program and its device state before the reference runs."""
        self.model = None
        self.tables = []
