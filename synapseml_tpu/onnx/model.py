"""``ONNXModel`` — generic ONNX inference transformer.

Rebuild of ``deep-learning/src/main/scala/.../onnx/ONNXModel.scala`` (685 LoC): feed/
fetch dicts, minibatch→tensor coercion, post-processing (softmax/argmax). Where the
reference opens an ORT session per partition and pays JVM↔native copies per batch
(``applyModel:305-355``), this version compiles the graph once per batch shape and runs
whole batches as single XLA programs on the TPU.

Batching: rows are processed in fixed-size buckets (``batch_size``); the final partial
batch is padded to the bucket and the padding sliced off after — so exactly ONE compiled
executable serves the whole table (the reference pins dim 0 for the same reason,
``ONNXModel.scala:357-362``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ..core import (ColumnSpec, ComplexParam, Param, Table, TableSchema,
                    Transformer)
from ..core.params import ParamValidators
from ..observability import spans as _spans
from ..observability.metrics import get_registry
from .importer import OnnxFunction, model_io_specs

__all__ = ["ONNXModel"]

# what crossed the host/device boundary, counted where it crosses (the
# phase spans of ``_run_buckets``) and added to the registry once a call;
# docs/observability.md has the use of each
_COUNTERS = (
    ("smt_onnx_padded_rows_total",
     "rows repeated to fill a short last bucket up to batch_size"),
    ("smt_onnx_upload_bytes_total",
     "bytes of the host arrays handed to the program"),
    ("smt_onnx_download_bytes_total",
     "bytes of the fetched outputs read back to the host"),
    ("smt_onnx_unfetched_output_bytes_total",
     "bytes of program outputs that no fetch_dict entry read"),
)


class ONNXModel(Transformer):
    """Run an ONNX graph over table columns.

    - ``feed_dict``: onnx input name -> table column name
      (reference ``setFeedDict``, ``ONNXModel.scala:122``)
    - ``fetch_dict``: output column name -> onnx output name (``setFetchDict``)
    - ``softmax_dict`` / ``argmax_dict``: output col -> new col post-ops
      (``softMaxDict``/``argMaxDict``, ``ONNXModel.scala:516-562``)
    """

    model_bytes = ComplexParam("serialized ONNX ModelProto", bytes, default=None)
    feed_dict = Param("onnx input name -> table column", dict, default={})
    fetch_dict = Param("output column -> onnx output name", dict, default={})
    batch_size = Param("inference bucket size (pad-to-bucket)", int, default=64,
                       validator=ParamValidators.gt(0))
    dtype_policy = Param("float32 | bfloat16 (MXU-native)", str, default="float32",
                         validator=ParamValidators.in_list(["float32", "bfloat16"]))
    softmax_dict = Param("col -> softmax(col) output col", dict, default={})
    argmax_dict = Param("col -> argmax(col) output col", dict, default={})
    sharding_layout = ComplexParam(
        "optional runtime.layout.SpecLayout: shard MatMul/Gemm/Conv weights "
        "over the layout's 'model' axis (tensor-parallel serving — models "
        "bigger than one chip's HBM)", object, default=None)

    def __init__(self, uid=None, **kw):
        super().__init__(uid=uid, **kw)
        self._fn: Optional[OnnxFunction] = None
        self._io_specs_cache = None

    def _post_load(self):
        self._fn = None
        self._io_specs_cache = None

    def set_model(self, model_bytes: bytes) -> "ONNXModel":
        self.set("model_bytes", bytes(model_bytes))
        self._fn = None
        self._io_specs_cache = None
        return self

    @property
    def fn(self) -> OnnxFunction:
        if self._fn is None:
            if self.model_bytes is None:
                raise ValueError(f"ONNXModel({self.uid}): model_bytes not set")
            self._fn = OnnxFunction(self.model_bytes,
                                    dtype_policy=self.dtype_policy,
                                    layout=self.sharding_layout)
        return self._fn

    # -- static schema (derived from the graph's value_info; NO jax) --------------

    def _io_specs(self):
        """Graph input/output specs via :func:`model_io_specs` — protobuf
        parsing only (so ``Pipeline.validate`` stays jax-free), cached:
        real models carry hundreds of MB of initializers and must not be
        re-parsed per validate() call. The cache is keyed on the current
        ``model_bytes`` OBJECT, so replacing the model through the generic
        ``Params.set`` path (not just :meth:`set_model`) invalidates it."""
        mb = self.model_bytes
        if mb is None:
            raise ValueError(f"ONNXModel({self.uid}): model_bytes not set")
        cache = self._io_specs_cache
        if cache is None or cache[0] is not mb:
            self._io_specs_cache = cache = (mb, model_io_specs(mb))
        return cache[1]

    def _input_schema_from(self, ins) -> TableSchema:
        cols = {}
        for onnx_in, col in self.feed_dict.items():
            dc, role = ins.get(onnx_in, ("any", "any"))
            # a rank-k graph tensor feeds from a per-row rank-(k-1) column,
            # which may also arrive as an object column of arrays — keep
            # the dtype class, relax the role (stacking is _gather_feed's
            # job, the static contract is "this column exists & is dc")
            cols[col] = ColumnSpec(dc, "any" if role == "tensor" else role)
        return TableSchema(cols)

    def input_schema(self) -> "TableSchema | None":
        if not self.feed_dict or self.model_bytes is None:
            return None
        return self._input_schema_from(self._io_specs()[0])

    def transform_schema(self, schema: TableSchema) -> "TableSchema | None":
        # mis-wiring raises SchemaError so Pipeline.validate wraps it into
        # its documented PipelineSchemaError (naming this stage) instead
        # of letting a bare ValueError escape the plan-time gate
        from ..core.schema import SchemaError

        if self.model_bytes is None or not self.feed_dict \
                or not self.fetch_dict:
            raise SchemaError(
                f"ONNXModel({self.uid}): model_bytes, feed_dict and "
                f"fetch_dict must be set")
        ins, outs = self._io_specs()
        unknown = [k for k in self.feed_dict if k not in ins]
        if unknown:
            raise SchemaError(
                f"ONNXModel({self.uid}): feed_dict keys {unknown} are not "
                f"graph inputs; graph expects {sorted(ins)}")
        missing_out = [n for n in self.fetch_dict.values() if n not in outs]
        if missing_out:
            raise SchemaError(
                f"ONNXModel({self.uid}): fetch_dict outputs {missing_out} "
                f"are not graph outputs; graph produces {sorted(outs)}")
        self._check_schema(schema, self._input_schema_from(ins))
        out = schema
        for col, onnx_name in self.fetch_dict.items():
            dc, role = outs.get(onnx_name, ("any", "any"))
            out = out.with_column(col, ColumnSpec(dc, role))
        for src, dst in self.softmax_dict.items():
            out = out.with_column(dst, ColumnSpec(
                "float", out[src].role if src in out else "any"))
        for src, dst in self.argmax_dict.items():
            out = out.with_column(dst, ColumnSpec("int", "any"))
        return out

    # -- helpers -------------------------------------------------------------------

    def _gather_feed(self, table: Table, col: str) -> np.ndarray:
        arr = table[col]
        if arr.dtype == object:  # ragged/list column -> stack (must be uniform)
            if len(arr) == 0:
                return np.zeros((0,), dtype=np.float32)
            try:
                arr = np.stack([np.asarray(v) for v in arr])
            except ValueError as e:
                raise ValueError(
                    f"ONNXModel({self.uid}): column {col!r} has non-uniform shapes; "
                    f"resize/pad upstream (e.g. ResizeImageTransformer)"
                ) from e
        return arr

    def transform_arrays(self, feeds: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Batched execution with pad-to-bucket; returns full-length outputs."""
        parts = self._run_buckets(feeds)
        with _spans.span("ONNXModel", "assemble"):
            return {k: np.concatenate(v, axis=0) for k, v in parts.items()}

    def _run_buckets(self, feeds: Dict[str, np.ndarray]) -> Dict[str, List[np.ndarray]]:
        """Every bucket's fetched outputs, in order, one list an output
        column. A bucket passes through three phase spans: ``pad`` (slice
        it out, fill a short one), ``dispatch`` (hand it to the program;
        returns when the runtime has taken the call, not when the device
        is done) and ``fetch`` (the host blocked on the reply)."""
        fn = self.fn
        n = len(next(iter(feeds.values())))
        if n == 0:  # empty partitions are normal in a partitioned pipeline
            dummy = {}
            shapes = fn.input_shapes()
            for k, v in feeds.items():
                shp = v.shape[1:]
                if not shp and shapes.get(k) and len(shapes[k]) > 1:
                    shp = tuple(s if isinstance(s, int) else 1 for s in shapes[k][1:])
                dt = v.dtype if v.dtype != object else np.float32
                dummy[k] = np.zeros((1,) + tuple(shp), dtype=dt)
            result = fn(dummy)
            out0 = {}
            for col, name in self.fetch_dict.items():
                if name not in result:  # same error as the non-empty path
                    raise ValueError(
                        f"ONNXModel({self.uid}): graph has no output {name!r}; "
                        f"outputs: {list(result)}"
                    )
                out0[col] = [np.asarray(result[name])[:0]]
            return out0
        b = min(self.batch_size, max(1, n))
        span = _spans.span
        fetched = set(self.fetch_dict.values())
        padded = uploaded = downloaded = unfetched = 0  # in _COUNTERS' order
        out_parts: Dict[str, List[np.ndarray]] = {k: [] for k in self.fetch_dict}
        for lo in range(0, n, b):
            hi = min(lo + b, n)
            pad = b - (hi - lo)
            with span("ONNXModel", "pad", rows=hi - lo):
                batch = {k: v[lo:hi] for k, v in feeds.items()}
                if pad:
                    batch = {
                        k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)]) for k, v in batch.items()
                    }
                    padded += pad
            with span("ONNXModel", "dispatch", rows=b):
                result = fn(batch)
                uploaded += sum(v.nbytes for v in batch.values()
                                if isinstance(v, np.ndarray))
            with span("ONNXModel", "fetch", rows=hi - lo):
                for out_col, onnx_name in self.fetch_dict.items():
                    if onnx_name not in result:
                        raise ValueError(
                            f"ONNXModel({self.uid}): graph has no output {onnx_name!r}; "
                            f"outputs: {list(result)}"
                        )
                    r = np.asarray(result[onnx_name])
                    out_parts[out_col].append(r[: hi - lo] if pad else r)
                    downloaded += r.nbytes
                unfetched += sum(v.nbytes for name, v in result.items()
                                 if name not in fetched)
        if _spans.is_enabled():  # the counters go off with the spans
            reg = get_registry()
            for (name, help_), v in zip(_COUNTERS, (padded, uploaded,
                                                    downloaded, unfetched)):
                reg.counter(name, help_).inc(v)
        return out_parts

    # -- transform -----------------------------------------------------------------

    def _transform(self, table: Table) -> Table:
        with _spans.span("ONNXModel", "gather", rows=len(table)):
            if not self.feed_dict or not self.fetch_dict:
                raise ValueError(f"ONNXModel({self.uid}): feed_dict and fetch_dict must be set")
            unknown = [k for k in self.feed_dict if k not in self.fn.input_names]
            if unknown:
                raise ValueError(
                    f"ONNXModel({self.uid}): feed_dict keys {unknown} are not graph inputs; "
                    f"graph expects {self.fn.input_names}"
                )
            for onnx_in, col in self.feed_dict.items():
                self._validate_input(table, col)
            feeds = {onnx_in: self._gather_feed(table, col) for onnx_in, col in self.feed_dict.items()}
        parts = self._run_buckets(feeds)
        with _spans.span("ONNXModel", "assemble", rows=len(table)):
            out = table
            for col, v in parts.items():
                out = out.with_column(col, np.concatenate(v, axis=0))
            for src, dst in self.softmax_dict.items():
                x = np.asarray(out[src], dtype=np.float64)
                x = x - x.max(axis=-1, keepdims=True)
                e = np.exp(x)
                out = out.with_column(dst, (e / e.sum(axis=-1, keepdims=True)).astype(np.float32))
            for src, dst in self.argmax_dict.items():
                out = out.with_column(dst, np.argmax(np.asarray(out[src]), axis=-1).astype(np.int64))
        return out
