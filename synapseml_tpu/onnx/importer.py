"""ONNX graph → XLA executable.

Replaces the reference's ONNX Runtime JNI session
(``deep-learning/.../onnx/ONNXModel.scala:173-193`` ``initializeOrt`` /
``applyModel:305-355``) with a direct lowering: the graph is *interpreted once under
``jax.jit`` tracing*, emitting one fused XLA program per input-shape signature. There is
no per-op dispatch at run time and no JVM↔native tensor copies — feeds go device-side
once, the whole graph runs as a single compiled computation.

Static-shape discipline (TPU requirement): ``Shape``/shape arithmetic is constant-folded
during tracing (any node whose inputs are all graph-constants is evaluated eagerly and
pinned as numpy), so BERT-style dynamic-reshape chains compile to static programs. Each
distinct input shape triggers one retrace — callers batch with fixed bucket sizes
(``ONNXModel`` pads minibatches for exactly this reason; the reference instead pins
shape(0)=batch at ``ONNXModel.scala:357-362``).

``dtype_policy='bfloat16'`` is the MXU-native mode: every floating tensor one node hands
to the next is bfloat16 (inputs and weights are cast on the way in), float32 lives only
inside an op (the accumulator of MatMul/Gemm/Conv, the reductions of Softmax and
LayerNormalization), and graph outputs are returned float32. A graph's own
``Cast(to=FLOAT)`` is honoured; ``smt_onnx_float32_handoff_bytes{fn}`` says how many
bytes of float32 a traced program still passes between nodes.

Weights are ARGUMENTS of the compiled program, not literals in it: every floating
initializer of ``WEIGHT_ARGUMENT_MIN_SIZE`` elements or more is cast to the policy's
type and placed on the device once, at construction, and handed to the program after
the feeds on every call. The program is then keyed by the weights' shapes and types,
never their values (two checkpoints of one graph share one executable), and its size
does not grow with the model's. Smaller floating initializers, and every integer or
shape operand, stay constants of the trace, so ``Shape`` folding is untouched.
"""

from __future__ import annotations

import hashlib
import logging
import threading
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .ops import NOTE_FAMILIES, OPS
from .wire import (DataType, GraphProto, ModelProto, ValueInfo, parse_model,
                   tensor_to_numpy)

__all__ = ["OnnxFunction", "load_model", "model_io_specs"]

_logger = logging.getLogger("synapseml_tpu.onnx")

# floating initializers with at least this many elements are arguments of the
# program (module docstring); below it sit the scalars and the short vectors
# that ops read as static values (Resize's scales and roi, Range's bounds)
WEIGHT_ARGUMENT_MIN_SIZE = 16


def _is_const(v) -> bool:
    return isinstance(v, np.ndarray) or np.isscalar(v)


def _is_floating(dtype) -> bool:
    """numpy's floating types and ml_dtypes' bfloat16, which numpy does not
    count among them."""
    return np.issubdtype(dtype, np.floating) or dtype.name == "bfloat16"


def _value_info_spec(vi: ValueInfo):
    """(dtype_class, shape_role) of a graph ``value_info`` entry, in
    :mod:`synapseml_tpu.core.schema` vocabulary. The leading dim is the
    batch axis, so a rank-2 graph tensor is a per-row *vector* column, a
    rank-3+ one a *tensor* column, rank-0/1 a *scalar* column. Unknown
    element types / shapes degrade to ``any``."""
    np_dtype = DataType._TO_NUMPY.get(vi.elem_type)
    if np_dtype is None:
        dtype_class = "any"
    else:
        from ..core.schema import dtype_class_of

        dtype_class = dtype_class_of(np_dtype)
    if vi.shape is None:
        role = "any"
    elif len(vi.shape) <= 1:
        role = "scalar"
    elif len(vi.shape) == 2:
        role = "vector"
    else:
        role = "tensor"
    return (dtype_class, role)


def model_io_specs(model: "ModelProto | bytes"):
    """Static (input specs, output specs) of an ONNX model, derived from
    the graph's ``value_info`` — ``{name: (dtype_class, shape_role)}``
    per side, initializers excluded from inputs.

    Pure wire-format work: parses the protobuf only, NEVER imports jax —
    this is what ``ONNXModel.transform_schema`` and ``Pipeline.validate``
    run at plan time, and what serving admission derives its request
    schema from."""
    if isinstance(model, (bytes, bytearray, memoryview)):
        model = parse_model(bytes(model))
    graph = model.graph
    init_names = {t.name for t in graph.initializer}
    inputs = {vi.name: _value_info_spec(vi) for vi in graph.input
              if vi.name not in init_names}
    outputs = {vi.name: _value_info_spec(vi) for vi in graph.output}
    return inputs, outputs


class _SharedProgram:
    """The jitted entry point of every live ``OnnxFunction`` whose trace is
    the same (``OnnxFunction._program_digest``): compiled once, whichever
    instance's weights it is then called with. It holds its instances
    weakly, so a dropped model's weights leave the device."""

    def __init__(self, name: str):
        from ..observability.profiling import profiled_jit

        self.instances: "weakref.WeakSet[OnnxFunction]" = weakref.WeakSet()
        # profiled jit entry point: every XLA compile of this program is
        # timed into smt_compile_seconds{fn=...}, its cost_analysis FLOPs
        # cached, and warm calls attribute achieved MFU to the enclosing
        # stage span (observability/profiling.py)
        self.jit = profiled_jit(self._run_positional, name=name)

    def _run_positional(self, *arrays):  # the XLA module is named after it
        with _PROGRAMS_LOCK:  # a WeakSet must not change size under the look
            instance = next(iter(self.instances))
        return instance._run_positional(*arrays)


_PROGRAMS: "weakref.WeakValueDictionary[str, _SharedProgram]" = \
    weakref.WeakValueDictionary()
_PROGRAMS_LOCK = threading.Lock()


class OnnxFunction:
    """Callable wrapper: ``fn(feeds: dict[str, array]) -> dict[str, array]``.

    jit-compiled per input-shape signature; signatures are cached by jax.jit itself.
    """

    def __init__(self, model: "ModelProto | bytes", dtype_policy: str = "float32",
                 external_data_dir: "str | None" = None,
                 layout=None):
        from ..observability import spans

        if dtype_policy not in ("float32", "bfloat16"):
            raise ValueError(f"unknown dtype_policy {dtype_policy!r}")
        self.dtype_policy = dtype_policy
        self._external_dir = external_data_dir
        self.layout = layout
        # three phases of a set-up, a span each: the model's bytes read into
        # host arrays and checked, the weights cast and uploaded, the
        # program found among those already traced (or entered there)
        with spans.span("ONNXModel", "parse"):
            self._parse(model)
        self._place_weights()
        with spans.span("ONNXModel", "register_program"):
            self._register_program()

    def _parse(self, model: "ModelProto | bytes") -> None:
        """Everything before a byte goes to the device: the protobuf, every
        initializer as a numpy array, the ops checked, the sharding plan."""
        if isinstance(model, (bytes, bytearray, memoryview)):
            model = parse_model(bytes(model))
        self.model = model
        self.graph = model.graph
        self.opset = model.opset_version
        # model-local functions: nodes whose (domain, op_type) matches expand
        # to the function body (real exporters emit e.g. LayerNormalization
        # or custom ops this way from IR 8 on)
        self.functions = {(f.domain, f.name): f
                          for f in getattr(model, "functions", [])}
        self.constants: Dict[str, np.ndarray] = {
            t.name: tensor_to_numpy(t, external_dir=self._external_dir)
            for t in self.graph.initializer
        }
        if self.dtype_policy == "float32":
            # a bfloat16 checkpoint computes in float32 under this policy
            # (exact: every bfloat16 is a float32)
            for name, const in self.constants.items():
                if const.dtype.name == "bfloat16":
                    self.constants[name] = const.astype(np.float32)
        init_names = set(self.constants)
        # Graph inputs that are not initializers are the real feeds.
        self.input_infos: List[ValueInfo] = [
            vi for vi in self.graph.input if vi.name not in init_names
        ]
        self.input_names: List[str] = [vi.name for vi in self.input_infos]
        self.output_names: List[str] = [vi.name for vi in self.graph.output]
        self._validate_ops(self.graph)
        # -- model-parallel weight sharding (runtime/layout.py SpecLayout) ----
        # MatMul/Gemm RHS weights partition COLUMN-wise over the layout's
        # 'model' axis and Conv kernels over output channels; jax.jit's GSPMD
        # pass inserts the collectives. Each chip then holds 1/m of every
        # big weight — models larger than one chip's HBM serve at all, and
        # the matmuls themselves run tensor-parallel. Weights keep their
        # sharded placement from __init__ (device_put) and the traced program
        # re-pins it (with_sharding_constraint), so the intent survives
        # however jit stages the closure constants.
        layout = self.layout
        self._const_plan: List[Dict[str, Any]] = []
        self._const_specs: Dict[str, Any] = (
            self._plan_const_specs() if layout is not None
            and (getattr(layout, "model_size", 1) > 1
                 or getattr(layout, "fsdp_size", 1) > 1) else {})
        self._fn_name = "onnx." + (getattr(self.graph, "name", "") or "graph")

    def _register_program(self) -> None:
        """One entry point for every live ``OnnxFunction`` of this program:
        two checkpoints of one graph differ in arguments only."""
        policy_and_placement = f"dtype={self.dtype_policy}"
        if self._const_specs:
            policy_and_placement += ";layout=" + \
                str(self.layout.describe()) + ";" + \
                ",".join(f"{n}:{self._const_specs[n]}"
                         for n in sorted(self._const_specs))
        digest = self._program_digest(policy_and_placement)
        with _PROGRAMS_LOCK:
            program = _PROGRAMS.get(digest)
            if program is None:
                program = _PROGRAMS[digest] = _SharedProgram(self._fn_name)
            program.instances.add(self)
        self._program = program
        self._jit = program.jit
        # the weights' part of a call's signature is settled here, once
        self._call_with_weights = program.jit.bind(*self._weights)

    def _program_digest(self, policy_and_placement: str) -> str:
        """What a trace of ``_run_positional`` depends on besides its
        arguments: the nodes, the policy, every initializer's name, type and
        shape, and the VALUES of those that stay constants. Equal digests
        trace to equal programs. A sharded layout is part of it by identity
        (its mesh's devices are in the trace)."""
        from .wire import _ser_attribute, _ser_node

        h = hashlib.sha256(repr((
            policy_and_placement, self._external_dir,
            sorted(self.model.opset_imports.items()),
            id(self.layout) if self._const_specs else None,
            [vi.name for vi in self.graph.input], self.output_names,
            [(key, f.input, f.output, sorted(f.opset_imports.items()),
              f.attribute, [_ser_attribute(a) for a in f.attribute_proto])
             for key, f in self.functions.items()])).encode())
        for graph in (self.graph, *self.functions.values()):
            for n in graph.node:
                h.update(_ser_node(n))
        weights = set(self._weight_names)
        for name, const in self.constants.items():
            h.update(repr((name, str(const.dtype), const.shape)).encode())
            if name not in weights:
                h.update(np.ascontiguousarray(const).tobytes())
        return h.hexdigest()

    def _place_weights(self) -> None:
        """Cast every weight (module docstring) to the policy's type and put
        it on the device, once; ``constants`` holds the placed array from
        here on, under its initializer's name. A tensor-parallel weight goes
        to its planned shards, any other weight of a sharded layout to every
        device of it (one program cannot mix a mesh with a lone device)."""
        import jax

        from ..observability import spans
        from ..observability.metrics import get_registry

        bf16 = np.dtype("bfloat16")
        sharded = bool(self._const_specs)
        self._weight_names: List[str] = [
            name for name, const in self.constants.items()
            if name in self._const_specs
            or (_is_floating(const.dtype)
                and const.size >= WEIGHT_ARGUMENT_MIN_SIZE)]
        placed = 0
        with spans.span("ONNXModel", "place_weights"):
            for name in self._weight_names:
                const = self.constants[name]
                if self.dtype_policy == "bfloat16" and const.dtype != bf16:
                    # cast BEFORE placement: the executable only ever
                    # consumes the bf16 view; a resident f32 master copy
                    # would triple the weights' HBM
                    const = const.astype(bf16)
                if sharded:
                    const = self.layout.put(const, self._const_specs.get(
                        name, self.layout.replicated()))
                else:
                    const = jax.device_put(const)
                self.constants[name] = const
                placed += const.nbytes
            self._weights = tuple(self.constants[n]
                                  for n in self._weight_names)
            jax.block_until_ready(self._weights)  # the span ends with the upload
        get_registry().gauge(
            "smt_onnx_weight_argument_bytes",
            "bytes of weights placed on the device at construction and "
            "passed to the program as arguments",
            ("fn",), merge="max").labels(self._fn_name).set(placed)

    # -- public ------------------------------------------------------------------

    def __call__(self, feeds: Dict[str, Any]) -> Dict[str, Any]:
        missing = [n for n in self.input_names if n not in feeds]
        if missing:
            raise ValueError(f"missing feeds {missing}; expected {self.input_names}")
        import jax

        # Leave device-resident jax arrays in place; only materialize host data.
        args = [
            feeds[n] if isinstance(feeds[n], jax.Array) else np.asarray(feeds[n])
            for n in self.input_names
        ]
        outs = self._call_with_weights(*args)
        return dict(zip(self.output_names, outs))

    def input_shapes(self) -> Dict[str, Optional[List[Any]]]:
        return {vi.name: vi.shape for vi in self.input_infos}

    # -- model-parallel spec planning (pure graph analysis, no jax) --------------

    def _plan_const_specs(self) -> Dict[str, Any]:
        """Per-initializer PartitionSpec for tensor-parallel serving.

        A weight is sharded only when EVERY consumer agrees on one role:
        - ``MatMul`` input 1, rank 2  -> columns (output features) over
          ``model``;
        - ``Gemm`` input 1, rank 2    -> the output-feature dim (respects
          ``transB``);
        - ``Conv`` input 1, rank 4    -> output channels (OIHW dim 0).
        Anything else (biases, norm params, shape operands, multi-role
        weights) replicates — GSPMD still partitions the surrounding
        compute. Shape arithmetic never involves these tensors, so
        constant folding is unaffected.

        Under a 3-D layout (``fsdp_size > 1``) the planner knows a THIRD
        decision besides shard-over-model/replicate: store-over-fsdp +
        gather-at-consumer. Weights are *stored* row-sharded over the
        fsdp axis (stacked on top of any model sharding) and all-gathered
        transiently at the point of use (``gather_for_use`` re-pin inside
        the jit). This finally gives multi-role weights a correct answer:
        a tied tensor consumed as both a MatMul RHS and a transposed Gemm
        RHS cannot pick one resident sharded form, but it CAN store
        row-sharded and hand each consumer its own transient gathered
        copy — at-rest HBM drops by 1/fsdp instead of paying full
        replication."""
        roles: Dict[str, set] = {}

        def scan(graph):
            for node in graph.node:
                attrs = node.attrs()
                for slot, name in enumerate(node.input):
                    if not name or name not in self.constants:
                        continue
                    const = self.constants[name]
                    role = None
                    if slot == 1 and node.op_type == "MatMul" \
                            and const.ndim == 2:
                        role = ("col", 1)
                    elif slot == 1 and node.op_type == "Gemm" \
                            and const.ndim == 2:
                        role = ("col", 0 if int(attrs.get("transB", 0))
                                else 1)
                    elif slot == 1 and node.op_type == "Conv" \
                            and const.ndim == 4:
                        role = ("conv", 0)
                    roles.setdefault(name, set()).add(role)
                for a in node.attribute:
                    if a.g is not None:
                        scan(a.g)
                    for g in a.graphs:
                        scan(g)

        scan(self.graph)
        for f in self.functions.values():
            scan(f)
        layout = self.layout
        m = layout.model_size
        f = getattr(layout, "fsdp_size", 1)
        specs: Dict[str, Any] = {}

        def record(name: str, decision: str, reason: str) -> None:
            # residency ledger for placement_report(): shape/bytes are
            # captured NOW, while the constant is still a host array
            # (after __init__ the sharded ones are device-resident)
            const = self.constants[name]
            self._const_plan.append({
                "tensor": name, "shape": tuple(const.shape),
                "nbytes": int(const.nbytes),
                "decision": decision, "reason": reason})

        def fsdp_store_dim(const, avoid: Optional[int]) -> Optional[int]:
            # first dim (skipping any model-sharded one) whose size splits
            # over the fsdp axis — the row dim the weight is STORED over
            if f <= 1:
                return None
            for sd in range(const.ndim):
                if sd != avoid and const.shape[sd] % f == 0:
                    return sd
            return None

        for name, rs in roles.items():
            const = self.constants[name]
            is_float = _is_floating(const.dtype)
            if len(rs) != 1 or None in rs:
                kinds = sorted(str(r) for r in rs)
                conflict = (f"consumer-role conflict ({', '.join(kinds)}) — "
                            f"no single shardable role; tied/multi-use "
                            f"weight")
                # store-over-fsdp only pays for real WEIGHTS (some consumer
                # wanted it sharded); pure-elementwise operands (biases,
                # norm params: roles == {None}) stay replicated as before
                sd = (fsdp_store_dim(const, None)
                      if is_float and rs != {None} else None)
                if sd is None:
                    record(name, "replicated", conflict)
                    continue
                # THE fsdp decision: no resident sharded form satisfies
                # every consumer, but row-sharded STORAGE + a transient
                # gathered copy per consumer satisfies all of them
                specs[name] = layout.fsdp_weight(rank=const.ndim, dim=sd)
                record(name, "fsdp",
                       f"stored over fsdp={f} on dim {sd}, all-gathered at "
                       f"each consumer — resolves {conflict}")
                continue
            kind, dim = next(iter(rs))
            if not is_float:
                record(name, "replicated",
                       f"non-float dtype {const.dtype} (shape operand / "
                       f"index table)")
                continue
            if m > 1 and const.shape[dim] % m == 0:
                use = (layout.conv_weight(rank=const.ndim)
                       if kind == "conv"
                       else layout.col_weight(rank=const.ndim, dim=dim))
                sd = fsdp_store_dim(const, avoid=dim)
                if sd is None:
                    specs[name] = use
                    record(name, "sharded",
                           f"{kind} weight: dim {dim} over model={m}")
                else:
                    # SNIPPETS [3] embeddings layout: use-sharded over
                    # model AND stored row-sharded over fsdp — at rest
                    # each device holds 1/(f*m) of the tensor
                    specs[name] = layout.fsdp_weight(
                        rank=const.ndim, dim=sd, use_spec=use)
                    record(name, "fsdp",
                           f"{kind} weight: dim {dim} over model={m}, "
                           f"stored over fsdp={f} on dim {sd}; fsdp axis "
                           f"all-gathered on use")
                continue
            if m > 1:
                record(name, "replicated",
                       f"{kind} dim {dim} size {const.shape[dim]} not "
                       f"divisible by model={m}")
                continue
            # model axis unpopulated (fsdp-only layout): storage sharding
            # is still worth it for weight-role tensors
            sd = fsdp_store_dim(const, None)
            if sd is None:
                record(name, "replicated",
                       f"{kind} weight: no dim divisible by fsdp={f}")
                continue
            specs[name] = layout.fsdp_weight(rank=const.ndim, dim=sd)
            record(name, "fsdp",
                   f"{kind} weight: stored over fsdp={f} on dim {sd}, "
                   f"all-gathered on use")
        for name in self.constants:
            if name not in roles:
                record(name, "replicated",
                       "no weight-role consumer (bias / norm param / "
                       "unconsumed initializer)")
        return specs

    def placement_report(self) -> List[Dict[str, Any]]:
        """Per-initializer residency decisions under the tensor-parallel
        layout, largest tensor first — each row names the tensor, its
        host-side footprint, and WHY the planner sharded or replicated it.
        Empty without a populated model or fsdp axis (nothing to shard
        across). The SPMD lint pack (``analysis/rules_spmd.py`` SMT110)
        turns every large replicated row into a finding, so the planner's
        silent "replicate on conflict" choices surface before they cost
        HBM; ``fsdp`` rows document the store-over-fsdp +
        gather-at-consumer placements (reason strings carry the stored
        dim and axis sizes)."""
        return sorted((dict(r) for r in self._const_plan),
                      key=lambda r: (-r["nbytes"], r["tensor"]))

    # -- execution ---------------------------------------------------------------

    def _validate_ops(self, graph: GraphProto) -> None:
        missing = sorted({n.op_type for n in graph.node
                          if n.op_type not in OPS
                          and (n.domain, n.op_type) not in self.functions})
        for f in self.functions.values():
            missing += [n.op_type for n in f.node
                        if n.op_type not in OPS
                        and (n.domain, n.op_type) not in self.functions]
        if missing:
            raise NotImplementedError(
                f"ONNX ops not supported by the importer: {sorted(set(missing))}. "
                f"Supported: {len(OPS)} ops; extend synapseml_tpu/onnx/ops.py."
            )

    def _cast_policy_in(self, x):
        import jax.numpy as jnp

        dtype = getattr(x, "dtype", None)
        if self.dtype_policy == "bfloat16" and dtype is not None and jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(x, dtype=jnp.bfloat16)
        return x

    def _run_positional(self, *arrays):
        """The program: the feeds in ``input_names``' order, then the weights
        in ``_weight_names``' order. Called with the feeds alone it takes the
        placed weights from ``constants`` (they are then literals of that
        trace: for a caller that lowers the function itself)."""
        import jax.numpy as jnp

        n_feeds = len(self.input_names)
        weights = dict(zip(self._weight_names,
                           arrays[n_feeds:] or self._weights))
        env: Dict[str, Any] = {"": None}
        for name, const in self.constants.items():
            if name in weights:
                v = weights[name]  # cast and placed by _place_weights
            elif self.dtype_policy == "bfloat16" \
                    and _is_floating(const.dtype):
                v = const.astype(np.dtype("bfloat16"))
            else:
                v = const
            if name in self._const_specs:
                # re-pin the tensor-parallel placement inside the traced
                # program so GSPMD partitions the consuming matmul however
                # jit chose to stage the closure constant
                spec = self._const_specs[name]
                v = self.layout.constraint(jnp.asarray(v), spec)
                use = self.layout.use_spec(spec) \
                    if hasattr(self.layout, "use_spec") else spec
                if use != spec:
                    # stored-over-fsdp weight: all-gather-on-use. The
                    # re-pin to the use spec makes GSPMD insert the
                    # all-gather here, so the gathered copy is a transient
                    # of this step — at rest only the row shards persist.
                    v = self.layout.gather_for_use(v, spec)
            env[name] = v
        for name, arr in zip(self.input_names, arrays[:n_feeds]):
            env[name] = self._cast_policy_in(arr)
        # float32 bytes handed from node to node under the bfloat16 policy,
        # summed while the graph is traced (so once a compiled program)
        handoff = [0] if self.dtype_policy == "bfloat16" else None
        # what ops say of the program being traced (ops._note)
        notes: Dict[str, Any] = {}
        self._run_graph(self.graph, env, handoff=handoff, notes=notes)
        from ..observability.metrics import get_registry

        self._record_notes(notes)
        if handoff is not None:
            get_registry().gauge(
                "smt_onnx_float32_handoff_bytes",
                "bytes of float32 tensors one node hands another in the "
                "newest program traced under dtype_policy='bfloat16'",
                ("fn",), merge="max").labels(self._jit.name).set(handoff[0])
        outs = []
        for name in self.output_names:
            v = env[name]
            if self.dtype_policy == "bfloat16" and hasattr(v, "dtype") and v.dtype == jnp.bfloat16:
                v = v.astype(jnp.float32)
            outs.append(jnp.asarray(v))
        return tuple(outs)

    def _record_notes(self, notes: Dict[str, Any]) -> None:
        """Once a traced program: what its ops noted of how they were
        lowered and what they hold, into the metric of each of
        ``ops.NOTE_FAMILIES``."""
        from ..observability.metrics import get_registry

        reg, fn = get_registry(), self._jit.name
        for family, declare in NOTE_FAMILIES.items():
            metric = declare(reg)
            for labels, amount in notes.get(family, {}).items():
                series = metric.labels(fn, *labels)
                if metric.type == "gauge":
                    series.set(amount)
                else:
                    series.inc(amount)

    def _run_function(self, fdef, call, env: Dict[str, Any],
                      handoff: "List[int] | None" = None,
                      notes: "Dict[str, Any] | None" = None) -> None:
        """Inline-expand a model-local function call: bind formal inputs,
        substitute ``ref_attr_name`` attributes from the call site (falling
        back to ``attribute_proto`` defaults, recursing into subgraph
        attributes), run the body in a private scope under the function's
        own opset, and export the formal outputs."""
        import dataclasses

        call_attrs = {a.name: a for a in call.attribute}
        for a in fdef.attribute_proto:  # declared params with defaults
            call_attrs.setdefault(a.name, a)

        def resolve_node(node):
            changed = False
            resolved = []
            for a in node.attribute:
                if a.ref_attr_name:
                    src = call_attrs.get(a.ref_attr_name)
                    if src is not None:
                        resolved.append(dataclasses.replace(src, name=a.name))
                    # absent optional attr: drop (ONNX function semantics)
                    changed = True
                elif a.g is not None or a.graphs:
                    # refs are legal inside If/Loop bodies of the function
                    a2 = dataclasses.replace(
                        a,
                        g=resolve_graph(a.g) if a.g is not None else None,
                        graphs=[resolve_graph(g) for g in a.graphs])
                    resolved.append(a2)
                    changed = True
                else:
                    resolved.append(a)
            return dataclasses.replace(node, attribute=resolved) if changed \
                else node

        def resolve_graph(g):
            return dataclasses.replace(g, node=[resolve_node(n)
                                                for n in g.node])

        fenv: Dict[str, Any] = {"": None}
        for formal in fdef.input:  # trailing optionals may be uncalled
            fenv[formal] = None
        for formal, actual in zip(fdef.input, call.input):
            fenv[formal] = env[actual] if actual else None
        body = GraphProto(
            node=[resolve_node(n) for n in fdef.node],
            output=[ValueInfo(name=o) for o in fdef.output],
        )
        # the body executes under ITS opset (pre-13 bodies keep e.g.
        # attribute-form Unsqueeze even inside an opset-13+ model)
        self._run_graph(body, fenv,
                        opset=fdef.opset_imports.get("") or None,
                        handoff=handoff, notes=notes)
        for formal, actual in zip(fdef.output, call.output):
            if actual:
                env[actual] = fenv[formal]

    def _run_graph(self, graph: GraphProto, env: Dict[str, Any],
                   opset: "int | None" = None,
                   handoff: "List[int] | None" = None,
                   notes: "Dict[str, Any] | None" = None) -> None:
        import jax
        import jax.numpy as jnp

        opset = self.opset if opset is None else opset
        accum = jnp.float32 if self.dtype_policy == "bfloat16" else None
        # names this graph's nodes make that no node has been seen to read yet
        unread = ({o for n in graph.node for o in n.output}
                  if handoff is not None else set())

        def subgraph_runner(sub: GraphProto):
            """The subgraph as a function of its own inputs (``Loop``'s body;
            ``If``'s branches have none); every other name it reads is this
            graph's, weights among them."""
            def run(*values):
                sub_env = dict(env)
                sub_env.update(zip((vi.name for vi in sub.input), values))
                self._run_graph(sub, sub_env, opset=opset, handoff=handoff,
                                notes=notes)
                vals = [sub_env[o.name] for o in sub.output]
                return vals[0] if len(vals) == 1 else tuple(vals)

            return run

        for node in graph.node:
            # every op a node stages carries ``<op_type>.<node name>`` in
            # its scope path (trace time only): a profile of the compiled
            # program names device ops by the graph's own nodes
            scope = (f"{node.op_type}."
                     f"{node.name or next(filter(None, node.output), '')}")
            for i in unread.intersection(node.input):
                unread.discard(i)  # a tensor counts once, at its first reader
                v = env[i]
                if not _is_const(v) and getattr(v, "dtype", None) == jnp.float32:
                    handoff[0] += v.size * 4
            fdef = self.functions.get((node.domain, node.op_type))
            # builtins win only in the standard domains; a custom-domain
            # function whose name collides with a builtin must still expand
            if fdef is not None and (node.domain not in ("", "ai.onnx")
                                     or node.op_type not in OPS):
                with jax.named_scope(scope):
                    self._run_function(fdef, node, env, handoff, notes)
                continue
            try:
                fn = OPS[node.op_type]
            except KeyError:
                raise NotImplementedError(f"unsupported ONNX op {node.op_type}") from None
            inputs = [env[i] if i else None for i in node.input]
            ctx = {
                "op_type": node.op_type,
                "node_name": node.name,
                "opset": opset,
                "n_outputs": len(node.output),
                "accum_dtype": accum,
                "subgraph_runner": subgraph_runner,
                "external_dir": self._external_dir,
                "notes": notes,
            }
            # Constant folding: all-constant inputs => evaluate OUTSIDE the
            # trace (omnistaging would otherwise stage jnp ops on concrete
            # values into tracers) and pin outputs as numpy, so shape chains
            # (Shape -> Gather/Mod/Add -> Reshape -> Slice.ends) stay static.
            const_in = (all(v is None or _is_const(v) for v in inputs)
                        and node.op_type != "Dropout")
            try:
                if const_in:
                    with jax.ensure_compile_time_eval():
                        out = fn(inputs, node.attrs(), ctx)
                else:
                    with jax.named_scope(scope):
                        out = fn(inputs, node.attrs(), ctx)
            except Exception as e:
                raise type(e)(
                    f"while executing node {node.name or '?'} ({node.op_type}) "
                    f"inputs={node.input}: {e}"
                ) from e
            outs = out if isinstance(out, tuple) else (out,)
            if const_in:
                pinned = []
                for o in outs:
                    try:
                        pinned.append(np.asarray(o))
                    except Exception:
                        pinned.append(o)  # traced despite const inputs (subgraph capture)
                outs = tuple(pinned)
            for name, val in zip(node.output, outs):
                if name:
                    env[name] = val


def load_model(path_or_bytes, dtype_policy: str = "float32") -> OnnxFunction:
    """Load an ``.onnx`` file (path or bytes) into an executable function.

    Loading by PATH resolves external-data tensors (``data_location=EXTERNAL``,
    the real-exporter format past protobuf's 2GB limit) relative to the
    model's directory; from raw bytes pass ``external_data_dir`` to
    :class:`OnnxFunction` directly."""
    if isinstance(path_or_bytes, (bytes, bytearray, memoryview)):
        data = bytes(path_or_bytes)
        ext_dir = None
    else:
        import os

        with open(path_or_bytes, "rb") as f:
            data = f.read()
        ext_dir = os.path.dirname(os.path.abspath(path_or_bytes))
    return OnnxFunction(data, dtype_policy=dtype_policy,
                        external_data_dir=ext_dir)
