"""Jaxpr-level device lint: the SMT1xx rule pack.

The AST pack (SMT001–009) stops at the Python source; the class of defect
that actually costs TPU cycles lives one layer down, in the traced
program: f64 leaks that double every matmul's bandwidth, host callbacks
that stall the device per step, transfers staged inside jit, collectives
over axis names no mesh declares, closure constants bloating every
executable's HBM footprint, and weak-typed scalar args churning the
``profiled_jit`` AOT cache (``smt_recompiles_total{cause="weak_type"}``).

This pack **abstract-evals** the repo's ``profiled_jit``-registered hot
entry points under canonical bench-lane-shaped signatures
(``jax.make_jaxpr`` — tracing only, no device execution, runs on any
backend) and walks the resulting jaxprs. Tracing happens under
``jax.enable_x64`` so *latent* f64 leaks — dtype-less
``jnp.zeros(...)``/numpy-f64 constants that today only stay f32 by the
grace of the global x64 flag — surface as findings instead of shipping.

Import discipline (enforced by ``tests/test_import_hygiene.py``): this
module is stdlib-only at import — jax is reached exclusively inside
:func:`run_device_pack` / the entry builders, so the default lint CLI and
``--list-rules`` stay jax-free; only ``--device`` pays for a trace.

Findings flow through the ordinary engine plumbing: rule codes register
in ``engine.RULES`` (so ``--select SMT101`` and ``--list-rules`` work),
findings anchor at the entry point's defining ``file:line`` and are
subject to the same ``LINT_ACKS.md`` waiver rows and the zero-unwaived
gate as the AST pack.
"""

from __future__ import annotations

import dataclasses
import inspect
import math
import os
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .engine import Finding, Module, Rule, register

__all__ = [
    "DeviceEntry",
    "DeviceRule",
    "DEVICE_RULES",
    "default_device_entries",
    "trace_entry",
    "run_device_pack",
]

# closure constants above this footprint flag SMT105 unless the entry
# overrides (ONNX serving deliberately bakes model weights into the
# executable — entries carrying real models raise their own limit)
DEFAULT_CONST_BYTES = 256 << 20


@dataclasses.dataclass
class DeviceEntry:
    """One hot entry point to abstract-eval.

    ``build()`` runs under jax (lazily) and returns a dict with:

    - ``fn``: the callable to trace (statics already bound);
    - ``args`` / ``kwargs``: the canonical bench-lane-shaped example
      arguments (arrays stay abstract — tracing only);
    - optionally ``anchor``: ``(path, line)`` overriding the source
      anchor derived from ``fn`` (needed for shard_map-wrapped fns).
    """

    name: str
    build: Callable[[], Dict[str, Any]]
    policy: str = "float32"          # declared dtype policy (f64 never OK)
    mesh_axes: Tuple[str, ...] = ()  # declared mesh axis names
    const_bytes_limit: int = DEFAULT_CONST_BYTES
    hot: bool = True                 # host callbacks are findings only here


class TracedEntry:
    """A :class:`DeviceEntry` plus its traced ClosedJaxpr and anchor.

    ``x64_error`` is set when the entry could only trace with x64 OFF —
    SMT101's latent-leak visibility is lost for it, which is itself a
    (waivable) SMT101 finding, never a silent downgrade."""

    def __init__(self, entry: DeviceEntry, closed, anchor: Tuple[str, int],
                 x64_error: Optional[str] = None):
        self.entry = entry
        self.closed = closed         # jax ClosedJaxpr
        self.anchor = anchor         # (path, line) findings anchor
        self.x64_error = x64_error


# ---------------------------------------------------------------------------
# jaxpr traversal helpers (duck-typed: no jax import needed at call time
# beyond the objects already in hand)
# ---------------------------------------------------------------------------

def _const_nbytes(const) -> int:
    """Footprint of one ``ClosedJaxpr.consts`` entry. jax 0.9 hands numpy
    closure constants over wrapped (``TypedNdArray``): shape and dtype, no
    ``nbytes``."""
    nbytes = getattr(const, "nbytes", None)
    if nbytes is None:
        shape = getattr(const, "shape", None)
        itemsize = getattr(getattr(const, "dtype", None), "itemsize", None)
        nbytes = (math.prod(shape) * itemsize
                  if shape is not None and itemsize else 0)
    return int(nbytes)


def _sub_jaxprs(value) -> Iterable[Any]:
    """Jaxpr objects hiding inside one eqn param value (pjit carries a
    ClosedJaxpr, cond a tuple of branches, shard_map a bare Jaxpr)."""
    if value is None:
        return
    if hasattr(value, "eqns"):               # bare Jaxpr
        yield value
    elif hasattr(value, "jaxpr") and hasattr(getattr(value, "jaxpr"),
                                             "eqns"):  # ClosedJaxpr
        yield value.jaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Every eqn in ``jaxpr`` and, recursively, in any sub-jaxpr carried
    by an eqn's params (pjit / scan / cond / while / shard_map / pallas)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from iter_eqns(sub)


def _aval_dtype_name(aval) -> Optional[str]:
    dtype = getattr(aval, "dtype", None)
    return getattr(dtype, "name", None)


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------

DEVICE_RULES: Dict[str, "DeviceRule"] = {}


def register_device(cls):
    """Register in BOTH the engine registry (``--select``/listing/waivers)
    and the device-pack registry (what :func:`run_device_pack` runs)."""
    register(cls)
    inst = DEVICE_RULES[cls.code] = cls()
    return cls


class DeviceRule(Rule):
    """A rule over traced entry points instead of source modules. The AST
    hook is inert — device rules only produce findings when the device
    pass runs (``--device``). ``ast_active = False`` tells the engine an
    AST-only run cannot judge these rules' waiver rows stale."""

    ast_active = False

    def check(self, module: Module) -> Iterable[Finding]:
        return []

    def check_entry(self, traced: TracedEntry) -> Iterable[Finding]:
        raise NotImplementedError  # pragma: no cover

    def entry_finding(self, traced: TracedEntry, message: str) -> Finding:
        path, line = traced.anchor
        return Finding(path=path, line=line, col=1, code=self.code,
                       message=f"[{traced.entry.name}] {message}")


@register_device
class F64Leak(DeviceRule):
    """SMT101 — float64 values in a hot entry point's traced program.

    TPUs have no f64 ALUs: every f64 op emulates at a many-x slowdown and
    doubles bandwidth, silently defeating the bf16/f32 policy. Entries are
    traced under ``enable_x64`` so the LATENT leaks (dtype-less
    ``jnp.zeros``, numpy-f64 closure constants) that the global x64=off
    flag currently papers over are caught before someone runs with x64 on.
    Fix: pin dtypes explicitly (``jnp.zeros(..., jnp.float32)``).
    """

    code = "SMT101"
    name = "device-f64-leak"
    rationale = ("f64 in a jitted hot path emulates on TPU and defeats "
                 "the bf16/f32 dtype policy")
    _MAX_REPORTS = 3

    def check_entry(self, traced: TracedEntry) -> Iterable[Finding]:
        findings: List[Finding] = []
        if traced.x64_error:
            # the x64 trace failing is USUALLY a latent dtype conflict —
            # exactly what this rule hunts; surface it as a waivable
            # finding instead of silently losing x64 visibility
            findings.append(self.entry_finding(
                traced,
                f"entry could not trace under enable_x64 (latent-f64 "
                f"visibility lost; the failure is often itself a dtype "
                f"conflict): {traced.x64_error}"))
        seen: Set[str] = set()
        hits = 0
        for i, const in enumerate(getattr(traced.closed, "consts", ())):
            if getattr(getattr(const, "dtype", None), "name", "") == "float64":
                hits += 1
                if len(findings) < self._MAX_REPORTS:
                    findings.append(self.entry_finding(
                        traced,
                        f"closure constant #{i} (shape "
                        f"{getattr(const, 'shape', '?')}) is float64; pin "
                        f"it to float32/bfloat16"))
        for eqn in iter_eqns(traced.closed.jaxpr):
            for var in eqn.outvars:
                if _aval_dtype_name(getattr(var, "aval", None)) == "float64":
                    hits += 1
                    prim = getattr(eqn.primitive, "name", "?")
                    if prim not in seen and len(findings) < self._MAX_REPORTS:
                        seen.add(prim)
                        findings.append(self.entry_finding(
                            traced,
                            f"primitive '{prim}' produces float64 under "
                            f"x64 (policy {traced.entry.policy}); pin the "
                            f"dtype explicitly (e.g. jnp.zeros(..., "
                            f"jnp.float32))"))
                    break
        if hits > len(findings) and findings:
            findings[-1] = dataclasses.replace(
                findings[-1],
                message=findings[-1].message
                + f" ({hits} f64 sites total in this entry)")
        return findings


@register_device
class HostCallbackInJit(DeviceRule):
    """SMT102 — host callbacks staged into a hot jitted program.

    ``pure_callback`` / ``io_callback`` / ``jax.debug.print`` /
    ``debug_callback`` round-trip device->host->device EVERY step; one
    stray debug print in a scan body serializes the whole pipeline behind
    the host. Debug-only uses belong outside the jitted path or behind a
    flag that drops them from the traced program.
    """

    code = "SMT102"
    name = "host-callback-in-jit"
    rationale = ("host callbacks in a jitted hot path stall the device on "
                 "a host round-trip every step")

    _CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                       "debug_print", "outside_call", "host_callback_call"}

    def check_entry(self, traced: TracedEntry) -> Iterable[Finding]:
        if not traced.entry.hot:
            return []
        findings: List[Finding] = []
        seen: Set[str] = set()
        for eqn in iter_eqns(traced.closed.jaxpr):
            prim = getattr(eqn.primitive, "name", "?")
            if prim in self._CALLBACK_PRIMS and prim not in seen:
                seen.add(prim)
                findings.append(self.entry_finding(
                    traced,
                    f"host callback '{prim}' staged inside the jitted hot "
                    f"path; move it outside the traced program"))
        return findings


@register_device
class TransferInsideJit(DeviceRule):
    """SMT103 — explicit device transfers staged inside jit.

    ``jax.device_put`` under an active trace records a transfer/placement
    op in the compiled program — the placement should happen once at the
    call boundary (as every trainer here does before its step loop), not
    per executed step where it defeats XLA's layout freedom.
    """

    code = "SMT103"
    name = "transfer-inside-jit"
    rationale = ("device_put inside a jitted program re-stages placement "
                 "per step; place once at the call boundary")

    _TRANSFER_PRIMS = {"device_put", "copy"}

    def check_entry(self, traced: TracedEntry) -> Iterable[Finding]:
        findings: List[Finding] = []
        count = 0
        for eqn in iter_eqns(traced.closed.jaxpr):
            prim = getattr(eqn.primitive, "name", "?")
            if prim in self._TRANSFER_PRIMS:
                count += 1
                if count == 1:
                    findings.append(self.entry_finding(
                        traced,
                        f"'{prim}' staged inside the jitted program; move "
                        f"placement outside the traced fn"))
        if count > 1 and findings:
            findings[0] = dataclasses.replace(
                findings[0],
                message=findings[0].message + f" ({count} sites)")
        return findings


@register_device
class CollectiveAxisMismatch(DeviceRule):
    """SMT104 — a collective over an axis name the entry does not declare.

    ``psum``/``ppermute``/``all_to_all`` bind an axis NAME resolved at run
    time against the enclosing mesh; a typo'd or stale name is invisible
    until a pod run dies (or worse, silently reduces over the wrong
    axis when meshes nest). Every entry declares its mesh axes
    (``DeviceEntry.mesh_axes``); collectives must stay inside them.
    """

    code = "SMT104"
    name = "collective-axis-mismatch"
    rationale = ("collectives over undeclared axis names fail (or reduce "
                 "wrongly) only once a real mesh is attached")

    _COLLECTIVE_PRIMS = {"psum", "pmax", "pmin", "ppermute", "pbroadcast",
                         "all_gather", "all_to_all", "reduce_scatter",
                         "axis_index"}

    @staticmethod
    def _axis_names(eqn) -> List[str]:
        names: List[str] = []
        for key in ("axes", "axis_name"):
            v = eqn.params.get(key)
            if v is None:
                continue
            for name in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(name, str):
                    names.append(name)
        return names

    def check_entry(self, traced: TracedEntry) -> Iterable[Finding]:
        declared = set(traced.entry.mesh_axes)
        findings: List[Finding] = []
        seen: Set[Tuple[str, str]] = set()
        for eqn in iter_eqns(traced.closed.jaxpr):
            prim = getattr(eqn.primitive, "name", "?")
            if prim not in self._COLLECTIVE_PRIMS:
                continue
            for axis in self._axis_names(eqn):
                if axis in declared or (prim, axis) in seen:
                    continue
                seen.add((prim, axis))
                findings.append(self.entry_finding(
                    traced,
                    f"collective '{prim}' binds axis name {axis!r} but the "
                    f"entry declares mesh axes "
                    f"{sorted(declared) if declared else 'NONE'}"))
        return findings


@register_device
class HbmBloatConstant(DeviceRule):
    """SMT105 — closure constants above the HBM-bloat threshold.

    Arrays captured by closure are baked into EVERY compiled executable of
    the entry (one copy per shape signature) and live in HBM for the
    executable's lifetime — ``smt_device_hbm_peak_bytes`` pays for them
    whether or not the entry runs. Big operands belong in the argument
    list (donated or sharded); only genuine model weights (ONNX) get a
    raised per-entry limit.
    """

    code = "SMT105"
    name = "hbm-bloat-constant"
    rationale = ("closure constants replicate into every compiled "
                 "executable and squat in HBM for its lifetime")

    def check_entry(self, traced: TracedEntry) -> Iterable[Finding]:
        limit = traced.entry.const_bytes_limit
        findings: List[Finding] = []
        for i, const in enumerate(getattr(traced.closed, "consts", ())):
            nbytes = _const_nbytes(const)
            if nbytes > limit:
                findings.append(self.entry_finding(
                    traced,
                    f"closure constant #{i} (shape "
                    f"{getattr(const, 'shape', '?')}, "
                    f"{nbytes / (1 << 20):.1f} MiB) exceeds the "
                    f"{limit / (1 << 20):.0f} MiB HBM-bloat threshold; "
                    f"pass it as an argument instead"))
        return findings


@register_device
class WeakTypeChurn(DeviceRule):
    """SMT106 — weak-typed scalar arguments in a hot entry's signature.

    A python scalar argument traces as a WEAK-typed aval; the same call
    site passing a numpy scalar (or a jax array) later produces a
    different abstract signature and recompiles — exactly what
    ``smt_recompiles_total{cause="weak_type"}`` counts in production.
    When the live registry has recorded such churn for the entry, the
    finding says so; either way the fix is the same: coerce scalars at
    the boundary (``jnp.float32(x)`` / ``np.asarray(x, np.float32)``) or
    make the argument static.
    """

    code = "SMT106"
    name = "weak-type-churn"
    rationale = ("weak-typed scalar args flip the abstract signature "
                 "between python/numpy callers and churn the AOT cache")

    @staticmethod
    def _live_weak_type_recompiles() -> Dict[str, float]:
        """fn -> recorded weak_type recompiles from the process registry
        (``observability`` is stdlib-only; absence of data is fine)."""
        try:
            from ..observability import get_registry

            fam = get_registry().snapshot()["families"].get(
                "smt_recompiles_total")
            if not fam:
                return {}
            li = {n: i for i, n in enumerate(fam["labelnames"])}
            out: Dict[str, float] = {}
            for s in fam["series"]:
                if s["labels"][li["cause"]] == "weak_type":
                    fn = s["labels"][li["fn"]]
                    out[fn] = out.get(fn, 0.0) + float(s["value"])
            return out
        except Exception:
            return {}

    def check_entry(self, traced: TracedEntry) -> Iterable[Finding]:
        findings: List[Finding] = []
        churn = self._live_weak_type_recompiles().get(traced.entry.name)
        for i, aval in enumerate(getattr(traced.closed, "in_avals", ())):
            if getattr(aval, "weak_type", False):
                extra = (f"; profiling has recorded {churn:.0f} weak_type "
                         f"recompile(s) for this entry" if churn else "")
                findings.append(self.entry_finding(
                    traced,
                    f"argument #{i} ({aval}) is weak-typed — a python "
                    f"scalar here recompiles against numpy/array callers; "
                    f"coerce at the boundary or make it static{extra}"))
        return findings


# ---------------------------------------------------------------------------
# canonical entry points
# ---------------------------------------------------------------------------

def _build_flash_entry() -> Dict[str, Any]:
    """``flash.attention`` (``parallel/flash._flash_impl``, what
    ``flash_attention`` jits) under a shrunk grouped-query signature at the
    head size the executor's cells bring: (B, S, H, D) bf16 with 4 / 2 heads
    of 128, so the kernel reads its operands where they lie, with the
    statics bound the way ``flash_attention`` binds them."""
    import functools

    import numpy as np

    from ..parallel import flash

    q = np.zeros((1, 256, 4, 128), np.dtype("bfloat16"))
    k = np.zeros((1, 256, 2, 128), np.dtype("bfloat16"))
    v = np.zeros((1, 256, 2, 128), np.dtype("bfloat16"))
    # interpret=True: the kernel body traces identically, and the Mosaic
    # compiler-params path needs TPU plugin versions the lint host may
    # not have — tracing is the point here, not lowering
    fn = functools.partial(flash._flash_impl, causal=True, block_q=128,
                           block_k=128, interpret=True)
    return {"fn": fn, "args": (q, k, v), "anchor_obj": flash._flash_impl}


def _tiny_mlp_bytes():
    """A small MatMul+Add+Relu+MatMul graph (the shape of the codegen /
    test_onnx models) through the repo's own builder — jax-free."""
    import numpy as np

    from ..onnx import builder
    from ..onnx.wire import serialize_model

    rng = np.random.default_rng(0)
    w1 = rng.normal(size=(16, 32)).astype(np.float32)
    b1 = rng.normal(size=(32,)).astype(np.float32)
    w2 = rng.normal(size=(32, 8)).astype(np.float32)
    g = builder.make_graph(
        [builder.constant_node("w1", w1),
         builder.constant_node("b1", b1),
         builder.constant_node("w2", w2),
         builder.node("MatMul", ["x", "w1"], ["h0"]),
         builder.node("Add", ["h0", "b1"], ["h1"]),
         builder.node("Relu", ["h1"], ["h2"]),
         builder.node("MatMul", ["h2", "w2"], ["y"])],
        "mlp",
        [builder.value_info("x", np.float32, [None, 16])],
        [builder.value_info("y", np.float32, [None, 8])])
    return serialize_model(builder.make_model(g))


def _build_onnx_entry(policy: str) -> Callable[[], Dict[str, Any]]:
    def build() -> Dict[str, Any]:
        import numpy as np

        from ..onnx.importer import OnnxFunction

        of = OnnxFunction(_tiny_mlp_bytes(), dtype_policy=policy)
        x = np.zeros((8, 16), np.float32)
        return {"fn": of._run_positional, "args": (x,)}

    return build


def _gbdt_grow_inputs():
    import numpy as np

    from ..gbdt.grow import TreeConfig

    rng = np.random.default_rng(0)
    n, d, B = 64, 4, 8
    binned = rng.integers(0, B, size=(n, d)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, np.float32)
    w = np.ones(n, np.float32)
    fmask = np.ones(d, np.float32)
    return binned, g, h, w, fmask, TreeConfig, B


def _build_gbdt_grow_entry() -> Dict[str, Any]:
    """``gbdt.iter``'s kernel (``grow.grow_tree``) in single-chip
    data-parallel shape — the Adult-scale bench lane shrunk."""
    from ..gbdt import grow

    binned, g, h, w, fmask, TreeConfig, B = _gbdt_grow_inputs()
    cfg = TreeConfig(n_bins=B, num_leaves=4)

    def fn(b, gg, hh, ww, fm):
        return grow.grow_tree(b, gg, hh, ww, fm, cfg)

    return {"fn": fn, "args": (binned, g, h, w, fmask),
            "anchor_obj": grow.grow_tree}


def _build_gbdt_voting_entry() -> Dict[str, Any]:
    """``gbdt.iter_sharded`` in voting-parallel mode over a 1-device mesh
    (the PV-tree vote path: per-shard top-k vote, psum'd candidates) —
    the distributed configuration SMT104/SMT101 most need to see."""
    import jax

    from ..gbdt import grow
    from ..runtime.layout import SpecLayout

    binned, g, h, w, fmask, TreeConfig, B = _gbdt_grow_inputs()
    cfg = TreeConfig(n_bins=B, num_leaves=4, parallelism="voting", top_k=2)
    layout = SpecLayout.build(devices=jax.devices("cpu")[:1],
                              model_axis=None)
    data, rep = layout.batch(), layout.replicated()

    def body(b, gg, hh, ww, fm):
        return grow.grow_tree(b, gg, hh, ww, fm, cfg,
                              axis_name=layout.data_axis)

    fn = layout.shard_map(body,
                          in_specs=(data, data, data, data, rep),
                          out_specs=(rep, data), check=False)
    return {"fn": fn, "args": (binned, g, h, w, fmask),
            "anchor_obj": grow.grow_tree}


def _build_gbdt_feature_parallel_entry() -> Dict[str, Any]:
    """``gbdt.iter_sharded`` over a 2-D ``(data, model)`` ``SpecLayout``
    mesh — the feature-parallel histogram path (features over ``model``,
    stats ``psum``'d per axis). The jaxpr binds BOTH axis names, so
    SMT104 verifies collectives against a 2-D declaration."""
    import jax

    from ..gbdt import grow
    from ..runtime.layout import SpecLayout

    binned, g, h, w, fmask, TreeConfig, B = _gbdt_grow_inputs()
    cfg = TreeConfig(n_bins=B, num_leaves=4)
    layout = SpecLayout.build(data=1, model=1,
                              devices=jax.devices("cpu")[:1])
    data, rep = layout.batch(), layout.replicated()

    def body(b, gg, hh, ww, fm):
        return grow.grow_tree(b, gg, hh, ww, fm, cfg,
                              axis_name=layout.data_axis,
                              model_axis_name=layout.model_axis)

    fn = layout.shard_map(body,
                          in_specs=(data, data, data, data, rep),
                          out_specs=(rep, data), check=False)
    return {"fn": fn, "args": (binned, g, h, w, fmask),
            "anchor_obj": grow.grow_tree}


def default_device_entries() -> List[DeviceEntry]:
    """The canonical hot entry points, one per ``profiled_jit`` family the
    bench lanes exercise (docs/analysis.md lists the mapping)."""
    return [
        DeviceEntry("flash.attention", _build_flash_entry,
                    policy="bfloat16"),
        DeviceEntry("onnx.mlp", _build_onnx_entry("float32"),
                    policy="float32"),
        DeviceEntry("onnx.mlp[bf16]", _build_onnx_entry("bfloat16"),
                    policy="bfloat16"),
        DeviceEntry("gbdt.grow", _build_gbdt_grow_entry,
                    policy="float32"),
        DeviceEntry("gbdt.grow[voting,sharded]", _build_gbdt_voting_entry,
                    policy="float32", mesh_axes=("data",)),
        DeviceEntry("gbdt.grow[feature-parallel,2d]",
                    _build_gbdt_feature_parallel_entry,
                    policy="float32", mesh_axes=("data", "model")),
    ]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _repo_root() -> str:
    """The checkout root (three levels above this file) — the same anchor
    LINT_ACKS.md lives at, so device findings stay waiver-matchable even
    when the caller passes no root (e.g. ``--no-acks`` CLI runs)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _anchor_of(built: Dict[str, Any], root: Optional[str]
               ) -> Tuple[str, int]:
    obj = built.get("anchor_obj") or built.get("fn")
    if "anchor" in built:
        path, line = built["anchor"]
    else:
        try:
            while hasattr(obj, "func"):  # unwrap functools.partial
                obj = obj.func
            obj = inspect.unwrap(obj)
            path = inspect.getsourcefile(obj) or "<unknown>"
            line = inspect.getsourcelines(obj)[1]
        except (TypeError, OSError):
            path, line = "<unknown>", 1
    if os.path.isabs(path) or os.path.exists(path):
        path = os.path.abspath(path)
    root_abs = os.path.abspath(root) if root else _repo_root()
    if path.startswith(root_abs + os.sep):
        path = os.path.relpath(path, root_abs)
    return path.replace(os.sep, "/"), int(line)


def trace_entry(entry: DeviceEntry, root: Optional[str] = None
                ) -> TracedEntry:
    """Abstract-eval one entry: build its fn + canonical args, trace with
    ``jax.make_jaxpr`` under ``enable_x64`` (latent-f64 visibility).
    When the x64 trace fails but a plain trace works, the failure is
    recorded on the TracedEntry — SMT101 reports it as a finding instead
    of a silent visibility downgrade. Tracing only — no compile, no
    device execution."""
    import jax

    built = entry.build()
    fn = built["fn"]
    args = built.get("args", ())
    kwargs = built.get("kwargs", {})
    x64_error = None
    try:
        with jax.enable_x64():
            closed = jax.make_jaxpr(fn)(*args, **kwargs)
    except Exception as e:
        x64_error = f"{type(e).__name__}: {e}"
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return TracedEntry(entry, closed, _anchor_of(built, root),
                       x64_error=x64_error)


def run_device_pack(entries: Optional[Sequence[DeviceEntry]] = None,
                    select: Optional[Sequence[str]] = None,
                    root: Optional[str] = None
                    ) -> Tuple[List[Finding], List[str]]:
    """Trace every entry and run the (selected) device rules over the
    jaxprs. Returns ``(findings, errors)`` — an entry whose trace fails
    is an ERROR (the gate must see it), not a silent skip."""
    codes = [c for c in (select or sorted(DEVICE_RULES))
             if c in DEVICE_RULES]
    if not codes:
        # selection excludes every device rule: don't pay for (or fail
        # on) traces that cannot produce a finding
        return [], []
    if entries is None:
        entries = default_device_entries()
    findings: List[Finding] = []
    errors: List[str] = []
    for entry in entries:
        try:
            traced = trace_entry(entry, root=root)
        except Exception as e:
            errors.append(f"device entry {entry.name!r} failed to trace: "
                          f"{type(e).__name__}: {e}")
            continue
        for code in codes:
            findings.extend(DEVICE_RULES[code].check_entry(traced))
    return findings, errors
