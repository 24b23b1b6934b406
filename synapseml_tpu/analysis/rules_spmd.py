"""SPMD static verifier: the SMT11x sharding-aware rule pack.

The device pack (SMT10x) abstract-evals entry points on ONE device; the
class of defect that costs a *mesh* lives in what GSPMD does with the
program: large tensors silently resident fully-replicated across a
populated model axis (every fsdp blocker looks like this), conflicting
``with_sharding_constraint`` chains that force an implicit reshard on a
hot path, host fallbacks that are only reachable in the mesh
configuration (``use_device_bin`` required ``mesh is None`` for three
arcs — the binning searchsorted ran on host exactly when 8 chips were
waiting, until the device-side distributed binning change made the flag
mesh-capable), and mesh-vs-single-device traces that structurally
diverge where they should not (the bisection instrument
``test_sparse_mesh_matches_single_device`` needs).

This pack traces the canonical entry points under representative
``SpecLayout``s — (1, 1), (4, 2) feature-parallel, and a (1, 2, 2)
fsdp+tensor-parallel ONNX serving layout — and walks the jaxprs with
sharding awareness. Two rules additionally run as ordinary AST rules in the
default jax-free pass (SMT112's host-fallback-guard half and SMT114's
refusal-guard inventory), so the debt they enumerate cannot silently
grow even when no one pays for a trace.

Import discipline (enforced by ``tests/test_import_hygiene.py``): this
module is stdlib-only at import — jax is reached exclusively inside
:func:`run_spmd_pack` / the entry builders / :func:`trace_spmd_entry`,
so the default lint CLI and ``--list-rules`` stay jax-free; only
``--spmd`` pays for a trace.

Findings flow through the ordinary engine plumbing: codes register in
``engine.RULES``, findings anchor at the entry's defining ``file:line``
and are subject to the same ``LINT_ACKS.md`` waiver rows and the
zero-unwaived gate as every other pack. ``tools/spmd_diff.py`` exposes
the SMT113 differential (canonicalize + diff) as a standalone CLI.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
import sys
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Set, Tuple)

from .engine import Finding, Module, Rule, register, walk_scoped
from .rules_device import (_anchor_of, _const_nbytes, _gbdt_grow_inputs,
                           _sub_jaxprs, iter_eqns)

__all__ = [
    "SpmdEntry",
    "SpmdRule",
    "SPMD_RULES",
    "default_spmd_entries",
    "differential_entry_names",
    "trace_spmd_entry",
    "run_spmd_pack",
    "canonical_lines",
    "structural_diff",
]

# a tensor resident fully-replicated across a populated model axis above
# this footprint flags SMT110 (per-entry override for entries whose
# weights are legitimately small)
DEFAULT_REPLICATED_BYTES = 1 << 20


@dataclasses.dataclass
class SpmdEntry:
    """One entry point to trace under a representative ``SpecLayout``.

    ``build()`` runs under jax (lazily) and returns a dict with:

    - ``fn`` / ``args`` / ``kwargs``: the mesh-configured callable and its
      canonical example arguments (tracing only — arrays stay abstract);
    - optionally ``single_fn`` / ``single_args`` / ``single_kwargs``: the
      SAME computation in its single-device configuration — the
      differential twin SMT112's jaxpr half and SMT113 diff against;
    - optionally ``layout``: the ``SpecLayout`` the entry traced under
      (axis sizes gate SMT110 — a 1-wide model axis replicates nothing);
    - optionally ``placement_report``: the entry's own per-tensor
      residency decisions (``OnnxFunction.placement_report()``) so SMT110
      can name the tensor and the planner decision that replicated it;
    - optionally ``anchor`` / ``anchor_obj``: the findings anchor.
    """

    name: str
    build: Callable[[], Dict[str, Any]]
    mesh_axes: Tuple[str, ...] = ()
    replicated_bytes_limit: int = DEFAULT_REPLICATED_BYTES
    hot: bool = True


class TracedSpmdEntry:
    """An :class:`SpmdEntry` plus its traced jaxpr(s) and metadata."""

    def __init__(self, entry: SpmdEntry, closed, anchor: Tuple[str, int],
                 single=None, layout=None,
                 placement: Optional[Sequence[Dict[str, Any]]] = None):
        self.entry = entry
        self.closed = closed          # mesh-configuration ClosedJaxpr
        self.single = single          # single-device ClosedJaxpr or None
        self.anchor = anchor          # (path, line) findings anchor
        self.layout = layout          # SpecLayout or None
        self.placement = list(placement or [])

    @property
    def model_size(self) -> int:
        return int(getattr(self.layout, "model_size", 1) or 1)


# ---------------------------------------------------------------------------
# jaxpr canonicalization + structural diff (SMT113 / tools/spmd_diff.py)
# ---------------------------------------------------------------------------

# primitives that MUST differ between the mesh and single-device traces —
# collectives and placement pins only exist under a mesh; stripping them
# is what makes the remaining diff signal
_STRIP_PRIMS = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "axis_index",
    "sharding_constraint",
})

# structural wrappers: descend into the sub-jaxpr without emitting a line
# (shard_map exists only mesh-side; pjit nesting is a staging artifact)
_TRANSPARENT_PRIMS = frozenset({
    "pjit", "jit", "closed_call", "core_call", "named_call", "shard_map",
    "custom_jvp_call", "custom_vjp_call", "custom_jvp_call_jaxpr",
    "custom_vjp_call_jaxpr", "remat", "remat2", "checkpoint",
})


def canonical_lines(closed) -> List[str]:
    """Render a ClosedJaxpr as a canonical line stream for diffing.

    One line per eqn, in trace order, recursing through control flow
    (scan/cond bodies are structure and stay; pjit/shard_map wrappers are
    transparent; collectives that must differ are stripped). Variable
    names never appear; dimension SIZES are alpha-renamed PER LINE in
    first-seen order (``d0, d1, ...``) so a 192-row single-device trace
    lines up with its 48-row-per-shard mesh twin when — and only when —
    the primitive structure matches. The renaming is line-local on
    purpose: a global mapping would let one extra mesh-side eqn near the
    head (the per-shard RNG fold) shift every later symbol and turn a
    4-line divergence into a whole-trace one.
    """
    lines: List[str] = []

    def rec(jaxpr) -> None:
        for eqn in jaxpr.eqns:
            prim = getattr(eqn.primitive, "name", "?")
            if prim in _STRIP_PRIMS:
                continue
            subs = [s for v in eqn.params.values() for s in _sub_jaxprs(v)]
            if prim in _TRANSPARENT_PRIMS:
                for s in subs:
                    rec(s)
                continue
            sym: Dict[Any, str] = {}

            def dim(s) -> str:
                try:
                    s = int(s)
                except (TypeError, ValueError):
                    return str(s)
                if s not in sym:
                    sym[s] = f"d{len(sym)}"
                return sym[s]

            def aval_str(v) -> str:
                aval = getattr(v, "aval", None)
                if aval is None:
                    return "?"
                dt = getattr(getattr(aval, "dtype", None), "name", "?")
                shape = getattr(aval, "shape", ())
                return f"{dt}[{','.join(dim(s) for s in shape)}]"

            ins = ",".join(aval_str(v) for v in eqn.invars)
            outs = ",".join(aval_str(v) for v in eqn.outvars)
            lines.append(f"{prim}({ins})->({outs})")
            for s in subs:
                rec(s)

    rec(closed.jaxpr)
    return lines


def structural_diff(mesh_lines: Sequence[str], single_lines: Sequence[str]
                    ) -> Optional[Dict[str, Any]]:
    """Structurally divergent regions between two canonical streams.

    A real LCS diff (``difflib``), not prefix/suffix trimming: the
    canonical mesh-side extra region (the per-shard RNG fold) sits at the
    very HEAD of the trace, where prefix matching would report the entire
    trace as divergent. Returns ``None`` when the streams are identical,
    else a dict naming the FIRST divergence — ``index`` (eqns shared
    before it), ``mesh_only`` / ``single_only`` line runs,
    ``common_suffix`` (eqns shared after the LAST divergence) — plus the
    full ``hunks`` list for the CLI.
    """
    import difflib

    a, b = list(mesh_lines), list(single_lines)
    sm = difflib.SequenceMatcher(None, a=a, b=b, autojunk=False)
    hunks = [{"mesh_index": i1, "single_index": j1,
              "mesh_only": a[i1:i2], "single_only": b[j1:j2]}
             for tag, i1, i2, j1, j2 in sm.get_opcodes() if tag != "equal"]
    if not hunks:
        return None
    first, last = hunks[0], hunks[-1]
    return {
        "index": first["mesh_index"],
        "common_suffix": len(a) - (last["mesh_index"]
                                   + len(last["mesh_only"])),
        "mesh_only": first["mesh_only"],
        "single_only": first["single_only"],
        "hunks": hunks,
    }


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------

SPMD_RULES: Dict[str, "SpmdRule"] = {}


def register_spmd(cls):
    """Register in BOTH the engine registry (``--select``/listing/waivers)
    and the spmd-pack registry (what :func:`run_spmd_pack` runs)."""
    register(cls)
    inst = SPMD_RULES[cls.code] = cls()
    return cls


class SpmdRule(Rule):
    """A rule over layout-parameterized traced entries. The AST hook is
    inert unless a subclass opts in (``ast_active = True``) — the engine
    uses the flag to decide which waiver rows a jax-free run may judge
    stale."""

    ast_active = False  # pure jaxpr rules produce nothing in AST runs

    def check(self, module: Module) -> Iterable[Finding]:
        return []

    def check_entry(self, traced: TracedSpmdEntry) -> Iterable[Finding]:
        raise NotImplementedError  # pragma: no cover

    def entry_finding(self, traced: TracedSpmdEntry, message: str) -> Finding:
        path, line = traced.anchor
        return Finding(path=path, line=line, col=1, code=self.code,
                       message=f"[{traced.entry.name}] {message}")


def _spec_axis_names(spec) -> Set[str]:
    """Axis names a PartitionSpec actually binds (entries are str, tuple
    of str, or None)."""
    names: Set[str] = set()
    for part in tuple(spec or ()):
        if isinstance(part, str):
            names.add(part)
        elif isinstance(part, (tuple, list)):
            names.update(p for p in part if isinstance(p, str))
    return names


@register_spmd
class ReplicatedResidency(SpmdRule):
    """SMT110 — a large tensor resident fully-replicated under a
    populated model axis.

    Sharding a model over ``model=m`` chips only buys HBM headroom for
    the tensors that actually shard; every tensor the planner silently
    replicates costs ``(m-1)/m`` of its bytes times ``m`` chips — and the
    ONNX tp planner replicates on ANY consumer-role conflict, indivisible
    dim, or non-float dtype without telling anyone. This rule makes each
    such decision a named finding (tensor, bytes, planner reason) so the
    fsdp work (ROADMAP item 4) starts from an inventory instead of a
    surprise OOM. Entries that expose a ``placement_report`` (the ONNX
    importer) get per-tensor attribution; for the rest, closure constants
    whose committed sharding leaves the model axis unused are flagged.
    """

    code = "SMT110"
    name = "replicated-residency"
    rationale = ("tensors resident fully-replicated across a populated "
                 "model axis forfeit the HBM headroom sharding exists "
                 "to buy")

    def check_entry(self, traced: TracedSpmdEntry) -> Iterable[Finding]:
        if traced.model_size <= 1:
            return []  # nothing to replicate ACROSS on a 1-wide model axis
        limit = traced.entry.replicated_bytes_limit
        layout = traced.layout
        model_axis = getattr(layout, "model_axis", None)
        findings: List[Finding] = []
        if traced.placement:
            # the entry planner knows tensor names and WHY it replicated:
            # report its decisions verbatim (the jaxpr consts below would
            # double-count the same arrays namelessly)
            for row in traced.placement:
                if row.get("decision") != "replicated":
                    continue
                nbytes = int(row.get("nbytes", 0) or 0)
                if nbytes <= limit:
                    continue
                findings.append(self.entry_finding(
                    traced,
                    f"tensor {row.get('tensor', '?')!r} "
                    f"(shape {row.get('shape', '?')}, "
                    f"{nbytes / 1024:.0f} KiB) is resident fully-replicated "
                    f"across the populated model axis "
                    f"({model_axis}={traced.model_size}); planner decision: "
                    f"{row.get('reason', 'unrecorded')}"))
            return findings
        for i, const in enumerate(getattr(traced.closed, "consts", ())):
            nbytes = _const_nbytes(const)
            if nbytes <= limit:
                continue
            sharding = getattr(const, "sharding", None)
            spec = getattr(sharding, "spec", None)
            # numpy constants (no sharding) replicate onto every chip; a
            # NamedSharding whose spec never binds the model axis
            # replicates across it
            if sharding is not None and spec is None:
                continue  # opaque sharding: cannot judge, stay silent
            if spec is not None and model_axis in _spec_axis_names(spec):
                continue
            findings.append(self.entry_finding(
                traced,
                f"closure constant #{i} (shape "
                f"{getattr(const, 'shape', '?')}, {nbytes / 1024:.0f} KiB) "
                f"is resident fully-replicated across the populated model "
                f"axis ({model_axis}={traced.model_size}); shard it "
                f"(layout.col_weight/feature_blocks) or pass it as a "
                f"sharded argument"))
        return findings


@register_spmd
class ConstraintConflict(SpmdRule):
    """SMT111 — conflicting sharding constraints on one value chain.

    ``with_sharding_constraint`` is a promise to GSPMD; two different
    promises about the same value force the partitioner to materialize an
    implicit all-gather/reshard between them — bandwidth spent on a
    placement disagreement, invisible in the source because each
    constraint looks locally reasonable. Flags any value that is
    re-constrained to a different spec (directly chained or fanned out
    from the same producer). One chain is sanctioned: the fsdp
    all-gather-on-use re-pin (``layout.gather_for_use``), where the later
    spec is exactly the earlier spec with the layout's fsdp axis dropped
    — that reshard is the POINT (transient gathered copy, row-sharded
    residency), not a disagreement.
    """

    code = "SMT111"
    name = "sharding-constraint-conflict"
    rationale = ("re-constraining a value to a different spec forces "
                 "GSPMD to insert an implicit reshard on the hot path")

    @staticmethod
    def _constraint_spec(eqn) -> Optional[Any]:
        s = eqn.params.get("sharding")
        if s is None:
            return None
        return getattr(s, "spec", s)

    @staticmethod
    def _is_fsdp_repin(layout, a, b) -> bool:
        """True when one spec is the other's all-gathered *use* form under
        the layout's fsdp axis — the intentional stored→use re-pin (or the
        symmetric use→stored re-shard after an update step)."""
        use_spec = getattr(layout, "use_spec", None)
        if use_spec is None or getattr(layout, "fsdp_axis", None) is None:
            return False
        try:
            return use_spec(a) == b or use_spec(b) == a
        except Exception:
            return False

    def check_entry(self, traced: TracedSpmdEntry) -> Iterable[Finding]:
        if not traced.entry.hot:
            return []
        findings: List[Finding] = []
        committed: Dict[int, Any] = {}   # id(var) -> spec committed to it
        seen_pairs: Set[Tuple[str, str]] = set()
        for eqn in iter_eqns(traced.closed.jaxpr):
            prim = getattr(eqn.primitive, "name", "?")
            if prim != "sharding_constraint":
                continue
            spec = self._constraint_spec(eqn)
            if spec is None:
                continue
            key = str(spec)
            for var in eqn.invars:
                prev = committed.get(id(var))
                if prev is None:
                    continue
                pkey = str(prev)
                if pkey != key and (pkey, key) not in seen_pairs \
                        and not self._is_fsdp_repin(traced.layout,
                                                    prev, spec):
                    seen_pairs.add((pkey, key))
                    findings.append(self.entry_finding(
                        traced,
                        f"value constrained to {pkey} is re-constrained to "
                        f"{key} — GSPMD must insert an implicit "
                        f"all-gather/reshard between the two pins; agree on "
                        f"one spec per value"))
            for var in eqn.outvars:
                committed[id(var)] = spec
            # the constraint output carries the same value: a later
            # constraint on the INPUT var conflicts with this one too
            for var in eqn.invars:
                committed.setdefault(id(var), spec)
        return findings


_MESHISH_NAMES = ("mesh", "layout")
_CALLBACK_CALLS = ("pure_callback", "io_callback", "debug_callback")


def _compares_mesh_to_none(node: ast.AST, negated: bool) -> Optional[str]:
    """``<mesh> is None`` (negated=False) / ``is not None`` (True) —
    returns the compared name when the node is that comparison."""
    if not isinstance(node, ast.Compare) or len(node.ops) != 1:
        return None
    op = node.ops[0]
    if not isinstance(op, ast.IsNot if negated else ast.Is):
        return None
    if not (isinstance(node.comparators[0], ast.Constant)
            and node.comparators[0].value is None):
        return None
    left = node.left
    name = left.id if isinstance(left, ast.Name) else \
        left.attr if isinstance(left, ast.Attribute) else None
    if name and any(m in name.lower() for m in _MESHISH_NAMES):
        return name
    return None


@register_spmd
class HostFallbackUnderMesh(SpmdRule):
    """SMT112 — host fallback reachable only in the mesh configuration.

    The worst scaling bug is the one that only exists when the hardware
    shows up: a device-side fast path gated on ``mesh is None`` means the
    mesh configuration — the one with 8 chips waiting — does the work on
    the HOST (the ``use_device_bin`` searchsorted guard was the canonical
    true finding — mesh fits binned multi-million-row matrices in numpy
    for three arcs — until device-side distributed binning removed it).
    Two halves: an AST pass (jax-free, always on) flags device-path flags
    that require ``mesh is None`` and host callbacks lexically gated on
    ``mesh is not None``; the ``--spmd`` jaxpr pass flags host-callback
    primitives present in an entry's mesh trace but absent from its
    single-device twin.
    """

    code = "SMT112"
    name = "host-fallback-under-mesh"
    rationale = ("a device path gated on `mesh is None` means the mesh "
                 "configuration does the work on the host, serializing "
                 "every chip behind it")
    ast_active = True

    _DEVICEISH = re.compile(r"device|dev_bin|on_dev", re.IGNORECASE)

    def check(self, module: Module) -> Iterable[Finding]:
        findings: List[Finding] = []

        def visit(node: ast.AST, ctx) -> None:
            if isinstance(node, ast.Assign):
                targets = [t.id for t in node.targets
                           if isinstance(t, ast.Name)]
                if not any(self._DEVICEISH.search(t) for t in targets):
                    return
                for sub in ast.walk(node.value):
                    mesh_name = _compares_mesh_to_none(sub, negated=False)
                    if mesh_name:
                        findings.append(self.finding(
                            module, node,
                            f"device-path flag "
                            f"{[t for t in targets if self._DEVICEISH.search(t)][0]!r} "
                            f"requires '{mesh_name} is None' — the device "
                            f"path is unreachable under a mesh, so the mesh "
                            f"configuration falls back to the host; make "
                            f"the path mesh-capable or record the debt"))
                        return
            if isinstance(node, ast.If):
                gated_body: List[ast.stmt] = []
                for sub in ast.walk(node.test):
                    if _compares_mesh_to_none(sub, negated=True):
                        gated_body = node.body
                        break
                    if _compares_mesh_to_none(sub, negated=False):
                        gated_body = node.orelse
                        break
                for stmt in gated_body:
                    for sub in ast.walk(stmt):
                        if isinstance(sub, ast.Call):
                            callee = sub.func
                            cname = callee.attr if isinstance(
                                callee, ast.Attribute) else getattr(
                                callee, "id", None)
                            if cname in _CALLBACK_CALLS:
                                findings.append(self.finding(
                                    module, sub,
                                    f"host callback '{cname}' is reachable "
                                    f"only under a mesh — the distributed "
                                    f"configuration stalls every chip on a "
                                    f"host round-trip the single-device "
                                    f"path never pays"))

        walk_scoped(module.tree, visit)
        return findings

    _CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback",
                                 "debug_callback", "outside_call",
                                 "host_callback_call"})

    def _callback_prims(self, closed) -> Set[str]:
        return {getattr(e.primitive, "name", "?")
                for e in iter_eqns(closed.jaxpr)
                if getattr(e.primitive, "name", "?") in self._CALLBACK_PRIMS}

    def check_entry(self, traced: TracedSpmdEntry) -> Iterable[Finding]:
        if traced.single is None:
            return []
        mesh_only = (self._callback_prims(traced.closed)
                     - self._callback_prims(traced.single))
        return [self.entry_finding(
            traced,
            f"host callback '{prim}' is staged in the mesh trace but "
            f"absent from the single-device trace — host fallback "
            f"reachable only in the mesh configuration")
            for prim in sorted(mesh_only)]


@register_spmd
class MeshDivergence(SpmdRule):
    """SMT113 — structural mesh-vs-single-device jaxpr divergence.

    The sparse mesh parity failure (``test_sparse_mesh_matches_single_
    device``) is a needle in a 400-eqn haystack; diffing the two traces
    after canonicalization (collectives stripped, names/dims
    alpha-renamed) names the FIRST structurally divergent region — the
    place a bisection starts. An entry whose twins should be structurally
    identical and are not is a finding; known-divergent entries carry a
    reasoned LINT_ACKS row that documents exactly which region is
    accepted. ``tools/spmd_diff.py`` prints the full region.
    """

    code = "SMT113"
    name = "mesh-divergence"
    rationale = ("a mesh trace that structurally diverges from its "
                 "single-device twin computes something different per "
                 "shard — the parity bug's hiding place")

    _HEAD = 2  # divergent-region lines quoted in the finding message

    def check_entry(self, traced: TracedSpmdEntry) -> Iterable[Finding]:
        if traced.single is None:
            return []
        mesh_lines = canonical_lines(traced.closed)
        single_lines = canonical_lines(traced.single)
        d = structural_diff(mesh_lines, single_lines)
        if d is None:
            return []
        mo, so = d["mesh_only"], d["single_only"]

        def head(lines: List[str]) -> str:
            shown = "; ".join(lines[:self._HEAD])
            more = len(lines) - self._HEAD
            return (shown + (f" (+{more} more)" if more > 0 else "")) \
                if lines else "<empty>"

        return [self.entry_finding(
            traced,
            f"mesh trace structurally diverges from the single-device "
            f"trace after {d['index']} shared eqns "
            f"({d['common_suffix']} shared after): mesh-only region "
            f"[{head(mo)}] vs single-only region [{head(so)}]; run "
            f"`python tools/spmd_diff.py --entry {traced.entry.name!r}` "
            f"for the full region")]


_REFUSAL_KEYWORDS = ("mesh", "sparse", "dart", "distributed")


@register
class RefusalGuardInventory(Rule):
    """SMT114 — mesh/sparse refusal-guard inventory (AST, always on).

    Every ``raise NotImplementedError`` whose message mentions
    mesh/sparse/dart/distributed is a piece of distributed-GBDT debt:
    a configuration the engine refuses instead of running. Refusing is
    the RIGHT call (a loud error beats silently-wrong trees), but the
    debt must be enumerable by machine — this rule makes each guard a
    finding, the matching ``LINT_ACKS.md`` row its tracked waiver, and
    ``docs/analysis.md``'s debt table its human ledger. Adding a new
    refusal without a reasoned waiver row fails the gate: the debt
    cannot silently grow.
    """

    code = "SMT114"
    name = "mesh-refusal-guard"
    rationale = ("NotImplementedError guards over mesh/sparse configs are "
                 "tracked debt — each needs a reasoned waiver row so the "
                 "inventory cannot silently grow")

    def check(self, module: Module) -> Iterable[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Raise) or node.exc is None:
                continue
            exc = node.exc
            name = None
            if isinstance(exc, ast.Call):
                name = exc.func.id if isinstance(exc.func, ast.Name) else \
                    exc.func.attr if isinstance(exc.func, ast.Attribute) \
                    else None
            elif isinstance(exc, ast.Name):
                name = exc.id
            if name != "NotImplementedError":
                continue
            text = " ".join(
                s.value for s in ast.walk(exc)
                if isinstance(s, ast.Constant) and isinstance(s.value, str))
            low = text.lower()
            kws = sorted(k for k in _REFUSAL_KEYWORDS if k in low)
            if not kws:
                continue
            snippet = re.sub(r"\s+", " ", text).strip()
            if len(snippet) > 90:
                snippet = snippet[:87] + "..."
            findings.append(self.finding(
                module, node,
                f"refusal guard mentions {'/'.join(kws)}: \"{snippet}\" — "
                f"tracked distributed-GBDT debt; keep its LINT_ACKS.md row "
                f"and docs/analysis.md debt-table entry current"))
        return findings


# ---------------------------------------------------------------------------
# canonical entries: representative SpecLayouts over the hot paths
# ---------------------------------------------------------------------------

def _spmd_mlp_bytes():
    """The tp-serving stand-in model: the tiny MLP plus a TIED projection
    weight consumed in two roles (``MatMul`` rhs AND ``Gemm`` transB rhs —
    the tied-embedding pattern). Under a tp-only layout the planner
    replicates on the role conflict (SMT110's canonical finding); under
    an fsdp layout it stores the tied weight row-sharded and all-gathers
    at each consumer — the finding's resolution."""
    import numpy as np

    from ..onnx import builder
    from ..onnx.wire import serialize_model

    rng = np.random.default_rng(0)
    w1 = rng.normal(size=(64, 128)).astype(np.float32)
    b1 = rng.normal(size=(128,)).astype(np.float32)
    w_tied = rng.normal(size=(128, 128)).astype(np.float32)  # 64 KiB
    c0 = np.zeros((128,), np.float32)
    g = builder.make_graph(
        [builder.node("MatMul", ["x", "w1"], ["h0"]),
         builder.node("Add", ["h0", "b1"], ["h1"]),
         builder.node("Relu", ["h1"], ["h2"]),
         builder.node("MatMul", ["h2", "w_tied"], ["h3"]),
         builder.node("Gemm", ["h3", "w_tied", "c0"], ["y"], transB=1)],
        "mlp_tp",
        [builder.value_info("x", np.float32, [None, 64])],
        [builder.value_info("y", np.float32, [None, 128])],
        initializers={"w1": w1, "b1": b1, "w_tied": w_tied, "c0": c0})
    return serialize_model(builder.make_model(g))


def _build_onnx_fsdp_entry() -> Dict[str, Any]:
    """Beyond-HBM ONNX serving over the (1, 2, 2) layout: MatMul weights
    column-shard over ``model`` and are additionally STORED row-sharded
    over ``fsdp`` (all-gathered at each consumer). The tied weight — the
    planner's old replicate-on-conflict debt, SMT110's canonical finding
    — now stores over fsdp too, so the finding is resolved rather than
    waived; SMT111 sees the stored→use re-pin chain and must recognize it
    as the sanctioned fsdp gather. The no-layout twin gives SMT113 a
    structurally-identical baseline (constraints strip)."""
    import numpy as np

    from ..onnx.importer import OnnxFunction
    from ..runtime.layout import representative_layouts

    layout = representative_layouts()["(1,2,2)"]
    model = _spmd_mlp_bytes()
    of = OnnxFunction(model, dtype_policy="float32", layout=layout)
    single = OnnxFunction(model, dtype_policy="float32")
    x = np.zeros((8, 64), np.float32)
    return {"fn": of._run_positional, "args": (x,),
            "single_fn": single._run_positional, "single_args": (x,),
            "layout": layout, "placement_report": of.placement_report(),
            "anchor_obj": OnnxFunction._plan_const_specs}


def _build_gbdt_fp_entry(layout_key: str) -> Callable[[], Dict[str, Any]]:
    """2-D feature-parallel gbdt grow over a representative ``(data,
    model)`` mesh (degrading to what the host has) — the path ROADMAP
    item 2's device-side binning must feed."""

    def build() -> Dict[str, Any]:
        from ..gbdt import grow
        from ..runtime.layout import representative_layouts

        layout = representative_layouts()[layout_key]
        binned, g, h, w, fmask, TreeConfig, B = _gbdt_grow_inputs()
        cfg = TreeConfig(n_bins=B, num_leaves=4)
        dspec, rep = layout.batch(), layout.replicated()

        def body(b, gg, hh, ww, fm):
            return grow.grow_tree(b, gg, hh, ww, fm, cfg,
                                  axis_name=layout.data_axis,
                                  model_axis_name=layout.model_axis)

        fn = layout.shard_map(body,
                              in_specs=(dspec, dspec, dspec, dspec, rep),
                              out_specs=(rep, dspec), check=False)
        return {"fn": fn, "args": (binned, g, h, w, fmask),
                "layout": layout, "anchor_obj": grow.grow_tree}

    return build


def _build_gbdt_sparse_pair_entry() -> Dict[str, Any]:
    """The sparse grow step traced BOTH ways — the exact configuration
    ``test_sparse_mesh_matches_single_device`` fails on, exposed to
    SMT112/SMT113 and ``tools/spmd_diff.py`` as a differential pair."""
    from ..gbdt import boost

    mesh, single = boost.spmd_trace_pair()
    return {"fn": mesh["fn"], "args": mesh["args"],
            "single_fn": single["fn"], "single_args": single["args"],
            "layout": mesh["layout"], "anchor_obj": boost._build_step}


def _build_gbdt_device_bin_entry() -> Dict[str, Any]:
    """Shard-local device binning (the mesh ``use_device_bin`` path):
    raw f32 rows shard over ``data``, the packed edge/category tables
    replicate, and each shard runs the same vectorized binning kernel the
    single-device path uses — so the mesh trace must be STRUCTURALLY
    IDENTICAL to the single-device twin (any divergence here would break
    the bit-identical-trees parity the gbdt tests pin)."""
    from ..gbdt import device_predict
    from ..gbdt.binning import BinMapper
    from ..runtime.layout import representative_layouts

    import numpy as np

    layout = representative_layouts()["(4,2)-fp"]
    rng = np.random.default_rng(0)
    # 88 rows -> 22 per shard under data=4: no dimension of the
    # per-shard block aliases the packed-table width (max_bin) or the
    # feature count, so the canonical dim ids line up with the
    # single-device trace (64 rows gave 16/shard == max_bin and the
    # structural diff flagged a spurious broadcast hunk)
    x = rng.normal(size=(88, 6)).astype(np.float32)
    mapper = BinMapper(max_bin=16).fit(x.astype(np.float64))
    table, lens, cat_flags = device_predict.pack_feature_table(mapper)
    dspec, rep = layout.batch(), layout.replicated()

    def body(xb, t, ln):
        # cat_flags stays on host: static kernel-selection metadata
        return device_predict.device_bin_cat(xb, t, ln, cat_flags,
                                             mapper.missing_bin)

    fn = layout.shard_map(body, in_specs=(dspec, rep, rep),
                          out_specs=dspec, check=False)
    return {"fn": fn, "args": (x, table, lens),
            "single_fn": body, "single_args": (x, table, lens),
            "layout": layout, "anchor_obj": device_predict.device_bin_cat}


def default_spmd_entries() -> List[SpmdEntry]:
    """The canonical entries, one per representative layout: (1, 1)
    degenerate, (4, 2) feature-parallel, (1, 2, 2) fsdp+tensor-parallel
    serving, the sparse mesh-vs-single differential pair, and the
    shard-local device-binning pair the mesh ``use_device_bin`` path
    runs."""
    return [
        SpmdEntry("onnx.mlp[fsdp,(1,2,2)]", _build_onnx_fsdp_entry,
                  mesh_axes=("data", "fsdp", "model"),
                  replicated_bytes_limit=32 << 10),
        SpmdEntry("gbdt.grow[feature-parallel,(1,1)]",
                  _build_gbdt_fp_entry("(1,1)"),
                  mesh_axes=("data", "model")),
        SpmdEntry("gbdt.grow[feature-parallel,(4,2)]",
                  _build_gbdt_fp_entry("(4,2)-fp"),
                  mesh_axes=("data", "model")),
        SpmdEntry("gbdt.grow[sparse,mesh]", _build_gbdt_sparse_pair_entry,
                  mesh_axes=("data",)),
        SpmdEntry("gbdt.bin[device,mesh]", _build_gbdt_device_bin_entry,
                  mesh_axes=("data", "model")),
    ]


def differential_entry_names() -> List[str]:
    """Entries carrying a single-device twin (what ``tools/spmd_diff.py``
    can diff) — static so ``--list`` stays jax-free."""
    return ["gbdt.grow[sparse,mesh]", "gbdt.bin[device,mesh]",
            "onnx.mlp[fsdp,(1,2,2)]"]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _ensure_virtual_devices(n: int = 8) -> None:
    """Standalone CLI runs start jax with ONE cpu device — representative
    (4, 2)/(1, 2) layouts need more. Harmless when jax is already up (the
    flag is only read at first init) or when the caller set their own."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()


def trace_spmd_entry(entry: SpmdEntry, root: Optional[str] = None
                     ) -> TracedSpmdEntry:
    """Trace one entry's mesh configuration (and its single-device twin
    when the builder provides one) with ``jax.make_jaxpr`` — tracing
    only, no compile, no device execution."""
    import jax

    built = entry.build()
    closed = jax.make_jaxpr(built["fn"])(*built.get("args", ()),
                                         **built.get("kwargs", {}))
    single = None
    if built.get("single_fn") is not None:
        single = jax.make_jaxpr(built["single_fn"])(
            *built.get("single_args", ()), **built.get("single_kwargs", {}))
    return TracedSpmdEntry(entry, closed, _anchor_of(built, root),
                           single=single, layout=built.get("layout"),
                           placement=built.get("placement_report"))


def run_spmd_pack(entries: Optional[Sequence[SpmdEntry]] = None,
                  select: Optional[Sequence[str]] = None,
                  root: Optional[str] = None
                  ) -> Tuple[List[Finding], List[str]]:
    """Trace every entry under its representative layout and run the
    (selected) spmd rules over the jaxprs. Returns ``(findings, errors)``
    — an entry whose trace fails is an ERROR (the gate must see it),
    never a silent skip."""
    codes = [c for c in (select or sorted(SPMD_RULES)) if c in SPMD_RULES]
    if not codes:
        return [], []
    _ensure_virtual_devices()
    if entries is None:
        entries = default_spmd_entries()
    findings: List[Finding] = []
    errors: List[str] = []
    for entry in entries:
        try:
            traced = trace_spmd_entry(entry, root=root)
        except Exception as e:
            errors.append(f"spmd entry {entry.name!r} failed to trace: "
                          f"{type(e).__name__}: {e}")
            continue
        for code in codes:
            findings.extend(SPMD_RULES[code].check_entry(traced))
    return findings, errors
