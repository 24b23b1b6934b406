"""The SMT rule pack: this repo's load-bearing invariants as lint rules.

Each rule names one invariant, says why it is load-bearing, and yields
``file:line`` findings. Heuristic rules (SMT006/SMT007) are tuned on the
real lock sites in ``observability/``, ``io/serving*.py`` and ``runtime/``;
anything they over-flag gets a reasoned ``LINT_ACKS.md`` row, never a
silent exemption. Fixture-level true-positive/true-negative coverage for
every rule lives in ``tests/test_lint_clean.py``.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .engine import (Ctx, Finding, Module, Rule, dotted_name, is_lock_expr,
                     register, walk_scoped)

__all__ = []  # rules are reached through engine.RULES


def _is_jax_module(name: Optional[str]) -> bool:
    return bool(name) and (name == "jax" or name.startswith("jax."))


def _imports_jax(node: ast.AST) -> bool:
    if isinstance(node, ast.Import):
        return any(_is_jax_module(a.name) for a in node.names)
    if isinstance(node, ast.ImportFrom):
        return _is_jax_module(node.module)
    return False


def _is_type_checking_if(node: ast.AST) -> bool:
    if not isinstance(node, ast.If):
        return False
    t = node.test
    return (isinstance(t, ast.Name) and t.id == "TYPE_CHECKING") or (
        isinstance(t, ast.Attribute) and t.attr == "TYPE_CHECKING")


@register
class ModuleLevelJaxImport(Rule):
    """SMT001 — jax imported at module import time.

    ``import synapseml_tpu`` (and every operational layer a serving worker
    or CLI tool touches at startup) must never import jax: initialization
    is slow, environment-sensitive, and grabs accelerator state. The
    subprocess gate in ``tests/test_import_hygiene.py`` stays the ground
    truth (it catches *transitive* imports this AST pass cannot); this
    rule adds the file:line diagnostic per offending statement, over every
    file instead of a curated module list. Fix: import inside the function
    that uses it, or use ``core.lazyimport.lazy_import``.
    """

    code = "SMT001"
    name = "module-level-jax-import"
    rationale = ("jax at import time breaks the no-jax-at-import contract "
                 "every worker/CLI startup relies on")

    def check(self, module: Module) -> Iterable[Finding]:
        findings: List[Finding] = []

        def rec(node: ast.AST) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                return  # function bodies run post-import
            if _is_type_checking_if(node):
                return  # typing-only imports never execute
            if _imports_jax(node):
                what = (", ".join(a.name for a in node.names)
                        if isinstance(node, ast.Import) else node.module)
                findings.append(self.finding(
                    module, node,
                    f"module-level import of {what!r} runs at import time; "
                    f"import jax inside the using function or via "
                    f"core.lazyimport.lazy_import"))
                return
            for child in ast.iter_child_nodes(node):
                rec(child)

        for stmt in module.tree.body:
            rec(stmt)
        return findings


@register
class DirectShardMap(Rule):
    """SMT002 — ``shard_map`` imported/used directly instead of through
    ``runtime.topology.shard_map_compat``.

    jax moved ``shard_map`` between ``jax.experimental`` (0.4.x,
    ``check_rep=``) and top level (``check_vma=``); direct imports are
    exactly the drift that shipped 8 mesh-test ImportErrors in the seed.
    Every mesh-distributed call site goes through the one wrapper, which
    spells the installed jax's API (``jax.shard_map``) in one place.
    """

    code = "SMT002"
    name = "direct-shard-map"
    rationale = ("direct shard_map imports break across jax versions; "
                 "runtime.topology.shard_map_compat is the one call site")

    def check(self, module: Module) -> Iterable[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                names = {a.name for a in node.names}
                if (mod == "jax.experimental.shard_map"
                        or (mod in ("jax", "jax.experimental")
                            and "shard_map" in names)):
                    findings.append(self.finding(
                        module, node,
                        f"direct shard_map import from {mod!r}; use "
                        f"runtime.topology.shard_map_compat"))
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.startswith("jax.experimental.shard_map"):
                        findings.append(self.finding(
                            module, node,
                            f"direct import of {a.name!r}; use "
                            f"runtime.topology.shard_map_compat"))
            elif isinstance(node, ast.Attribute):
                dn = dotted_name(node)
                if dn in ("jax.shard_map",
                          "jax.experimental.shard_map",
                          "jax.experimental.shard_map.shard_map"):
                    findings.append(self.finding(
                        module, node,
                        f"direct use of {dn}; use "
                        f"runtime.topology.shard_map_compat"))
        return findings


def _is_wallclock_call(node: ast.AST, bare_time: bool) -> bool:
    """A ``time.time()`` call (or bare ``time()`` when imported that way)."""
    if not isinstance(node, ast.Call):
        return False
    dn = dotted_name(node.func)
    return dn == "time.time" or (bare_time and dn == "time")


@register
class WallClockDelta(Rule):
    """SMT003 — durations computed from ``time.time()`` deltas.

    Wall-clock deltas jump under NTP slew; every elapsed-time measurement
    uses ``time.perf_counter()`` / ``core.clock.StopWatch``. Timestamp-only
    uses of ``time.time()`` (event ``ts`` fields, exemplar ages) are fine —
    the rule only flags *subtractions* whose both operands trace back to
    wall-clock reads.
    """

    code = "SMT003"
    name = "wall-clock-delta"
    rationale = ("time.time() deltas jump under NTP slew; durations use "
                 "perf_counter / core.clock.StopWatch")

    def check(self, module: Module) -> Iterable[Finding]:
        bare_time = any(
            isinstance(n, ast.ImportFrom) and n.module == "time"
            and any(a.name == "time" for a in n.names)
            for n in ast.walk(module.tree))

        def taint_targets(stmt: ast.AST, names: Set[str],
                          attrs: Set[str]) -> None:
            if isinstance(stmt, ast.Assign) and _is_wallclock_call(
                    stmt.value, bare_time):
                for t in stmt.targets:
                    for el in (t.elts if isinstance(t, ast.Tuple) else [t]):
                        if isinstance(el, ast.Name):
                            names.add(el.id)
                        elif isinstance(el, ast.Attribute):
                            attrs.add(el.attr)

        # attribute taint is module-wide (self._start set in start(), read
        # in stop()); NAME taint is per function scope — a `t0` holding a
        # wall timestamp in one function must not poison a `t0` holding a
        # perf_counter in another
        attr_tainted: Set[str] = set()
        for node in ast.walk(module.tree):
            taint_targets(node, set(), attr_tainted)

        findings: List[Finding] = []

        def process_scope(body, inherited: Set[str]) -> None:
            tainted = set(inherited)
            nested: List[ast.AST] = []

            def rec(n: ast.AST, collect_only: bool) -> None:
                for child in ast.iter_child_nodes(n):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                        if collect_only:
                            nested.append(child)
                        continue  # separate name scope (closure inherits)
                    if collect_only:
                        taint_targets(child, tainted, set())
                    elif (isinstance(child, ast.BinOp)
                            and isinstance(child.op, ast.Sub)
                            and wallclockish(child.left, tainted)
                            and wallclockish(child.right, tainted)):
                        findings.append(self.finding(
                            module, child,
                            "duration computed as a time.time() delta; use "
                            "time.perf_counter() or core.clock.StopWatch"))
                    rec(child, collect_only)

            holder = ast.Module(body=body, type_ignores=[])
            rec(holder, True)
            rec(holder, False)
            for fn in nested:
                process_scope(fn.body, tainted)

        def wallclockish(node: ast.AST, tainted: Set[str]) -> bool:
            if _is_wallclock_call(node, bare_time):
                return True
            if isinstance(node, ast.Name):
                return node.id in tainted
            if isinstance(node, ast.Attribute):
                return node.attr in attr_tainted
            return False

        process_scope(module.tree.body, set())
        return findings


@register
class NonDefaultHistogramBuckets(Rule):
    """SMT004 — ``Histogram``/``registry.histogram`` constructed with
    non-default buckets.

    Fleet quantiles come from *bucket-wise merged* worker histograms; the
    merge is exact only because every histogram in every process shares the
    single fixed ``DEFAULT_BUCKETS`` layout. One histogram with custom
    buckets silently breaks exact fleet merge for its family.
    """

    code = "SMT004"
    name = "non-default-histogram-buckets"
    rationale = ("per-worker histograms merge exactly only on the one fixed "
                 "DEFAULT_BUCKETS layout")

    def check(self, module: Module) -> Iterable[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = None
            if isinstance(node.func, ast.Attribute):
                fname = node.func.attr
            elif isinstance(node.func, ast.Name):
                fname = node.func.id
            if fname not in ("histogram", "Histogram"):
                continue
            offending = None
            for kw in node.keywords:
                if kw.arg == "buckets":
                    v = kw.value
                    vn = dotted_name(v)
                    if not (vn and vn.split(".")[-1] == "DEFAULT_BUCKETS"):
                        offending = kw.value
            # positional buckets only exist on registry.histogram(name,
            # help, labelnames, buckets) — attribute calls; a bare-name
            # histogram() is the gbdt kernel, whose 4th arg is a weight
            if (offending is None and len(node.args) >= 4
                    and fname == "histogram"
                    and isinstance(node.func, ast.Attribute)):
                offending = node.args[3]
            if offending is not None:
                findings.append(self.finding(
                    module, offending,
                    "histogram constructed with non-default buckets; "
                    "per-worker merge is exact only on DEFAULT_BUCKETS"))
        return findings


_STAGE_BASES = {"PipelineStage", "Transformer", "Estimator", "Model",
                "UnaryTransformer", "PipelineModel"}
_STAGE_SUFFIXES = ("Transformer", "Estimator", "Model", "Stage")


def _registered_stage_classes(module: Module) -> List[ast.ClassDef]:
    """ClassDefs that would auto-register in ``STAGE_REGISTRY``: inherit a
    stage base (local subclass chains resolved, name-suffix heuristic for
    imported bases), not ``_``-prefixed, no ``_abstract_stage = True`` in
    their own body. Shared by SMT005 and SMT009 so the two rules cannot
    drift on what "registered" means."""
    local_bases: Dict[str, Set[str]] = {}
    classes = [n for n in ast.walk(module.tree)
               if isinstance(n, ast.ClassDef)]
    for cls in classes:
        local_bases[cls.name] = {
            dn.split(".")[-1] for dn in
            (dotted_name(b) for b in cls.bases) if dn}

    def is_stage_base(name: str, seen: Set[str]) -> bool:
        if name in _STAGE_BASES or name.endswith(_STAGE_SUFFIXES):
            return True
        if name in seen or name not in local_bases:
            return False
        seen.add(name)
        return any(is_stage_base(b, seen) for b in local_bases[name])

    out: List[ast.ClassDef] = []
    for cls in classes:
        if cls.name.startswith("_"):
            continue  # never registered (test/bench-local stages)
        abstract = any(
            isinstance(st, ast.Assign)
            and any(isinstance(t, ast.Name) and t.id == "_abstract_stage"
                    for t in st.targets)
            and isinstance(st.value, ast.Constant) and st.value.value
            for st in cls.body)
        if abstract:
            continue
        if any(is_stage_base(b, set()) for b in local_bases[cls.name]):
            out.append(cls)
    return out


@register
class StageOverridesInstrumentedMethod(Rule):
    """SMT005 — a registered ``PipelineStage`` subclass overrides base
    ``transform``/``fit``.

    Span instrumentation (wall time, row counts, cold/warm compile split,
    trace attachment) lives in the base ``Transformer.transform`` /
    ``Estimator.fit``; stages implement ``_transform``/``_fit``. An
    override silently drops the stage out of every ``/metrics`` and
    ``/traces`` view. Framework bases opt out with ``_abstract_stage =
    True`` in their own body; ``_``-prefixed classes are never registered.
    """

    code = "SMT005"
    name = "stage-overrides-instrumented-method"
    rationale = ("base transform/fit carry span instrumentation; stages "
                 "implement _transform/_fit")

    def check(self, module: Module) -> Iterable[Finding]:
        findings: List[Finding] = []
        for cls in _registered_stage_classes(module):
            for st in cls.body:
                if (isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and st.name in ("transform", "fit")):
                    findings.append(self.finding(
                        module, st,
                        f"stage {cls.name} overrides instrumented base "
                        f"method {st.name}(); implement _{st.name}() — the "
                        f"base carries span instrumentation"))
        return findings


_MUTATORS = {"append", "appendleft", "extend", "extendleft", "insert",
             "remove", "pop", "popleft", "popitem", "clear", "update",
             "setdefault", "add", "discard", "rotate"}


def _mutations(node: ast.AST) -> List[Tuple[str, str, ast.AST]]:
    """Shared-state mutations in one AST node: ``[(kind, name, site)]``
    where kind is 'attr' (``X.name = / X.name[k] = / X.name.append()``)
    or 'name' (``NAME[k] = / NAME.append() / NAME = `` for globals)."""
    out: List[Tuple[str, str, ast.AST]] = []

    def target(t: ast.AST) -> None:
        if isinstance(t, (ast.Tuple, ast.List)):
            for el in t.elts:
                target(el)
        elif isinstance(t, ast.Starred):
            target(t.value)
        elif isinstance(t, ast.Attribute):
            out.append(("attr", t.attr, t))
        elif isinstance(t, ast.Subscript):
            if isinstance(t.value, ast.Attribute):
                out.append(("attr", t.value.attr, t))
            elif isinstance(t.value, ast.Name):
                out.append(("name", t.value.id, t))
        elif isinstance(t, ast.Name):
            out.append(("name", t.id, t))

    if isinstance(node, ast.Assign):
        for t in node.targets:
            target(t)
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        target(node.target)
    elif isinstance(node, ast.Delete):
        for t in node.targets:
            target(t)
    elif (isinstance(node, ast.Call)
          and isinstance(node.func, ast.Attribute)
          and node.func.attr in _MUTATORS):
        recv = node.func.value
        if isinstance(recv, ast.Attribute):
            out.append(("attr", recv.attr, node))
        elif isinstance(recv, ast.Name):
            out.append(("name", recv.id, node))
    return out


def _local_bindings(func: ast.AST) -> Set[str]:
    """Names a function binds locally (bare assignments / for targets /
    with-as, excluding nested function bodies): per Python scoping, such a
    name is local for the WHOLE function unless declared ``global``."""
    out: Set[str] = set()

    def names_of(t: ast.AST) -> None:
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for el in t.elts:
                names_of(el)
        elif isinstance(t, ast.Starred):
            names_of(t.value)

    if isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
        a = func.args
        for arg in (a.posonlyargs + a.args + a.kwonlyargs
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            out.add(arg.arg)

    def rec(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue  # separate scope
            if isinstance(child, ast.Assign):
                for t in child.targets:
                    names_of(t)
            elif isinstance(child, (ast.AugAssign, ast.AnnAssign)):
                names_of(child.target)
            elif isinstance(child, (ast.For, ast.AsyncFor)):
                names_of(child.target)
            elif isinstance(child, (ast.With, ast.AsyncWith)):
                for item in child.items:
                    if item.optional_vars is not None:
                        names_of(item.optional_vars)
            rec(child)

    rec(func)
    return out


def _module_level_names(tree: ast.Module) -> Set[str]:
    names: Set[str] = set()
    for st in tree.body:
        if isinstance(st, ast.Assign):
            for t in st.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
        elif isinstance(st, (ast.AnnAssign, ast.AugAssign)):
            if isinstance(st.target, ast.Name):
                names.add(st.target.id)
    return names


@register
class UnlockedSharedWrite(Rule):
    """SMT006 — lock-protected state written outside the lock.

    Heuristic race check, tuned on the lock sites across ``observability/``,
    ``io/serving*.py`` and ``runtime/``: an attribute (or module global)
    that is *ever* mutated inside a ``with <lock>`` block is treated as
    lock-protected; any mutation of the same attribute outside a lock
    region is a finding. Constructor bodies (``__init__``/``__new__``) and
    module top level are exempt — construction happens-before publication.
    Unlocked *reads* are deliberately not flagged (lock-free fast-path
    reads are an intentional pattern here, e.g. double-checked
    ``shared_singleton``).
    """

    code = "SMT006"
    name = "unlocked-shared-write"
    rationale = ("state mutated under a lock in one place and without it in "
                 "another is a data race the GIL does not excuse")

    def check(self, module: Module) -> Iterable[Finding]:
        module_globals = _module_level_names(module.tree)
        protected_attrs: Set[str] = set()
        protected_globals: Set[str] = set()
        global_decls: Dict[int, Set[str]] = {}  # id(func) -> declared names
        locals_cache: Dict[int, Set[str]] = {}

        def _is_global_write(name: str, site: ast.AST, ctx: Ctx) -> bool:
            """A Name-rooted mutation counts as *shared* only when it can
            reach module state: a bare ``name = ...`` in a function binds a
            local unless declared ``global``, and a locally-bound name is
            local for the whole function scope; subscript/mutator-call
            sites mutate the object a module-level name refers to."""
            if not ctx.funcs:
                # module-level code runs at import (single-threaded)
                return not isinstance(site, ast.Name) and \
                    name in module_globals
            fn = ctx.funcs[-1]
            if name in global_decls.get(id(fn), ()):
                return True
            if isinstance(site, ast.Name):
                return False  # bare assign without global: binds a local
            key = id(fn)
            if key not in locals_cache:
                locals_cache[key] = _local_bindings(fn)
            if name in locals_cache[key]:
                return False  # shadowed: every use in this scope is local
            return name in module_globals

        def collect(node: ast.AST, ctx: Ctx) -> None:
            if isinstance(node, ast.Global) and ctx.funcs:
                global_decls.setdefault(
                    id(ctx.funcs[-1]), set()).update(node.names)
            if not ctx.in_lock:
                return
            for kind, name, site in _mutations(node):
                if kind == "attr":
                    protected_attrs.add(name)
                elif _is_global_write(name, site, ctx):
                    protected_globals.add(name)

        walk_scoped(module.tree, collect)
        if not protected_attrs and not protected_globals:
            return []

        findings: List[Finding] = []

        def flag(node: ast.AST, ctx: Ctx) -> None:
            if ctx.in_lock or not ctx.in_function or ctx.in_constructor:
                return
            for kind, name, site in _mutations(node):
                if kind == "attr" and name in protected_attrs:
                    findings.append(self.finding(
                        module, site,
                        f"attribute {name!r} is mutated under a lock "
                        f"elsewhere in this module but written here without "
                        f"one"))
                elif (kind == "name" and name in protected_globals
                        and _is_global_write(name, site, ctx)):
                    findings.append(self.finding(
                        module, site,
                        f"module global {name!r} is mutated under a lock "
                        f"elsewhere in this module but written here without "
                        f"one"))

        walk_scoped(module.tree, flag)
        return findings


_BLOCKING_DOTTED = {
    "time.sleep", "select.select", "subprocess.run", "subprocess.call",
    "subprocess.check_call", "subprocess.check_output", "subprocess.Popen",
    "urllib.request.urlopen", "socket.create_connection", "os.system",
    "requests.get", "requests.post", "requests.request",
}
_BLOCKING_ATTRS = {"recv", "accept", "connect", "sendall", "urlopen",
                   "wait", "result", "block_until_ready"}
_JAX_ROOTS = {"jax", "jnp", "lax"}


@register
class BlockingWorkUnderLock(Rule):
    """SMT007 — blocking I/O or jax dispatch while holding a lock.

    The family locks sit on the serving request hot path; a scrape or
    request that blocks on the network / a device computation while holding
    one turns every concurrent observation into queued p99. Flags known
    blocking calls (sleep, socket/subprocess/urllib, ``.wait()``/
    ``.result()``) and any jax dispatch (``jax.* / jnp.* / lax.*``,
    ``.block_until_ready()``) inside ``with <lock>`` bodies.
    """

    code = "SMT007"
    name = "blocking-work-under-lock"
    rationale = ("network / device / sleep work under a lock serializes "
                 "every concurrent hot-path observation behind it")

    def check(self, module: Module) -> Iterable[Finding]:
        findings: List[Finding] = []

        def visit(node: ast.AST, ctx: Ctx) -> None:
            if not ctx.in_lock or not isinstance(node, ast.Call):
                return
            dn = dotted_name(node.func)
            reason = None
            if dn is not None:
                root = dn.split(".")[0]
                if dn in _BLOCKING_DOTTED:
                    reason = f"blocking call {dn}()"
                elif root in _JAX_ROOTS:
                    reason = f"jax dispatch {dn}()"
            if (reason is None and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _BLOCKING_ATTRS
                    and not isinstance(node.func.value, ast.Constant)):
                reason = f"blocking call .{node.func.attr}()"
            if reason is not None:
                findings.append(self.finding(
                    module, node,
                    f"{reason} while holding a lock; move the blocking "
                    f"work outside the critical section"))

        walk_scoped(module.tree, visit)
        return findings


@register
class DuplicateStageName(Rule):
    """SMT009 — the same stage class name registered from two modules.

    ``STAGE_REGISTRY`` (and therefore ``load_stage``) is keyed by CLASS
    NAME: when two modules define a registered stage with the same name,
    whichever imports later silently wins, and a saved pipeline can load
    the WRONG class depending on import order. The runtime path only
    logged a warning (``core/stage.py register_stage``) — swallowed in
    production. This rule promotes it to a CI-failing finding: one
    diagnostic per defining site, each naming the other module(s).

    Detection reuses SMT005's registration heuristics: classes inheriting
    a stage base, not ``_``-prefixed, without ``_abstract_stage = True``
    in their own body.
    """

    code = "SMT009"
    name = "duplicate-stage-name"
    rationale = ("STAGE_REGISTRY is keyed by class name; a cross-module "
                 "collision makes load_stage resolve to whichever module "
                 "imported last")

    def __init__(self):
        # name -> [(module rel path, line, col)] — plain tuples only, so a
        # long-lived process does not pin every scanned module's AST
        self._sites: Dict[str, List[Tuple[str, int, int]]] = {}

    def begin(self) -> None:
        self._sites = {}

    def check(self, module: Module) -> Iterable[Finding]:
        for cls in _registered_stage_classes(module):
            self._sites.setdefault(cls.name, []).append(
                (module.rel, cls.lineno, cls.col_offset + 1))
        return []

    def finalize(self) -> Iterable[Finding]:
        findings: List[Finding] = []
        for name, sites in sorted(self._sites.items()):
            modules = sorted({rel for rel, _, _ in sites})
            if len(modules) < 2:
                continue
            for rel, line, col in sites:
                others = [m for m in modules if m != rel]
                findings.append(Finding(
                    path=rel, line=line, col=col, code=self.code,
                    message=f"stage class name {name!r} is also registered "
                            f"from {', '.join(others)}; load_stage resolves "
                            f"by NAME, so the later import silently shadows "
                            f"this one — rename one of the classes"))
        self._sites = {}
        return findings


@register
class UntimedNetworkCall(Rule):
    """SMT011 — ``urlopen`` / ``socket.create_connection`` without an
    explicit ``timeout=``.

    The fault-injection harness (``io/faultinject.py``) makes the failure
    mode concrete: under the wedged-socket plan an untimed call blocks
    FOREVER — a handler thread, a scrape, or a prober that never comes
    back. urllib's default is no timeout, so the only safe spelling is an
    explicit one at every call site. The timeout may be positional
    (``urlopen(url, data, t)`` / ``create_connection(addr, t)``) or a
    keyword.
    """

    code = "SMT011"
    name = "untimed-network-call"
    rationale = ("an untimed urlopen/socket connect wedges forever when "
                 "the peer stops answering; pass an explicit timeout=")

    # callable terminal name -> number of positional args that implies the
    # timeout was passed positionally
    _CALLS = {"urlopen": 3, "create_connection": 2}

    def check(self, module: Module) -> Iterable[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute):
                fname = node.func.attr
            elif isinstance(node.func, ast.Name):
                fname = node.func.id
            else:
                continue
            pos_needed = self._CALLS.get(fname)
            if pos_needed is None:
                continue
            if any(kw.arg == "timeout" for kw in node.keywords):
                continue
            if len(node.args) >= pos_needed:
                continue  # timeout passed positionally
            findings.append(self.finding(
                module, node,
                f"{fname}() without an explicit timeout= blocks forever "
                f"on a wedged peer; pass a timeout"))
        return findings


@register
class SilentExceptionSwallow(Rule):
    """SMT012 — silent exception swallowing in ``io/`` and
    ``observability/``.

    These packages are built from long-lived thread loops (dispatchers,
    probers, collectors, control loops). A bare ``except:`` — or a broad
    ``except Exception:`` whose body is only ``pass``/``continue`` inside
    a loop — makes such a loop eat its own death: the thread looks alive
    while serving nothing, which is the exact silent-failure mode the
    resilience layer exists to prevent. Swallowing deliberately is fine —
    say so by logging (or counting) what was swallowed; the handler then
    has a body and the rule passes. A bare ``except:`` that re-raises is
    also allowed (the narrow cleanup-then-reraise idiom).
    """

    code = "SMT012"
    name = "silent-exception-swallow"
    rationale = ("a swallowed exception in a serving/observability thread "
                 "loop turns a crash into a silent hang; log or count "
                 "what was swallowed")

    _SCOPES = (os.sep + os.path.join("synapseml_tpu", "io") + os.sep,
               os.sep + os.path.join("synapseml_tpu", "observability")
               + os.sep,
               # fixture paths: any io/ or observability/ directory
               os.sep + "io" + os.sep,
               os.sep + "observability" + os.sep)

    def _in_scope(self, module: Module) -> bool:
        path = os.path.abspath(module.path)
        return any(s in path for s in self._SCOPES)

    @staticmethod
    def _trivial_body(handler: ast.ExceptHandler) -> bool:
        return all(isinstance(s, (ast.Pass, ast.Continue))
                   for s in handler.body)

    @staticmethod
    def _reraises(handler: ast.ExceptHandler) -> bool:
        return any(isinstance(s, ast.Raise) for s in ast.walk(handler))

    def check(self, module: Module) -> Iterable[Finding]:
        if not self._in_scope(module):
            return []
        findings: List[Finding] = []

        class V(ast.NodeVisitor):
            def __init__(self):
                self.loops = 0

            def _loop(self, node):
                self.loops += 1
                self.generic_visit(node)
                self.loops -= 1

            visit_For = visit_While = _loop

            def visit_FunctionDef(self, node):
                # a handler inside a nested def is not "inside" the outer
                # loop — the function body runs whenever it is called
                saved, self.loops = self.loops, 0
                self.generic_visit(node)
                self.loops = saved

            visit_AsyncFunctionDef = visit_FunctionDef

            def visit_ExceptHandler(inner, node):
                bare = node.type is None
                broad = (isinstance(node.type, ast.Name)
                         and node.type.id in ("Exception", "BaseException"))
                if bare and not self._reraises(node):
                    findings.append(self.finding(
                        module, node,
                        "bare 'except:' swallows SystemExit/KeyboardInterrupt "
                        "too; catch Exception and log (or count) what was "
                        "swallowed"))
                elif broad and self._trivial_body(node) and inner.loops:
                    findings.append(self.finding(
                        module, node,
                        "'except Exception: pass' inside a loop lets a "
                        "thread loop eat its own death silently; log or "
                        "count the swallowed exception"))
                inner.generic_visit(node)

        V().visit(module.tree)
        return findings


@register
class AdHocMeshConstruction(Rule):
    """SMT013 — ad-hoc mesh construction outside the canonical layout.

    Every distributed path used to hand-roll its own 1-D
    ``jax.sharding.Mesh``, which is exactly how the repo ended up
    data-parallel-only (no model axis, no tensor-parallel serving, no
    feature-parallel histograms). Mesh construction now lives in ONE
    place — ``runtime/layout.py`` (``SpecLayout``) on top of
    ``runtime/topology.py`` (``make_mesh``) — so axis names, 2-D shapes
    and the (1, 1) degradation stay consistent across engines. Direct
    ``jax.sharding.Mesh(...)`` / ``make_mesh(...)`` calls anywhere else
    are findings (waiverable via ``LINT_ACKS.md`` for the rare
    deliberate exception).
    """

    code = "SMT013"
    name = "ad-hoc-mesh-construction"
    rationale = ("private meshes fragment sharding decisions and regress "
                 "to 1-D data parallelism; build layouts through "
                 "runtime.layout.SpecLayout")

    _ALLOWED_SUFFIXES = ("runtime/layout.py", "runtime/topology.py")

    def check(self, module: Module) -> Iterable[Finding]:
        rel = module.rel.replace(os.sep, "/")
        if any(rel.endswith(sfx) for sfx in self._ALLOWED_SUFFIXES):
            return []
        findings: List[Finding] = []
        mesh_aliases: Set[str] = set()   # names bound to the Mesh class
        module_aliases: Set[str] = set()  # names bound to the jax.sharding module
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom):
                if node.module == "jax.sharding":
                    for a in node.names:
                        if a.name == "Mesh":
                            mesh_aliases.add(a.asname or a.name)
                elif node.module == "jax":
                    for a in node.names:
                        if a.name == "sharding":
                            module_aliases.add(a.asname or a.name)
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "jax.sharding" and a.asname:
                        module_aliases.add(a.asname)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            dn = dotted_name(node.func)
            if dn is None:
                continue
            if dn in mesh_aliases or dn == "jax.sharding.Mesh" \
                    or dn.endswith(".sharding.Mesh") \
                    or any(dn == f"{m}.Mesh" for m in module_aliases):
                findings.append(self.finding(
                    module, node,
                    "ad-hoc jax.sharding.Mesh(...) construction; build the "
                    "mesh through runtime.layout.SpecLayout (canonical "
                    "axis names, 2-D shapes, (1,1) degradation)"))
            elif dn.split(".")[-1] == "make_mesh":
                findings.append(self.finding(
                    module, node,
                    "direct make_mesh(...) outside runtime/layout.py; use "
                    "runtime.layout.SpecLayout.build (or from_mesh) so "
                    "every engine shares one layout"))
        return findings


_WRONG_UNIT_SUFFIXES: Dict[str, str] = {
    # non-base-unit spellings -> the base unit Prometheus names use
    "_ms": "_seconds", "_millis": "_seconds", "_milliseconds": "_seconds",
    "_micros": "_seconds", "_us": "_seconds", "_nanos": "_seconds",
    "_ns": "_seconds", "_sec": "_seconds", "_secs": "_seconds",
    "_mins": "_seconds", "_minutes": "_seconds", "_hours": "_seconds",
    "_kb": "_bytes", "_mb": "_bytes", "_gb": "_bytes",
    "_kib": "_bytes", "_mib": "_bytes", "_gib": "_bytes",
}

# names that scream unbounded cardinality when they reach a label value:
# request/trace/span ids are unique per event, ports are unique per
# process incarnation — a label dict keyed by one grows the registry
# without bound and makes every scrape slower forever
_UNBOUNDED_LABEL_NAMES = {"rid", "request_id", "trace_id", "span_id",
                          "uuid", "request_uuid", "port"}


@register
class MetricNameDiscipline(Rule):
    """SMT014 — metric-name discipline on registry calls.

    Two invariants the whole exposition pipeline leans on:

    - **Unit-suffixed names.** Counters end ``_total`` (the OpenMetrics
      renderer strips it for family metadata — a counter without it
      produces spec-invalid OM and a failed scrape); nothing else ends
      ``_total``; timings/sizes use the base units ``_seconds``/``_bytes``
      (a ``_ms``/``_kb`` family breaks every recording rule and dashboard
      that assumes base units). Unitless gauges/histograms (ratios, MFU,
      batch sizes) are fine.
    - **Bounded label values.** ``labels(...)`` must never interpolate an
      unbounded value — a request id, trace id, span id, or port: one
      series per REQUEST is a memory leak wearing a label dict, and trace
      ids already have a first-class channel (exemplars). Detection is by
      value-expression name (``rid`` / ``request_id`` / ``trace_id`` /
      ``span_id`` / ``uuid`` / ``port``, bare or as an attribute or inside
      an f-string) and by direct ``uuid.*()`` calls. Bounded composite
      labels (``server_label = host:port`` retired on ``close()``) pass —
      the rule flags the raw signals, not every string containing digits.
    """

    code = "SMT014"
    name = "metric-name-discipline"
    rationale = ("non-base-unit or suffix-confused metric names break the "
                 "exposition contract; unbounded label values grow the "
                 "registry per request instead of per component")

    _CTORS = ("counter", "gauge", "histogram")

    def _name_findings(self, module: Module, node: ast.Call,
                       kind: str) -> Iterable[Finding]:
        if not (node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            return  # dynamic name: the runtime schema check owns it
        mname = node.args[0].value
        if kind == "counter" and not mname.endswith("_total"):
            yield self.finding(
                module, node.args[0],
                f"counter {mname!r} must end in '_total' (the OpenMetrics "
                f"renderer names counter families by stripping it)")
        elif kind != "counter" and mname.endswith("_total"):
            yield self.finding(
                module, node.args[0],
                f"{kind} {mname!r} ends in '_total', the counter "
                f"convention; rename or make it a counter")
        for suf, base in _WRONG_UNIT_SUFFIXES.items():
            if mname.endswith(suf):
                yield self.finding(
                    module, node.args[0],
                    f"metric {mname!r} uses non-base unit {suf!r}; record "
                    f"base units ({base!r}) and let the dashboard scale")
                break

    @staticmethod
    def _unbounded_expr(expr: ast.AST) -> Optional[str]:
        """The offending name when ``expr`` is an unbounded-cardinality
        value (bare name, attribute, uuid call, or an f-string
        interpolating one); None when it looks bounded."""
        if isinstance(expr, ast.Name) and expr.id in _UNBOUNDED_LABEL_NAMES:
            return expr.id
        if isinstance(expr, ast.Attribute):
            if expr.attr in _UNBOUNDED_LABEL_NAMES:
                return expr.attr
            if isinstance(expr.value, (ast.Call, ast.Attribute)):
                # uuid.uuid4().hex and friends: the id hides one hop down
                return MetricNameDiscipline._unbounded_expr(expr.value)
        if isinstance(expr, ast.Call):
            dn = dotted_name(expr.func)
            if dn and (dn.startswith("uuid.")
                       or dn.split(".")[-1] in ("uuid4", "uuid1")):
                return dn
        if isinstance(expr, ast.JoinedStr):
            for v in expr.values:
                if isinstance(v, ast.FormattedValue):
                    got = MetricNameDiscipline._unbounded_expr(v.value)
                    if got is not None:
                        return got
        return None

    def check(self, module: Module) -> Iterable[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            attr = node.func.attr
            if attr in self._CTORS:
                findings.extend(self._name_findings(module, node, attr))
            elif attr == "labels":
                values = list(node.args) + [kw.value for kw in node.keywords]
                for v in values:
                    bad = self._unbounded_expr(v)
                    if bad is not None:
                        findings.append(self.finding(
                            module, v,
                            f"unbounded value {bad!r} interpolated into a "
                            f"label: one series per request/trace/port "
                            f"incarnation grows the registry without "
                            f"bound — use a bounded label (trace ids "
                            f"belong in exemplars)"))
        return findings


# cache of "does this file use jax" verdicts, keyed by absolute path
_JAX_USING_CACHE: Dict[str, bool] = {}


def _file_uses_jax(path: str) -> bool:
    cached = _JAX_USING_CACHE.get(path)
    if cached is not None:
        return cached
    verdict = False
    try:
        with open(path, encoding="utf-8") as f:
            src = f.read()
        if "jax" in src:  # cheap pre-filter before parsing
            for node in ast.walk(ast.parse(src)):
                if _imports_jax(node):
                    verdict = True
                    break
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id == "lazy_import"
                        and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)
                        and _is_jax_module(node.args[0].value)):
                    verdict = True
                    break
    except (OSError, SyntaxError):
        verdict = False
    _JAX_USING_CACHE[path] = verdict
    return verdict


@register
class EagerJaxSubpackageInit(Rule):
    """SMT008 — a package ``__init__`` eagerly imports a jax-using
    submodule instead of exporting via ``core/lazyimport.py`` (PEP 562).

    ``import synapseml_tpu.gbdt`` must stay cheap and jax-free even though
    the trainer underneath uses jax everywhere: serving workers, scrapers
    and tools import packages at startup. The fix is
    ``lazy_module(__name__, {...})`` — attribute access imports the owning
    submodule on demand. Direct-submodule depth only (``from .boost import
    train``); the subprocess hygiene gate remains the transitive ground
    truth.
    """

    code = "SMT008"
    name = "eager-jax-subpackage-init"
    rationale = ("eager __init__ imports of jax-using submodules make "
                 "package import pay for the whole trainer")

    def check(self, module: Module) -> Iterable[Finding]:
        if not module.is_init:
            return []
        findings: List[Finding] = []
        for node in module.tree.body:
            targets: List[Tuple[str, str]] = []  # (display, abs path base)
            if isinstance(node, ast.ImportFrom) and node.level >= 1:
                base = module.dirname
                for _ in range(node.level - 1):
                    base = os.path.dirname(base)
                if node.module is None:
                    targets = [(a.name, os.path.join(
                        base, *a.name.split("."))) for a in node.names]
                else:
                    targets = [(node.module, os.path.join(
                        base, *node.module.split(".")))]
            elif isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.startswith("synapseml_tpu."):
                # absolute self-import: find the package root on the
                # FILESYSTEM (walk up to the 'synapseml_tpu' directory) —
                # rel-path depth depends on where the scan was rooted
                top = node.module.split(".")[0]
                root = module.dirname
                while (os.path.basename(root) != top
                       and os.path.dirname(root) != root):
                    root = os.path.dirname(root)
                if os.path.basename(root) == top:
                    targets = [(node.module, os.path.join(
                        os.path.dirname(root), *node.module.split(".")))]
            for display, base in targets:
                for cand in (base + ".py", os.path.join(base, "__init__.py")):
                    if os.path.isfile(cand) and _file_uses_jax(cand):
                        findings.append(self.finding(
                            module, node,
                            f"eager import of jax-using submodule "
                            f"{display!r} in package __init__; export via "
                            f"core.lazyimport.lazy_module (PEP 562)"))
                        break
        return findings
