"""Device-side performance observability: compile / HBM / FLOPs accounting.

The spans half of this subsystem answers *which stage* was slow; this
module answers *why the hardware was slow*. Its accountings are all merged
fleet-wide through the ordinary snapshot path and exposed at ``/metrics``:

- **Compile accounting** (:func:`profiled_jit`): every XLA compilation a
  wrapped entry point pays records a ``smt_compile_seconds{fn,backend}``
  histogram sample and bumps ``smt_recompiles_total{fn,cause}``, where
  ``cause`` names the abstract-signature change that forced the recompile
  (``first`` / ``shape`` / ``dtype`` / ``structure`` / ``static`` /
  ``weak_type`` / ``placement``). The compiled executable's ``cost_analysis()`` FLOPs and
  bytes are cached per signature, so every subsequent call is attributed
  at zero cost. The two halves of a compile are phase spans of their own,
  ``ProfiledJit.lower`` (the trace of this repo's Python and its lowering)
  and ``ProfiledJit.compile`` (XLA's compile, or the read of jax's
  persistent cache); which of the two it was is
  ``smt_compile_cache_total{fn,result=hit|miss}``, fed by jax's own
  monitoring events; what the loaded program holds on the device is
  ``smt_program_memory_bytes{fn,kind}``; and an entry point that gave the
  accounting up counts in ``smt_profiled_jit_fallback_total{fn,why}``.
- **FLOPs / bytes per stage**: calls through profiled entry points
  accumulate their executable's FLOPs/bytes into a thread-local; the
  stage-span hook (installed into ``observability.spans``) reads the
  delta at span exit and records ``smt_stage_flops_total`` /
  ``smt_stage_bytes_total{stage,method}`` — XLA's own count of the work a
  stage ran, and its arithmetic intensity (FLOPs÷bytes). Utilisation is
  the benchmark's to compute (``step_mfu``: model FLOPs from shapes over
  device time from a trace), not a host-clock ratio taken here.
- **Spans in the profiler's trace**: the same hook mirrors every enabled
  span (stage and phase) as a ``jax.profiler.TraceAnnotation`` named
  ``smt.<stage>.<method>``, and :class:`ProfiledJit` records the
  runtime's part of a dispatch as the ``ProfiledJit.execute`` span, so a
  profile names the host's phases on the device trace's clock.
- **Memory accounting**: per-stage ``smt_stage_hbm_live_bytes`` /
  ``smt_stage_hbm_peak_bytes`` gauges from ``device.memory_stats()``
  (graceful no-op on backends without allocator stats — CPU returns
  None), plus process-wide ``smt_device_hbm_*`` gauges synced at scrape
  time by a registry collector. Peak gauges are registered with
  ``merge="max"`` so a fleet merge reports the worst worker, not a
  meaningless sum (``observability.merge``).

Design constraints match the rest of the package: stdlib-only at import
(the no-jax-at-import gate covers this module), jax reached lazily inside
functions, and the hot path stays within the established <5% span budget
(``bench.py profiling_overhead``): a warm profiled call costs one
signature hash + two thread-local adds; a span exit with no profiled
calls inside costs two attribute reads.

Timeline export lives here too: :func:`chrome_trace_events` /
:func:`render_chrome_trace` turn a ``/traces`` payload (plus optional
telemetry events) into Chrome-trace / Perfetto JSON with one track per
process — ``tools/perf_timeline.py`` is the CLI, and every serving server
answers ``GET /timeline`` with the same rendering (the front door serves
the fleet-stitched timeline).
"""

from __future__ import annotations

import functools
import os
import sys
import threading
from time import perf_counter as _perf_counter
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

from . import spans as _spans
from .metrics import MetricsRegistry, get_registry

__all__ = [
    "PEAK_BF16_FLOPS",
    "ProfiledJit",
    "chrome_trace_events",
    "cost_snapshot",
    "disable",
    "enable",
    "install_memory_collector",
    "is_enabled",
    "memory_stats",
    "peak_flops",
    "profiled_jit",
    "render_chrome_trace",
    "update_memory_gauges",
]

# bf16 peak FLOPs by TPU generation (public figures). Nothing in the
# package reads it any more; ``bench.py`` and ``chip_smoke.py`` import it
# and it goes with ``bench.py`` (ROADMAP Design 3).
PEAK_BF16_FLOPS: Dict[str, float] = {
    "v5litepod": 197e12, "v5lite": 197e12, "v5e": 197e12,
    "v5p": 459e12, "v5": 459e12,
    "v6e": 918e12, "v6lite": 918e12,
    "v4": 275e12, "v3": 123e12, "v2": 45e12,
}


def peak_flops(device_kind: str) -> Optional[float]:
    """Peak bf16 FLOPs for a device kind string (substring match, most
    specific first), or the ``SMT_PEAK_FLOPS`` env override (how unknown
    hardware — or a test — supplies one). None when unknown, never
    guessed."""
    env = os.environ.get("SMT_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    kind = (device_kind or "").lower().replace(" ", "")
    for k, v in PEAK_BF16_FLOPS.items():
        if k in kind:
            return v
    return None


_enabled = True


def enable() -> None:
    """Turn device profiling on (the default) and re-install the span
    hook so spans resume their annotations and FLOPs/memory records."""
    global _enabled
    _enabled = True
    _spans.set_profiler(_PROFILER)


def disable() -> None:
    """Detach the span hook and stop all per-call accounting (profiled
    entry points fall back to their plain jitted path)."""
    global _enabled
    _enabled = False
    _spans.set_profiler(None)


def is_enabled() -> bool:
    return _enabled


# ---------------------------------------------------------------------------
# thread-local FLOPs/bytes accumulator: profiled calls add, span exits read
# ---------------------------------------------------------------------------

class _Accum(threading.local):
    flops = 0.0
    bytes = 0.0


_ACC = _Accum()


def cost_snapshot() -> Tuple[float, float]:
    """This thread's monotone ``(flops, bytes)`` accumulators. Engines
    snapshot around a pipeline batch and difference the two reads — the
    delta is the device cost of everything profiled that ran in between,
    which the serving layer attributes to the batch's REQUESTS
    (``smt_request_flops`` / ``smt_request_hbm_bytes``) and feeds into
    the cost-aware shedder (``io/serving.py``)."""
    acc = _ACC
    return (acc.flops, acc.bytes)


def _series_cache(reg: MetricsRegistry) -> Dict[Any, Any]:
    """Per-registry series cache (same pattern as spans._series_for: the
    cache dies with the registry, so swapped-out test registries are not
    kept alive through series backrefs)."""
    cache = reg.__dict__.get("_profiling_series_cache")
    if cache is None:
        cache = reg.__dict__.setdefault("_profiling_series_cache", {})
    return cache


# ---------------------------------------------------------------------------
# device peak / memory probes (never import jax; never initialize it)
# ---------------------------------------------------------------------------

def _jax_if_loaded():
    """The jax module ONLY if something else already imported it. A
    metrics scrape or span exit must never be the thing that drags jax
    (slow, environment-sensitive) into a process."""
    return sys.modules.get("jax")


class _DeviceState:
    """Lazily probed, cached view of the local devices: (device objects,
    whether memory_stats() yields anything). Re-probed only while jax is
    absent; once devices exist the answer is final."""

    def __init__(self):
        self._lock = threading.Lock()
        self.devices: Optional[List[Any]] = None
        self.has_memory_stats = False

    def probe(self):
        if self.devices is not None:
            return self
        jax = _jax_if_loaded()
        if jax is None:
            return self
        # device discovery OUTSIDE the lock (SMT007: no jax dispatch in a
        # critical section); a racing second prober computes the same
        # answer and the guarded publish below keeps one winner
        try:
            devices = list(jax.local_devices())
        except Exception:
            return self
        has_stats = False
        for d in devices:
            try:
                has_stats = d.memory_stats() is not None
            except Exception:
                has_stats = False
            break
        with self._lock:
            if self.devices is None:
                self.has_memory_stats = has_stats
                self.devices = devices
        return self


_DEV = _DeviceState()


def memory_stats() -> Optional[List[Tuple[str, Dict[str, int]]]]:
    """``(device_label, memory_stats dict)`` for every local device that
    reports allocator stats; None when jax is not loaded or the backend
    has none (CPU). Never initializes jax."""
    st = _DEV.probe()
    if not st.devices or not st.has_memory_stats:
        return None
    out = []
    for d in st.devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms:
            out.append((f"{d.platform}:{d.id}", ms))
    return out or None


def update_memory_gauges(registry: Optional[MetricsRegistry] = None,
                         stats: Optional[Sequence[Tuple[str, Dict[str, int]]]]
                         = None) -> bool:
    """Sync HBM gauges from ``device.memory_stats()`` into ``registry``:

    - ``smt_device_hbm_live_bytes{device}`` — bytes in use now (fleet
      merge: SUM — total footprint across workers);
    - ``smt_device_hbm_peak_bytes{device}`` — allocator high watermark
      (fleet merge: MAX — the worst worker, a sum would be meaningless);
    - ``smt_process_hbm_peak_bytes`` — process-wide high watermark: the
      summed per-device peaks, monotone over scrapes (merge: MAX).

    ``stats`` injects readings (tests / exotic backends); the default
    reads live devices. Returns True when gauges were updated — False is
    the graceful no-op (CPU, jax absent)."""
    if stats is None:
        stats = memory_stats()
    if not stats:
        return False
    reg = registry or get_registry()
    live = reg.gauge("smt_device_hbm_live_bytes",
                     "device bytes in use at last scrape", ("device",))
    peak = reg.gauge("smt_device_hbm_peak_bytes",
                     "device allocator high watermark", ("device",),
                     merge="max")
    proc = reg.gauge("smt_process_hbm_peak_bytes",
                     "process-wide HBM high watermark (summed device peaks)",
                     merge="max")
    total_peak = 0.0
    for label, ms in stats:
        live.labels(label).set(float(ms.get("bytes_in_use", 0)))
        p = float(ms.get("peak_bytes_in_use", ms.get("bytes_in_use", 0)))
        peak.labels(label).set(p)
        total_peak += p
    proc.set_max(total_peak)  # atomic monotone watermark
    return True


def install_memory_collector(registry: Optional[MetricsRegistry] = None
                             ) -> None:
    """Register :func:`update_memory_gauges` as a snapshot-time collector
    on ``registry`` (idempotent per registry): HBM gauges refresh at
    scrape frequency, never on a request hot path. Serving servers call
    this at startup so every worker's ``/metrics`` carries its memory
    view into the fleet merge."""
    reg = registry or get_registry()
    if reg.__dict__.get("_profiling_mem_collector"):
        return
    reg.__dict__["_profiling_mem_collector"] = True

    def _collect(_reg_ref=reg):
        update_memory_gauges(_reg_ref)

    # keep a strong ref on the registry: register_collector holds weakrefs
    reg.__dict__["_profiling_mem_collector_fn"] = _collect
    reg.register_collector(_collect)


# ---------------------------------------------------------------------------
# span hook: profiler annotation per span; FLOPs/memory per stage span
# ---------------------------------------------------------------------------

class _SpanProfiler:
    """Installed into ``observability.spans``. For every span,
    ``annotate()`` enters the span's mirror in the profiler's trace. For
    stage spans, ``enter()`` snapshots the thread-local FLOPs/bytes
    counters and ``exit()`` attributes the delta — the profiled-jit calls
    that ran inside the span — to the stage."""

    def annotate(self, label, rows):
        """An entered ``TraceAnnotation(label)`` (the caller exits it), or
        None while jax is not loaded. No switch: the annotation is inert
        unless a profile is being captured, and then it puts the span on
        the caller's line of that capture."""
        jax = _jax_if_loaded()
        if jax is None:
            return None
        if rows is None:
            ann = jax.profiler.TraceAnnotation(label)
        else:
            ann = jax.profiler.TraceAnnotation(label, rows=rows)
        ann.__enter__()
        return ann

    def enter(self):
        acc = _ACC
        return (acc.flops, acc.bytes)

    def exit(self, t0, name, elapsed_s, registry=None):
        """Attribute the profiled cost that ran inside the span; returns
        ``(dflops, dbytes)`` so the span can carry the figures into its
        trace record (per-stage cost visible in ``/traces``), or None
        when nothing profiled ran."""
        acc = _ACC
        dflops = acc.flops - t0[0]
        dbytes = acc.bytes - t0[1]
        st = _DEV.probe()
        if dflops <= 0.0 and not st.has_memory_stats:
            return None
        reg = registry or get_registry()
        cache = _series_cache(reg)
        if dflops > 0.0:
            key = ("span", name)
            got = cache.get(key)
            if got is None:
                flops_c = reg.counter(
                    "smt_stage_flops_total",
                    "cost_analysis FLOPs executed by profiled jit entry "
                    "points inside stage spans", ("stage", "method"))
                bytes_c = reg.counter(
                    "smt_stage_bytes_total",
                    "cost_analysis bytes accessed inside stage spans "
                    "(FLOPs/bytes = roofline arithmetic intensity)",
                    ("stage", "method"))
                got = cache[key] = (flops_c.labels(*name),
                                    bytes_c.labels(*name))
            flops_s, bytes_s = got
            flops_s.inc(dflops)
            if dbytes > 0.0:
                bytes_s.inc(dbytes)
        if st.has_memory_stats:
            stats = memory_stats()
            if stats:
                # series created only on backends that report allocator
                # stats: a CPU process must not grow zero-valued HBM
                # series for every stage it runs
                key = ("span_mem", name)
                got = cache.get(key)
                if got is None:
                    live_g = reg.gauge(
                        "smt_stage_hbm_live_bytes",
                        "device bytes in use at span exit",
                        ("stage", "method"))
                    peak_g = reg.gauge(
                        "smt_stage_hbm_peak_bytes",
                        "allocator high watermark observed at span exit",
                        ("stage", "method"), merge="max")
                    got = cache[key] = (live_g.labels(*name),
                                        peak_g.labels(*name))
                live_s, peak_s = got
                live = sum(ms.get("bytes_in_use", 0) for _, ms in stats)
                pk = sum(ms.get("peak_bytes_in_use", 0) for _, ms in stats)
                live_s.set(float(live))
                peak_s.set_max(float(pk))  # atomic monotone watermark
        if dflops > 0.0:
            return (dflops, dbytes)
        return None


_PROFILER = _SpanProfiler()


# ---------------------------------------------------------------------------
# profiled jit: compile accounting + per-executable cost analysis
# ---------------------------------------------------------------------------

def _classify_recompile(prev_sig, new_sig) -> str:
    """Name the abstract-signature change that forced a recompile. The
    label keys ``smt_recompiles_total{fn,cause}`` — a counter that grows
    under ``shape`` churn is a missing-padding bug, under ``weak_type`` a
    python-scalar-vs-array bug, under ``static`` a config churn."""
    if prev_sig is None:
        return "first"
    p_tree, p_avals, p_place, p_static = prev_sig
    n_tree, n_avals, n_place, n_static = new_sig
    if p_static != n_static:
        return "static"
    if p_tree != n_tree or len(p_avals) != len(n_avals):
        return "structure"
    shapes = dtypes = weak = False
    for pa, na in zip(p_avals, n_avals):
        if getattr(pa, "shape", None) != getattr(na, "shape", None):
            shapes = True
        elif getattr(pa, "dtype", None) != getattr(na, "dtype", None):
            dtypes = True
        elif getattr(pa, "weak_type", None) != getattr(na, "weak_type", None):
            weak = True
    if shapes:
        return "shape"
    if dtypes:
        return "dtype"
    if weak:
        return "weak_type"
    if p_place != n_place:
        return "placement"
    return "other"


def _cost_entry(obj) -> Tuple[float, float]:
    """(flops, bytes accessed) out of a ``cost_analysis()`` result, which
    is a dict on single-device programs and a per-partition list under
    SPMD; missing keys read as 0 (TPU backends sometimes omit bytes)."""
    try:
        ca = obj.cost_analysis()
    except Exception:
        return (0.0, 0.0)
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return (0.0, 0.0)
    return (float(ca.get("flops", 0.0) or 0.0),
            float(ca.get("bytes accessed", 0.0) or 0.0))


class _CompiledEntry:
    __slots__ = ("compiled", "flops", "bytes")

    def __init__(self, compiled, flops, bytes_):
        self.compiled = compiled
        self.flops = flops
        self.bytes = bytes_


_BOUND = "__bound_arguments__"  # marks ``ProfiledJit.bind``'s signature entry


class _Compiling(threading.local):
    """The ``ProfiledJit`` whose ``lowered.compile()`` runs on this thread."""
    jit = None


_COMPILING = _Compiling()
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_listening = False
_listening_lock = threading.Lock()


def _on_jax_event(event: str, **_kw) -> None:
    """jax's persistent compile cache says what a compile was: a ``hit``
    (the program was read from the cache) or a ``miss`` (it was compiled
    and written there). Counted for the ``ProfiledJit`` that is compiling
    on this thread; any other compile, and every other event, is not
    this accounting's."""
    result = _CACHE_EVENTS.get(event)
    pj = _COMPILING.jit
    if result is not None and pj is not None:
        _families(get_registry()).cache.labels(pj.name, result).inc()


def _listen_to_jax() -> None:
    """Register the listener once a process; called where jax is known
    loaded (a compile is about to run)."""
    global _listening
    if _listening:
        return
    with _listening_lock:
        if not _listening:
            from jax import monitoring

            monitoring.register_event_listener(_on_jax_event)
            _listening = True


_MEMORY_KINDS = (("arguments", "argument_size_in_bytes"),
                 ("outputs", "output_size_in_bytes"),
                 ("temporaries", "temp_size_in_bytes"),
                 ("code", "generated_code_size_in_bytes"))


class _LoadFamilies(NamedTuple):
    cache: Any     # smt_compile_cache_total{fn,result}
    fallback: Any  # smt_profiled_jit_fallback_total{fn,why}
    memory: Any    # smt_program_memory_bytes{fn,kind}


def _families(reg: MetricsRegistry) -> _LoadFamilies:
    """The three families of ``reg`` that say what a load was, which entry
    points left the accounting, and what a loaded program holds. Declared
    together at an entry point's first compile, so
    a registry that has seen a compile has all three and a healthy zero
    reads as a family with no series, not as a missing family."""
    cache = _series_cache(reg)
    got = cache.get("load")
    if got is None:
        got = cache["load"] = _LoadFamilies(
            reg.counter(
                "smt_compile_cache_total",
                "compiles of profiled jit entry points by what jax's "
                "persistent cache did: hit (read from it) or miss "
                "(compiled, then written to it)", ("fn", "result")),
            reg.counter(
                "smt_profiled_jit_fallback_total",
                "profiled jit entry points that left the profiled path for "
                "plain jax.jit: their compiles go unrecorded from then on",
                ("fn", "why")),
            reg.gauge(
                "smt_program_memory_bytes",
                "device bytes of a compiled program by kind, from "
                "memory_analysis(): the largest of an entry point's "
                "signatures", ("fn", "kind"), merge="max"))
    return got


class ProfiledJit:
    """``jax.jit`` with compile/cost accounting.

    Owns a signature -> compiled-executable cache (jax's AOT path:
    ``jit(fn).lower(...).compile()``), so every compilation is observed
    exactly once — timed into ``smt_compile_seconds{fn,backend}``, its
    cause recorded in ``smt_recompiles_total{fn,cause}``, and its
    ``cost_analysis()`` FLOPs/bytes cached so warm calls attribute cost
    to the enclosing stage span for free.

    Transparent fallbacks keep the computation unconditionally safe:
    tracer arguments (the wrapper called inside an enclosing jit — the
    compile belongs to the outer program), profiling disabled, or any
    failure of the AOT machinery route through a plain ``jax.jit`` of the
    same function. The wrapped function must not rely on donation.
    """

    def __init__(self, fn, name: Optional[str] = None,
                 static_argnames: Sequence[str] = ()):
        self._fn = fn
        self.name = name or getattr(fn, "__name__", "fn")
        self._static_argnames = tuple(static_argnames)
        self._lock = threading.Lock()
        self._cache: Dict[Any, _CompiledEntry] = {}
        self._last_sig = None
        self._plain = None
        self._left_profiled_path = False
        # (avals, placements) of arguments bound by ``bind`` -> the small
        # integer that stands for them in a signature
        self._bound_tokens: Dict[Any, int] = {}

    def _plain_jit(self):
        if self._plain is None:
            import jax

            self._plain = jax.jit(
                self._fn, static_argnames=self._static_argnames or None)
        return self._plain

    def _split(self, kwargs):
        """(dynamic kwargs, static kwargs sorted tuple). Static args are
        accepted by KEYWORD only — that is how every call site in this
        repo passes them, and it keeps the dynamic positional args
        exactly the tuple the compiled executable expects."""
        if not self._static_argnames:
            return kwargs, ()
        dyn = {k: v for k, v in kwargs.items()
               if k not in self._static_argnames}
        static = tuple((k, kwargs[k]) for k in self._static_argnames
                       if k in kwargs)
        return dyn, static

    def bind(self, *arrays):
        """``call(*args)`` == ``self(*args, *arrays)`` for trailing arguments
        that are the same arrays on every call (a model's weights, placed
        once). Their shapes, types and placements join the signature HERE,
        once, as one small integer: a call keys on what changes between
        calls, not on hundreds of weights it has seen before."""
        from jax.api_util import shaped_abstractify

        key = (tuple(shaped_abstractify(x) for x in arrays),
               tuple(getattr(x, "sharding", None) for x in arrays))
        with self._lock:
            token = self._bound_tokens.setdefault(
                key, len(self._bound_tokens))
        return functools.partial(self._call, (_BOUND, token), arrays)

    def __call__(self, *args, **kwargs):
        return self._call(None, (), *args, **kwargs)

    def _call(self, bound_key, bound, *args, **kwargs):
        import jax

        if not _enabled or self._left_profiled_path:
            return self._plain_jit()(*args, *bound, **kwargs)
        dyn_kwargs, static = self._split(kwargs)
        if bound_key is not None:
            static += (bound_key,)
        try:
            leaves, treedef = jax.tree_util.tree_flatten((args, dyn_kwargs))
        except Exception:
            return self._plain_jit()(*args, *bound, **kwargs)
        tracer = jax.core.Tracer
        for leaf in leaves:
            if isinstance(leaf, tracer):
                # under an outer trace the compilation (and its cost) is
                # the OUTER program's; inline like plain jit would
                return self._plain_jit()(*args, *bound, **kwargs)
        try:
            from jax.api_util import shaped_abstractify

            avals = tuple(shaped_abstractify(x) for x in leaves)
            # shardings join the key: a Compiled executable is pinned to
            # its input placement, and calling it with same-shaped arrays
            # on another device raises instead of recompiling the way
            # plain jit would — distinct placements get distinct entries
            placements = tuple(getattr(x, "sharding", None) for x in leaves)
            sig = (treedef, avals, placements, static)
        except Exception:
            return self._plain_jit()(*args, *bound, **kwargs)
        entry = self._cache.get(sig)
        if entry is not None:
            # track the last USED signature so a later recompile's cause
            # names what changed relative to the call stream, not
            # relative to whichever compile happened to come last
            self._last_sig = sig
        else:
            entry = self._compile(sig, args + bound, kwargs)
            if entry is None:
                # AOT lower/compile failed. The plain path re-traces: a
                # genuine user error re-raises with its natural traceback;
                # success means the AOT machinery specifically is broken
                # for this fn — stop retrying it (accounting is optional,
                # the computation is not).
                out = self._plain_jit()(*args, *bound, **kwargs)
                self._leave_profiled_path("compile_failed",
                                          "lower/compile failed")
                return out
        try:
            # the runtime's part of a dispatch: enqueue, and as much of
            # the upload as is synchronous. What the caller's own span
            # round this call holds beyond it is the keying above.
            with _spans.span("ProfiledJit", "execute"):
                out = entry.compiled(*args, *bound, **dyn_kwargs)
        except (TypeError, ValueError) as e:
            # calling-convention or placement mismatch the signature key
            # did not capture (donation, exotic shardings): permanent
            # plain fallback for this fn — plain jit handles these by
            # recompiling, and accounting is optional
            self._leave_profiled_path("call_refused",
                                      f"compiled call refused: {e}")
            return self._plain_jit()(*args, *bound, **kwargs)
        acc = _ACC
        acc.flops += entry.flops
        acc.bytes += entry.bytes
        return out

    def _leave_profiled_path(self, why: str, detail: str) -> None:
        """This entry point runs through plain ``jax.jit`` from here on:
        the computation is unaffected, its compiles and costs go
        unrecorded. Said once, as a warning and as one count of
        ``smt_profiled_jit_fallback_total{fn,why}`` — every FLOPs and
        compile-time figure for ``name`` is missing from then on, so a
        window with no recorded compile is healthy only where this
        counter stands at zero."""
        import logging

        self._left_profiled_path = True
        _families(get_registry()).fallback.labels(self.name, why).inc()
        logging.getLogger("synapseml_tpu").warning(
            "profiled jit %r left the profiled path (%s); compile and cost "
            "accounting for it stop here", self.name, detail)

    def _compile(self, sig, args, full_kwargs):
        # the lock is deliberately NOT held across lower/compile (lint
        # SMT007: no jax dispatch inside a critical section — a
        # multi-second XLA compile under a lock would serialize every
        # other thread's warm calls too). Two threads racing the same
        # first signature may both compile; the insert below makes one
        # winner and the loser's executable (and its accounting) is
        # dropped, so compiles are still recorded exactly once.
        import jax

        _listen_to_jax()
        _families(get_registry())  # a healthy zero needs its family
        t0 = _perf_counter()
        try:
            # this repo's part: the function's Python traced, then lowered
            with _spans.span("ProfiledJit", "lower"):
                lowered = jax.jit(
                    self._fn,
                    static_argnames=self._static_argnames or None,
                ).lower(*args, **full_kwargs)
            # XLA's part, or the cache's: the listener says which it was
            _COMPILING.jit = self
            try:
                with _spans.span("ProfiledJit", "compile"):
                    compiled = lowered.compile()
            finally:
                _COMPILING.jit = None
        except Exception:
            import logging

            logging.getLogger("synapseml_tpu").debug(
                "AOT lower/compile of %s failed", self.name, exc_info=True)
            return None  # caller re-runs through plain jit (see __call__)
        dt = _perf_counter() - t0
        flops, bytes_ = _cost_entry(compiled)
        if flops == 0.0 and bytes_ == 0.0:
            flops, bytes_ = _cost_entry(lowered)
        entry = _CompiledEntry(compiled, flops, bytes_)
        with self._lock:
            existing = self._cache.get(sig)
            if existing is not None:
                return existing  # lost the race: exactly-once accounting
            self._cache[sig] = entry
        cause = _classify_recompile(self._last_sig, sig)
        self._last_sig = sig
        self._record_compile(dt, cause, flops)
        self._record_memory(compiled)
        return entry

    def _record_memory(self, compiled) -> None:
        """What the loaded program holds on the device, by kind; silent
        where the runtime gives no ``memory_analysis()``."""
        try:
            stats = compiled.memory_analysis()
        except Exception:
            return
        if isinstance(stats, (list, tuple)):  # one a partition under SPMD
            stats = stats[0] if stats else None
        if stats is None:
            return
        gauge = _families(get_registry()).memory
        for kind, attr in _MEMORY_KINDS:
            value = getattr(stats, attr, None)
            if value is not None:
                gauge.labels(self.name, kind).set_max(float(value))

    def _record_compile(self, dt: float, cause: str, flops: float) -> None:
        jax = _jax_if_loaded()
        backend = jax.default_backend() if jax is not None else "?"
        reg = get_registry()
        cache = _series_cache(reg)
        key = ("compile", self.name, backend, cause)
        got = cache.get(key)
        if got is None:
            comp_h = reg.histogram(
                "smt_compile_seconds",
                "XLA lower+compile wall time per profiled jit entry point",
                ("fn", "backend"))
            rec_c = reg.counter(
                "smt_recompiles_total",
                "compilations by the signature change that caused them",
                ("fn", "cause"))
            got = cache[key] = (comp_h.labels(self.name, backend),
                                rec_c.labels(self.name, cause))
        got[0].observe(dt)
        got[1].inc()
        # the per-call event view joins compiles against /traces too
        from ..core import telemetry

        telemetry.log_event("xla_compile", className="profiling",
                            uid=self.name, duration_s=dt, cause=cause,
                            backend=backend, flops=flops)


def profiled_jit(fn=None, *, name: Optional[str] = None,
                 static_argnames: Sequence[str] = ()):
    """Wrap ``fn`` in a :class:`ProfiledJit` (decorator or call form).

    >>> step = profiled_jit(_step_impl, name="gbdt.step")
    """
    if fn is None:
        return lambda f: ProfiledJit(f, name=name,
                                     static_argnames=static_argnames)
    return ProfiledJit(fn, name=name, static_argnames=static_argnames)


# ---------------------------------------------------------------------------
# Chrome-trace / Perfetto timeline export
# ---------------------------------------------------------------------------

def chrome_trace_events(payload: Dict[str, Any],
                        events: Optional[Sequence[Dict[str, Any]]] = None
                        ) -> List[Dict[str, Any]]:
    """Render a ``/traces`` payload (one server's flight recorder or the
    front door's stitched fleet view) as Chrome-trace events.

    Spans become complete events (``ph="X"``) with ``ts``/``dur`` in
    microseconds of wall clock; each emitting PROCESS gets its own
    ``pid`` track (spans carry the recording process's pid — that is what
    stitches a ``ProcessServingFleet`` into per-worker tracks), each
    trace its own ``tid`` row within the process, and metadata events
    name the tracks. Telemetry events (``core.telemetry`` dicts, e.g.
    ``drain_events()``) render as instant events on the same clock; when
    one carries a ``trace_id`` it lands on that trace's row.
    """
    out: List[Dict[str, Any]] = []
    tid_by_key: Dict[Tuple[int, str], int] = {}
    next_tid: Dict[int, int] = {}
    pid_names: Dict[int, str] = {}

    def track(pid: int, trace_id: str) -> int:
        key = (pid, trace_id)
        tid = tid_by_key.get(key)
        if tid is None:
            tid = tid_by_key[key] = next_tid.get(pid, 1)
            next_tid[pid] = tid + 1
        return tid

    traces = [t for t in (payload.get("traces") or []) if isinstance(t, dict)]
    for trace in traces:
        tid_str = str(trace.get("trace_id", "?"))
        for s in trace.get("spans") or []:
            if not isinstance(s, dict):
                continue
            pid = int(s.get("pid") or 0)
            attrs = s.get("attributes") or {}
            if pid not in pid_names and attrs.get("server"):
                pid_names[pid] = str(attrs["server"])
            args = dict(attrs)
            args["trace_id"] = tid_str
            args["span_id"] = s.get("span_id")
            if s.get("parent_id"):
                args["parent_id"] = s["parent_id"]
            if s.get("status") and s["status"] != "OK":
                args["status"] = s["status"]
            out.append({
                "ph": "X",
                "name": str(s.get("name", "?")),
                "cat": "span",
                "ts": float(s.get("start_ts") or 0.0) * 1e6,
                "dur": max(float(s.get("duration_s") or 0.0), 0.0) * 1e6,
                "pid": pid,
                "tid": track(pid, tid_str),
                "args": args,
            })
    ev_tid_default: Dict[int, int] = {}
    for e in events or []:
        if not isinstance(e, dict) or "ts" not in e:
            continue
        pid = int(e.get("pid") or 0)
        tid_str = e.get("trace_id")
        if tid_str is not None and (pid, str(tid_str)) in tid_by_key:
            tid = tid_by_key[(pid, str(tid_str))]
        else:
            tid = ev_tid_default.setdefault(pid, 0)
        args = {k: v for k, v in e.items() if k not in ("ts", "pid")}
        out.append({
            "ph": "i",
            "s": "t",
            "name": f"{e.get('className', '?')}.{e.get('method', 'event')}",
            "cat": "telemetry",
            "ts": float(e["ts"]) * 1e6,
            "pid": pid,
            "tid": tid,
            "args": args,
        })
    # track-name metadata: one process_name per pid, one thread_name per
    # trace row (root span name + trace id prefix)
    for pid in sorted(set([p for p, _ in tid_by_key]) | set(ev_tid_default)):
        out.append({"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                    "ts": 0,
                    "args": {"name": pid_names.get(pid) or f"process {pid}"}})
    roots = {str(t.get("trace_id", "?")): t.get("root") or "trace"
             for t in traces}
    for (pid, tid_str), tid in sorted(tid_by_key.items(),
                                      key=lambda kv: kv[1]):
        out.append({"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                    "ts": 0,
                    "args": {"name": f"{roots.get(tid_str, 'trace')} "
                                     f"{tid_str[:8]}"}})
    return out


def render_chrome_trace(payload: Dict[str, Any],
                        events: Optional[Sequence[Dict[str, Any]]] = None
                        ) -> Dict[str, Any]:
    """``/traces`` payload -> a complete Chrome-trace JSON object (open
    in Perfetto / ``chrome://tracing``). Served at ``GET /timeline`` on
    every serving server; the routing front door renders the stitched
    fleet view, so one download shows router + every worker process as
    separate tracks on one wall-clock axis."""
    return {"traceEvents": chrome_trace_events(payload, events),
            "displayTimeUnit": "ms"}


# install the span hook at import: profiling is on by default, same as
# spans — the hook costs two attribute reads per span when nothing
# profiled ran inside it (benched by ``bench.py profiling_overhead``)
_spans.set_profiler(_PROFILER)

