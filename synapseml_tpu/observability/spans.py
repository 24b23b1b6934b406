"""Stage spans: wall-time / row-count / compile-vs-execute instrumentation.

Every ``Transformer.transform`` and ``Estimator.fit`` (wired in
``core/stage.py``) and the GBDT boosting loop (``gbdt/boost.py``) records a
span into the process-default :class:`~.metrics.MetricsRegistry`:

- ``smt_stage_duration_seconds{stage,method,cold}`` — histogram of span
  wall time, measured with the monotonic ``core.clock.StopWatch``. The
  ``cold`` label carries the compile-vs-execute split: ``cold="1"`` marks
  the first call of that method on that stage *instance* — for jitted
  stages that is the call paying trace + XLA compile, so warm-path latency
  (``cold="0"``) is queryable separately from compile spikes. A phase
  span INHERITS it: opened on the thread of a stage span that is
  ``cold="1"``, it records under ``cold="1"`` too, so the first call's
  phases (the model's import, the program's load, the first execution)
  are one account beside the call they are part of, and no warm series
  holds a set-up sample.
- ``smt_stage_rows_total{stage,method}`` — row throughput counter (rows =
  output rows for ``transform``, input rows for ``fit``). Call counts are
  the histogram's own ``_count`` (summed over ``cold``) — no separate
  counter, keeping the per-call cost down.
- ``smt_stage_errors_total{stage,method}`` — spans that raised (the
  duration is still observed, under the same labels).

Two kinds of span share the machinery. A **stage span** (``stage_span``:
one ``transform``/``fit`` of a stage instance) also runs the
device-profiling hook, which attributes FLOPs and samples device memory. A
**phase span** (``span``: a step inside a stage, such as
``ONNXModel.dispatch``) is plain: duration, rows, errors, nothing else;
outside every stage span it is ``cold="0"`` unless its caller says otherwise.

Every enabled span is also mirrored, for its duration, in two other views:

- the **profiler's trace**: the hook that ``observability.profiling``
  installs enters a ``jax.profiler.TraceAnnotation("smt.<stage>.<method>")``
  (when jax is already imported), so a capture taken by any means
  (``profile_trace``, ``Timer(profile_dir=)``, ``jax.profiler.trace``)
  holds the span on the caller's line, on the device trace's clock. The
  annotation is inert while no profile is being captured.
- the **request trace**: while a trace span is current in this thread the
  span begins a child ``TraceSpan`` and is current itself until it exits,
  so nested stage and phase spans form a tree in ``/traces``.

``disable()`` turns spans into no-ops (the bench microbench compares
on-vs-off; contract: < 5% per-transform overhead when ON — series lookups
are cached per (registry, stage, method), so the hot path is two monotonic
reads, three lock-protected adds, and one bisect).

``telemetry.log_stage_call`` is kept alongside for event-stream
compatibility; spans are the aggregate view, events the per-call view.
"""

from __future__ import annotations

import threading
import weakref
from time import perf_counter_ns as _now_ns  # the clock StopWatch wraps
from typing import Any, Optional

from . import tracing as _tracing
from .metrics import MetricsRegistry, get_registry

__all__ = ["span", "stage_span", "enable", "disable", "is_enabled", "Span",
           "set_profiler"]

_enabled = True

# Profiling hook (installed by ``observability.profiling``): an object with
# ``annotate(label, rows) -> entered context or None`` (every span: the
# profiler-trace mirror) and ``enter() -> token`` / ``exit(token, name,
# elapsed_s)`` (stage spans only: FLOPs/bytes of the profiled jit calls
# that ran inside, and a device-memory sample). Kept as a hook so this
# module stays stdlib-pure on its own.
_profiler = None


def set_profiler(profiler) -> None:
    """Install (or with ``None`` remove) the span profiling hook."""
    global _profiler
    _profiler = profiler


def enable() -> None:
    """Turn span recording on (the default)."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Turn span recording into no-ops (bench baseline / hot-path opt-out)."""
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    return _enabled


_cache_lock = threading.Lock()


class _Coldness(threading.local):
    """Whether the innermost stage span open on this thread is its
    instance's first call: what a phase span inherits."""
    cold = False


_COLD = _Coldness()


def _series_for(reg: MetricsRegistry, stage: str, method: str):
    """(duration_cold, duration_warm, rows, errors) series, then the
    span's name pair and its profiler label, cached ON the registry —
    family/label resolution off the per-call path, and the cache dies with
    the registry (a module-global cache would keep every swapped-out
    registry alive through the series backrefs)."""
    cache = reg.__dict__.get("_span_series_cache")
    if cache is None:
        with _cache_lock:
            cache = reg.__dict__.setdefault("_span_series_cache", {})
    key = (stage, method)
    got = cache.get(key)
    if got is not None:
        return got
    dur = reg.histogram(
        "smt_stage_duration_seconds",
        "stage span wall time; cold=1 marks an instance's first call "
        "(trace+compile included)", ("stage", "method", "cold"))
    rows = reg.counter("smt_stage_rows_total",
                       "rows through stage methods (transform: output rows; "
                       "fit: input rows)", ("stage", "method"))
    errors = reg.counter("smt_stage_errors_total",
                         "stage method calls that raised",
                         ("stage", "method"))
    got = (dur.labels(stage, method, "1"), dur.labels(stage, method, "0"),
           rows.labels(stage, method), errors.labels(stage, method),
           key, f"smt.{stage}.{method}")
    with _cache_lock:
        cache[key] = got
    return got


class Span:
    """Context manager recording one stage-method execution. Timing is the
    same monotonic clock ``core.clock.StopWatch`` wraps, read inline to
    keep the hot path at two clock reads + one histogram observe."""

    __slots__ = ("_dur", "_rows_c", "_errors", "_t0", "rows", "_name",
                 "_label", "_device", "_trace_span", "_prof0", "_annotation",
                 "_cold", "_cold_outside")

    def __init__(self, series, cold: bool, rows: Optional[int] = None,
                 device: bool = False):
        (dur_cold, dur_warm, self._rows_c, self._errors, self._name,
         self._label) = series
        self._dur = dur_cold if cold else dur_warm
        self._cold = cold
        self._device = device  # stage spans run the device-profiling hook
        self.rows = rows

    def set_rows(self, n: Optional[int]) -> None:
        self.rows = n

    def __enter__(self) -> "Span":
        # trace-context attachment: when a trace is active in this thread
        # (a serving engine activated the batch's pipeline span), this
        # span begins a child of the current span and is current itself
        # until it exits, so spans nested in it become ITS children. Cost
        # with no active trace: one module-bool check + one contextvar read.
        parent = (_tracing.current_span()
                  if _tracing.is_enabled() else None)
        self._trace_span = None if parent is None else \
            parent.tracer.begin_span(
                f"{self._name[0]}.{self._name[1]}", parent).__enter__()
        # cost with no hook installed: one module-global check
        prof = _profiler
        if prof is None:
            self._prof0 = self._annotation = None
        else:
            self._prof0 = prof.enter() if self._device else None
            self._annotation = prof.annotate(self._label, self.rows)
        if self._device:  # a stage span: its phases inherit its coldness
            self._cold_outside, _COLD.cold = _COLD.cold, self._cold
        self._t0 = _now_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        elapsed_s = (_now_ns() - self._t0) * 1e-9
        if self._device:
            _COLD.cold = self._cold_outside
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        ts = self._trace_span
        # the exemplar is the trace this span is part of, as the ambient
        # lookup would find; handed over, the lookup is not made again
        self._dur.observe(elapsed_s, None if ts is None else ts.trace_id,
                          ambient=False)
        cost = None
        if self._prof0 is not None and _profiler is not None:
            try:
                cost = _profiler.exit(self._prof0, self._name, elapsed_s)
            except Exception:
                pass  # accounting must never break the instrumented call
        if ts is not None:
            attrs = ts.attributes
            attrs["stage"], attrs["method"] = self._name
            if self.rows is not None:
                attrs["rows"] = self.rows
            if cost is not None:
                # the profiled device cost that ran inside this stage —
                # per-stage FLOPs/bytes readable straight off /traces
                attrs["flops"] = cost[0]
                if cost[1] > 0:
                    attrs["hbm_bytes"] = cost[1]
            ts.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            # rows only count on SUCCESS (a failed fit trained nothing;
            # counting its input would inflate throughput on every retry)
            self._errors.inc()
        elif self.rows is not None:
            self._rows_c.inc(self.rows)
        return False


class _NoopSpan:
    __slots__ = ()

    def set_rows(self, n) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopSpan()


def span(stage: str, method: str = "call", cold: bool = False,
         registry: Optional[MetricsRegistry] = None,
         rows: Optional[int] = None):
    """Record a phase span named (``stage``, ``method``) into ``registry``
    (the process default when omitted). ``rows`` known at entry also label
    the span's annotation in a profiler trace. Inside a stage span that is
    its instance's first call the span is ``cold`` whatever ``cold`` says.

    >>> with span("ingest", "decode") as sp:
    ...     sp.set_rows(128)
    """
    if not _enabled:
        return _NOOP
    return Span(_series_for(registry or get_registry(), stage, method),
                cold or _COLD.cold, rows)


def stage_span(stage_obj: Any, method: str):
    """Span for a pipeline-stage method call; tracks the cold/warm split per
    stage *instance* (first call of each method on an instance is cold).

    The warm-set is tagged with a weakref to its owner: ``Params.copy()``
    shallow-copies ``__dict__``, so a clone would otherwise alias the
    original's warm-set and have its genuinely cold first call recorded as
    warm. A weakref identity check cannot falsely match (unlike an id()
    tag, which CPython address reuse can resurrect). The warm-set is
    maintained even while spans are DISABLED: a first call that ran
    unrecorded during a disable() window must not make the next enabled
    call masquerade as the trace+compile one."""
    marker = getattr(stage_obj, "_span_warm_methods", None)
    if marker is None or marker[0]() is not stage_obj:
        try:
            marker = (weakref.ref(stage_obj), set(), {})
            stage_obj._span_warm_methods = marker
        except (AttributeError, TypeError):  # slotted/frozen/unweakrefable:
            marker = None                    # treat as always warm
    if marker is None:
        if not _enabled:
            return _NOOP
        return Span(_series_for(get_registry(),
                                type(stage_obj).__name__, method), False,
                    device=True)
    warm_set = marker[1]
    cold = method not in warm_set
    if cold:
        warm_set.add(method)
    if not _enabled:
        return _NOOP
    reg = get_registry()
    # per-instance series cache: method -> (registry, series); the registry
    # identity check invalidates entries across set_registry swaps
    cached = marker[2].get(method)
    if cached is None or cached[0] is not reg:
        series = _series_for(reg, type(stage_obj).__name__, method)
        marker[2][method] = (reg, series)
    else:
        series = cached[1]
    return Span(series, cold, device=True)
