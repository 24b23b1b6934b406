"""Multi-tenant serving: one fleet, many models.

The reference's Spark Serving turns ONE pipeline into a web service; a
production TPU fleet serves a zoo. Every hard single-tenant part already
exists — generation-tagged hot swap (``io/lifecycle.py``), burn-rate SLOs
(``observability/slo.py``), per-request FLOPs/HBM cost attribution, the
breaker/hedge/deadline control plane (``io/resilience.py``) — and this
module composes them into a tenancy subsystem instead of N parallel fleets:

- :class:`ModelCatalog` — the BOUNDED registry of model ids: model id ->
  saved-stage path + generation + resource class (derived from the cost
  EWMAs the serving engines report per batch). Every ``model`` metric /
  span label in the system comes from this catalog, never from request
  data — the bounded-cardinality contract lint SMT014 enforces.
- :class:`ResidencySet` — the per-worker LRU of resident pipelines: a
  worker holds up to ``capacity`` models hot, each behind its OWN
  generation-tagged :class:`~synapseml_tpu.io.lifecycle.WorkerLifecycle`
  slot, so swapping one model never touches the others; an evicted
  model's next request faults it back in from its saved stage (its
  programs compile again, through jax's persistent cache where the
  deployment has one).
- :func:`plan_placement` + :class:`PlacementBoard` — cost-driven
  placement: per-model FLOPs/HBM EWMAs classify tenants into resource
  classes; expensive models get isolated workers, cheap chatty ones are
  co-located. Decisions land in the telemetry ring and the router serves
  the current assignment at ``GET /placement``.

Requests pick their tenant with the ``X-SMT-Model`` header (or a
``model=`` query parameter); the routing front door validates it against
the catalog, keys breakers / retry budgets / SLO monitors by it, and the
worker-side displacement shedder only ever displaces the SAME tenant's
queued work — one model's overload burns only its own error budget.

Stdlib-only and import-pure (covered by the no-jax-at-import gate in
``tests/test_import_hygiene.py``), same design constraints as the rest of
the io/ layer.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..core.telemetry import get_logger, log_event

__all__ = [
    "MODEL_HEADER",
    "CatalogEntry",
    "ModelCatalog",
    "PlacementBoard",
    "ResidencySet",
    "RESOURCE_CLASSES",
    "model_from_request",
    "plan_placement",
]

_logger = get_logger("io.tenancy")

# the tenant-selection header a client (or the routing front door, which
# re-stamps it on every forward) uses to pick a model; ``?model=`` in the
# query string is the curl-friendly spelling
MODEL_HEADER = "X-SMT-Model"

# resource classes, cheap to expensive; thresholds on the per-request
# FLOPs EWMA the engines report (note_cost). "standard" is the default
# for models with no cost history yet — classification must never block
# serving on profiling coverage.
LIGHT, STANDARD, HEAVY = "light", "standard", "heavy"
RESOURCE_CLASSES = (LIGHT, STANDARD, HEAVY)


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def model_from_request(headers: Optional[Dict[str, str]],
                       path: str = "") -> Optional[str]:
    """The tenant a request selects: the ``X-SMT-Model`` header, else a
    ``model=`` query parameter; None when the request names no model
    (single-tenant deployments never see one)."""
    if headers:
        for k, v in headers.items():
            if k.lower() == MODEL_HEADER.lower() and v:
                return v
    query = path.partition("?")[2]
    for part in query.split("&"):
        key, _, val = part.partition("=")
        if key == "model" and val:
            return val
    return None


@dataclasses.dataclass
class CatalogEntry:
    """One tenant: where its pipeline lives, which generation is current,
    and what it costs to serve (EWMAs over the engines' per-batch cost
    attribution — the signal behind placement)."""

    model: str
    stage_path: str
    generation: int = 0
    flops_per_req: Optional[float] = None
    hbm_per_req: Optional[float] = None
    resource_class: Optional[str] = None  # None = classify from cost

    def classify(self, light_max_flops: float,
                 heavy_min_flops: float) -> str:
        """The resource class: pinned when set explicitly, else derived
        from the FLOPs-per-request EWMA; ``standard`` on no history."""
        if self.resource_class in RESOURCE_CLASSES:
            return self.resource_class
        f = self.flops_per_req
        if f is None:
            return STANDARD
        if f >= heavy_min_flops:
            return HEAVY
        if f <= light_max_flops:
            return LIGHT
        return STANDARD


class ModelCatalog:
    """Thread-safe bounded registry: model id -> :class:`CatalogEntry`.

    The catalog is the ONE source of model ids in the system: metric
    labels, span attributes, breaker keys, and SLO monitors are all keyed
    by catalog entries, so their cardinality is bounded by deployment
    configuration, never by request data (lint SMT014's contract).
    Cost EWMA thresholds: ``SMT_TENANCY_LIGHT_MAX_FLOPS`` (default 1e6)
    and ``SMT_TENANCY_HEAVY_MIN_FLOPS`` (default 1e9)."""

    def __init__(self, light_max_flops: Optional[float] = None,
                 heavy_min_flops: Optional[float] = None):
        self._lock = threading.Lock()
        self._entries: Dict[str, CatalogEntry] = {}
        self.light_max_flops = (
            light_max_flops if light_max_flops is not None
            else _env_float("SMT_TENANCY_LIGHT_MAX_FLOPS", 1e6))
        self.heavy_min_flops = (
            heavy_min_flops if heavy_min_flops is not None
            else _env_float("SMT_TENANCY_HEAVY_MIN_FLOPS", 1e9))

    def register(self, model: str, stage_path: str, generation: int = 0,
                 resource_class: Optional[str] = None) -> CatalogEntry:
        """Add (or replace) a tenant. ``resource_class`` pins the class
        explicitly; None lets the cost EWMAs classify."""
        if not model:
            raise ValueError("model id must be non-empty")
        if resource_class is not None and \
                resource_class not in RESOURCE_CLASSES:
            raise ValueError(f"resource_class must be one of "
                             f"{RESOURCE_CLASSES}, got {resource_class!r}")
        entry = CatalogEntry(model=model, stage_path=stage_path,
                             generation=int(generation),
                             resource_class=resource_class)
        with self._lock:
            self._entries[model] = entry
        return entry

    def unregister(self, model: str) -> Optional[CatalogEntry]:
        with self._lock:
            return self._entries.pop(model, None)

    def get(self, model: str) -> Optional[CatalogEntry]:
        with self._lock:
            return self._entries.get(model)

    def __contains__(self, model: str) -> bool:
        with self._lock:
            return model in self._entries

    def models(self) -> List[str]:
        """Registered model ids, sorted (deterministic placement input)."""
        with self._lock:
            return sorted(self._entries)

    def bump(self, model: str, stage_path: str, generation: int) -> None:
        """Swap bookkeeping: the catalog follows the model's live
        generation so restarts / scale-ups load the current pipeline."""
        with self._lock:
            e = self._entries.get(model)
            if e is not None:
                e.stage_path = stage_path
                e.generation = int(generation)

    def note_cost(self, model: str, flops_per_req: float,
                  hbm_per_req: float = 0.0, alpha: float = 0.2) -> None:
        """Fold one batch's attributed per-request cost into the model's
        EWMAs (same 0.8/0.2 blend the serving cost model uses)."""
        with self._lock:
            e = self._entries.get(model)
            if e is None:
                return
            if flops_per_req > 0:
                cur = e.flops_per_req
                e.flops_per_req = (flops_per_req if cur is None
                                   else (1 - alpha) * cur
                                   + alpha * flops_per_req)
            if hbm_per_req > 0:
                cur = e.hbm_per_req
                e.hbm_per_req = (hbm_per_req if cur is None
                                 else (1 - alpha) * cur
                                 + alpha * hbm_per_req)

    def resource_class(self, model: str) -> Optional[str]:
        with self._lock:
            e = self._entries.get(model)
            if e is None:
                return None
            return e.classify(self.light_max_flops, self.heavy_min_flops)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        """JSON-able view (the ``GET /placement`` models section)."""
        with self._lock:
            return {
                m: {"stage_path": e.stage_path,
                    "generation": e.generation,
                    "resource_class": e.classify(self.light_max_flops,
                                                 self.heavy_min_flops),
                    "flops_per_req": e.flops_per_req,
                    "hbm_per_req": e.hbm_per_req}
                for m, e in self._entries.items()
            }


class ResidencySet:
    """Per-worker LRU of resident model slots.

    A worker holds up to ``capacity`` pipelines hot; each slot is
    generation-tagged by its own :class:`WorkerLifecycle`, so a swap of
    model A flips A's slot and no other. Admitting model N+1 evicts the
    least-recently-USED resident (touch = a processed batch, not an
    enqueue), and the evicted model's next request faults it back in: the
    reload compiles its programs again, so eviction costs what jax's
    persistent cache makes of that (a load where the deployment has one
    and the entry is there, a compile otherwise). ``capacity=None`` = unbounded
    (every cataloged model stays resident — the common small-zoo case).

    The slot values are opaque to this class (the serving layer stores
    its per-tenant engine handle); eviction hands the slot back to the
    ``on_evict`` callback for teardown."""

    def __init__(self, capacity: Optional[int] = None,
                 on_evict: Optional[Callable[[str, Any], None]] = None):
        if capacity is not None and capacity < 1:
            raise ValueError("ResidencySet capacity must be >= 1")
        self.capacity = capacity
        self.on_evict = on_evict
        self._lock = threading.Lock()
        self._slots: "OrderedDict[str, Any]" = OrderedDict()
        self.evictions = 0
        self.faults = 0  # admits that displaced a resident

    def get(self, model: str, touch: bool = True) -> Optional[Any]:
        with self._lock:
            slot = self._slots.get(model)
            if slot is not None and touch:
                self._slots.move_to_end(model)
            return slot

    def resident(self) -> List[str]:
        """Resident model ids, LRU-first (the next eviction victim leads)."""
        with self._lock:
            return list(self._slots)

    def __contains__(self, model: str) -> bool:
        with self._lock:
            return model in self._slots

    def touch(self, model: str) -> None:
        with self._lock:
            if model in self._slots:
                self._slots.move_to_end(model)

    def admit(self, model: str, slot: Any) -> List[Tuple[str, Any]]:
        """Install ``slot`` as ``model``'s residency; returns the evicted
        ``(model, slot)`` pairs (at most one) AFTER invoking ``on_evict``
        on each — callers that need to stop an evicted engine can do it
        either way."""
        evicted: List[Tuple[str, Any]] = []
        with self._lock:
            if model in self._slots:
                self._slots[model] = slot
                self._slots.move_to_end(model)
                return evicted
            self._slots[model] = slot
            while (self.capacity is not None
                   and len(self._slots) > self.capacity):
                victim, vslot = self._slots.popitem(last=False)
                evicted.append((victim, vslot))
                self.evictions += 1
                self.faults += 1
        for victim, vslot in evicted:
            _logger.info("residency: evicted %s to admit %s (LRU, "
                         "capacity %s)", victim, model, self.capacity)
            if self.on_evict is not None:
                try:
                    self.on_evict(victim, vslot)
                except Exception:
                    _logger.exception("residency on_evict(%s) failed",
                                      victim)
        return evicted

    def evict(self, model: str) -> Optional[Any]:
        """Explicit unload (``/control/unload``); returns the slot (after
        ``on_evict``) or None when not resident."""
        with self._lock:
            slot = self._slots.pop(model, None)
            if slot is not None:
                self.evictions += 1
        if slot is not None and self.on_evict is not None:
            try:
                self.on_evict(model, slot)
            except Exception:
                _logger.exception("residency on_evict(%s) failed", model)
        return slot


def plan_placement(classes: Dict[str, str], workers: List[str],
                   isolate_workers: int = 1) -> Dict[str, List[str]]:
    """Cost-driven placement: model -> the workers that should serve it.

    The policy is deliberately simple and deterministic (inputs are
    sorted; same costs + same fleet = same plan):

    - **heavy** models are ISOLATED: each gets ``isolate_workers``
      dedicated workers, assigned round-robin from the fleet — an
      expensive tenant's batches must not ride in front of everyone
      else's queue.
    - **light** and **standard** models CO-LOCATE on the remaining
      workers (cheap chatty tenants share capacity; their batches are
      small enough to interleave).
    - Degenerate fleets degrade gracefully: with no worker left over
      after isolation (or fewer workers than heavy models), everybody
      shares everything — a placement must never strand a model with
      zero workers.
    """
    workers = sorted(workers)
    if not workers or not classes:
        return {m: list(workers) for m in classes}
    heavy = sorted(m for m, c in classes.items() if c == HEAVY)
    rest = sorted(m for m in classes if m not in heavy)
    n = len(workers)
    per_heavy = max(1, isolate_workers)
    need = len(heavy) * per_heavy
    if need > n - (1 if rest else 0):
        # not enough capacity to isolate every heavy tenant AND still
        # leave the co-location pool at least one worker: fall back to
        # full sharing rather than starving a tenant
        return {m: list(workers) for m in classes}
    plan: Dict[str, List[str]] = {}
    k = 0
    for m in heavy:
        plan[m] = workers[k:k + per_heavy]
        k += per_heavy
    shared = workers[k:]
    for m in rest:
        plan[m] = list(shared)
    return plan


class PlacementBoard:
    """The router's live placement state + bounded decision history.

    ``refresh`` recomputes the plan from the catalog's resource classes
    and the current worker set; a CHANGED plan is logged to the telemetry
    ring (``placement`` events) and appended to the bounded decision log
    the ``GET /placement`` endpoint serves. Reads are lock-cheap (the
    plan is replaced wholesale, never mutated in place)."""

    def __init__(self, catalog: ModelCatalog, isolate_workers: int = 1,
                 max_decisions: int = 64):
        self.catalog = catalog
        self.isolate_workers = isolate_workers
        self._lock = threading.Lock()
        self._plan: Dict[str, List[str]] = {}
        self._decisions: "deque" = deque(maxlen=max_decisions)

    def refresh(self, workers: List[str]) -> Dict[str, List[str]]:
        """Recompute placement for the current fleet; logs on change."""
        classes = {m: self.catalog.resource_class(m) or STANDARD
                   for m in self.catalog.models()}
        plan = plan_placement(classes, workers,
                              isolate_workers=self.isolate_workers)
        with self._lock:
            if plan == self._plan:
                return plan
            old = self._plan
            self._plan = plan
            decision = {
                "classes": dict(classes),
                "plan": {m: list(w) for m, w in plan.items()},
                "workers": sorted(workers),
            }
            self._decisions.append(decision)
        for m in sorted(set(old) | set(plan)):
            if old.get(m) != plan.get(m):
                log_event("placement", className="tenancy", uid=m,
                          model=m, workers=plan.get(m),
                          resource_class=classes.get(m))
        _logger.info("placement refreshed: %s",
                     {m: len(w) for m, w in plan.items()})
        return plan

    def targets(self, model: str) -> List[str]:
        """The workers placed for ``model`` (empty = no placement yet —
        the router falls back to the full registry)."""
        with self._lock:
            return list(self._plan.get(model, ()))

    def status(self) -> Dict[str, Any]:
        """The ``GET /placement`` payload: current plan, per-model cost /
        class rows from the catalog, recent decisions."""
        with self._lock:
            plan = {m: list(w) for m, w in self._plan.items()}
            decisions = list(self._decisions)
        return {"placement": plan, "models": self.catalog.snapshot(),
                "isolate_workers": self.isolate_workers,
                "decisions": decisions}
