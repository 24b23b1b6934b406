"""Continuous + distributed serving (Spark Serving v2 analogue).

Reference: ``continuous/HTTPSourceV2.scala:55-736`` — per-worker ``WorkerServer
:476`` with public handlers, a driver-side service registry
(``DriverServiceUtils:134``), routing tables, and the CONTINUOUS mode whose
latency story ("sub-millisecond", ``website/docs/features/spark_serving/
about.md:18``) comes from not waiting on a micro-batch tick; plus
``DistributedHTTPSource.scala:202-423`` (per-executor servers, round-robin
``MultiChannelMap:24-85``).

TPU-native design:
- ``ContinuousServingEngine`` — PUSH mode: request arrival signals the
  dispatch loop directly (no poll interval). The loop blocks until work
  exists, drains everything immediately available (adaptive batching: one
  request -> batch of 1 served at once; a burst -> one fused batch for the
  device), transforms, replies. p50 latency = pipeline latency, not
  tick/2 + pipeline.
- ``ServiceRegistry`` — name -> worker addresses (the driver registry).
- ``DistributedServingEngine`` — N worker servers each running a continuous
  engine (the per-executor ``WorkerServer`` fleet; workers are in-process
  here the same way the reference's unit tier simulates executors with
  local[*] threads), fronted by ``RoutingServer`` which forwards round-robin
  over the routing table.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
import urllib.request
from concurrent.futures import FIRST_COMPLETED
from concurrent.futures import TimeoutError as FutureTimeout
from concurrent.futures import ThreadPoolExecutor, wait as futures_wait
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from itertools import count
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..core import Table, Transformer
from ..core.telemetry import get_logger
from ..observability import (SLOConfig, SLOMonitor, get_registry,
                             histogram_quantile, merge_snapshots,
                             merge_traces, tracing)
from . import faultinject
from .http_schema import HTTPResponseData
from .lifecycle import (LifecycleConfig, LoadAwareBalancer, WorkerLifecycle,
                        healthz as lifecycle_healthz, model_generation,
                        post_control, wait_until)
from .resilience import (BreakerBoard, FleetHealth, HEALTHY, HealthProber,
                         HedgePolicy, KeyedBreakerBoards, KeyedRetryBudgets,
                         ResilienceConfig, RetryBudget, WORKER_STATES,
                         inject_deadline, parse_deadline, remaining_s)
from .serving import (MicroBatchServingEngine, ServingServer,
                      attribute_batch_cost, choose_batch_size, drain_engine,
                      engine_metrics, join_or_leak, microbatch_target_s,
                      prewarm_pipeline, resolve_admission_schema,
                      respond_batch, serve_metrics_exposition,
                      serve_slo_exposition, serve_timeline_exposition,
                      serve_traces_exposition, traced_batch)
from .tenancy import (ModelCatalog, PlacementBoard, ResidencySet,
                      model_from_request)

__all__ = ["ContinuousServingEngine", "DistributedServingEngine",
           "MultiTenantServingEngine", "ProcessServingFleet",
           "ServiceRegistry", "RoutingServer",
           "serve_continuous", "serve_distributed"]

_logger = get_logger("io.serving_v2")


class ContinuousServingEngine:
    """Push-mode drain -> transform -> reply loop (no micro-batch tick).

    With ``model`` set (a tenant engine inside
    :class:`MultiTenantServingEngine`) the engine drains only THAT
    model's queued requests, attaches its lifecycle slot under the model
    (so swaps are per-model), labels its metric series
    ``engine="tenant:<model>"`` (bounded by the catalog), and reports
    batches/costs/errors under the model so per-tenant SLOs and the
    placement cost EWMAs see the right tenant."""

    def __init__(self, server: ServingServer, pipeline: Transformer,
                 reply_col: str = "reply", max_batch: int = 1024,
                 admission_schema="auto", generation: int = 0,
                 model: Optional[str] = None):
        self.server = server
        self.pipeline = pipeline
        self.reply_col = reply_col
        self.max_batch = max_batch
        self.model = model
        # admission-time request validation against the pipeline's declared
        # input schema (core.schema): a 400 with the schema diff at the
        # door, not a worker 500 mid-batch. A TENANT engine must not
        # install its schema on the shared server — the last tenant would
        # win and 400 every other model's requests.
        self._admission_knob = admission_schema
        if model is None:
            server.admission_schema = resolve_admission_schema(
                pipeline, admission_schema)
        # generation-tagged pipeline slot (io/lifecycle.py): read once per
        # batch, so a hot swap flips atomically between batches
        self.lifecycle = WorkerLifecycle(pipeline, generation,
                                         on_swap=self._on_swap)
        server.attach_lifecycle(self.lifecycle,
                                swap_prewarm=self._prewarm, model=model)
        self._work = threading.Event()
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self.batches_processed = 0
        self.requests_processed = 0
        # push hook: request arrival wakes the dispatcher immediately (a
        # tenant engine is woken by the host's fan-out hook instead)
        if model is None:
            server._on_enqueue = lambda _model=None: self._work.set()
        self._batch_target_s = microbatch_target_s()
        self._m_reg = get_registry()
        self._engine_label = ("continuous" if model is None
                              else f"tenant:{model}")
        (self._m_batches, self._m_batch_size, self._m_pipeline_errors,
         self._m_req_flops, self._m_req_bytes, self._m_chosen) = \
            engine_metrics(self._m_reg, server.server_label,
                           self._engine_label)
        self._m_reg.register_collector(self._collect_metrics)
        self._thread = threading.Thread(target=self._run,
                                        name="serving-continuous", daemon=True)

    def _collect_metrics(self) -> None:
        self._m_batches.sync_total(self.batches_processed)

    def _on_swap(self, pipeline) -> None:
        self.pipeline = pipeline
        if self.model is None:
            self.server.admission_schema = resolve_admission_schema(
                pipeline, self._admission_knob)

    def _prewarm(self, pipeline) -> None:
        prewarm_pipeline(self.server, pipeline, model=self.model)

    def wake(self) -> None:
        """Signal the dispatcher that work may exist (the host's fan-out
        enqueue hook calls this for every resident tenant engine)."""
        self._work.set()

    def start(self) -> "ContinuousServingEngine":
        self._thread.start()
        return self

    def _run(self):
        while not self._stop.is_set():
            self._work.wait(timeout=0.5)
            if self._stop.is_set():
                return
            self._work.clear()
            while True:  # drain everything that arrived while transforming
                # adaptive batch bound from the live queue-depth /
                # service-EWMA signals (bounded by max_batch)
                limit = choose_batch_size(self.server, self.max_batch,
                                          self._batch_target_s)
                batch = self.server.get_requests(limit, model=self.model)
                if not batch:
                    break
                self._m_chosen.set(limit)
                self._process(batch)

    def _process(self, batch):
        from ..observability.profiling import cost_snapshot

        ids = [rid for rid, _ in batch]
        reqs = np.empty(len(batch), dtype=object)
        reqs[:] = [r for _, r in batch]
        table = Table({"id": np.array(ids, dtype=object), "request": reqs})
        # one slot read per batch: the atomic hot-swap flip point
        pipeline, _generation = self.lifecycle.current()
        t0 = time.perf_counter()
        c0 = cost_snapshot()
        try:
            with traced_batch(self.server, ids, self._engine_label,
                              model=self.model):
                out = pipeline.transform(table)
                replies, out_ids = out[self.reply_col], out["id"]
                # inside the batch trace: the bucket gets the leader
                # request's exemplar
                self._m_batch_size.observe(len(batch))
                # per-request device-cost attribution (inside the trace:
                # the batch totals land on the pipeline span; with a
                # model, also on that tenant's cost EWMAs + the catalog)
                attribute_batch_cost(self.server, ids, reqs, c0,
                                     self._m_req_flops, self._m_req_bytes,
                                     model=self.model)
        except Exception as e:
            _logger.exception("continuous serving pipeline failed")
            for rid in ids:
                self.server.respond(rid, HTTPResponseData(
                    500, "pipeline error", entity=str(e).encode()))
            self._error = e
            self._m_pipeline_errors.inc()
            if self.model is not None:
                self.server.note_model_error(self.model)
            return
        try:
            respond_batch(self.server, ids, out_ids, replies)
        except Exception as e:
            # reply-path failure (malformed output table): the drained
            # requests still get 500s NOW instead of hanging to their
            # reply timeout, and the dispatcher loop survives
            _logger.exception("continuous serving reply path failed")
            for rid in ids:  # respond() ignores already-answered ids
                self.server.respond(rid, HTTPResponseData(
                    500, "reply path error", entity=str(e).encode()))
            self._error = e
            self._m_pipeline_errors.inc()
            if self.model is not None:
                self.server.note_model_error(self.model)
            return
        self.server.note_batch(len(batch), time.perf_counter() - t0,
                               model=self.model)
        self.batches_processed += 1
        self.requests_processed += len(batch)

    def latency_p50(self) -> Optional[float]:
        return self.server.latency_quantile(0.5)

    def stop(self, close_server: bool = True) -> None:
        # drain-then-stop: refuse new work, let the dispatcher answer the
        # in-flight set (bounded), then stop the loop and the listener.
        # A TENANT engine passes close_server=False — the shared server
        # belongs to the MultiTenantServingEngine host, which drains it
        # once and closes it after every tenant dispatcher stopped.
        if close_server:
            self.server.begin_shutdown()
            drain_engine(self.server, self._stop)
        self._stop.set()
        self._work.set()
        # a dispatcher wedged inside the pipeline would previously leak
        # silently; now it is logged + counted (smt_thread_leaks_total)
        join_or_leak(self._thread, 5.0,
                     f"serving-engine:{self.server.server_label}:"
                     f"{self._engine_label}")
        if close_server:
            self.server.close()
        self._m_reg.unregister_collector(self._collect_metrics)
        for series in (self._m_batches, self._m_batch_size,
                       self._m_pipeline_errors, self._m_req_flops,
                       self._m_req_bytes, self._m_chosen):
            series.remove()


class MultiTenantServingEngine:
    """One worker, many models (io/tenancy.py's worker half).

    Hosts one tenant :class:`ContinuousServingEngine` per RESIDENT model
    on a shared :class:`ServingServer`: requests pick their tenant with
    the ``X-SMT-Model`` header (validated against the catalog at the
    door), each tenant dispatcher drains only its own queue, and each
    model sits behind its OWN generation-tagged lifecycle slot — swapping
    one never touches the others. Residency is an LRU
    (:class:`ResidencySet`): admitting model N+1 beyond ``capacity``
    evicts the least-recently-served tenant, whose next request faults it
    back in from its saved stage (its programs compile again, through
    jax's persistent cache where the deployment has one).
    ``/control/load`` and ``/control/unload`` drive explicit
    admission/eviction."""

    def __init__(self, server: ServingServer,
                 models: Dict[str, Transformer],
                 reply_col: str = "reply", max_batch: int = 1024,
                 catalog: Optional[ModelCatalog] = None,
                 capacity: Optional[int] = None,
                 stage_paths: Optional[Dict[str, str]] = None,
                 generations: Optional[Dict[str, int]] = None):
        if not models:
            raise ValueError("MultiTenantServingEngine needs >= 1 model")
        self.server = server
        self.reply_col = reply_col
        self.max_batch = max_batch
        self.catalog = catalog if catalog is not None else ModelCatalog()
        self.residency = ResidencySet(capacity=capacity,
                                      on_evict=self._on_evict)
        self._stop = threading.Event()
        self._fault_wake = threading.Event()
        self._lock = threading.Lock()
        stage_paths = stage_paths or {}
        generations = generations or {}
        for m in sorted(models):
            if m not in self.catalog:
                self.catalog.register(m, stage_paths.get(m, ""),
                                      generation=generations.get(m, 0))
        server.catalog = self.catalog
        # untagged legacy traffic lands on the first model (deterministic)
        server.default_model = sorted(models)[0]
        server.tenant_admit = self._tenant_admit
        server.tenant_evict = self._tenant_evict
        # arrival wake is TARGETED: the door stamps every slot with its
        # tenant, so only that tenant's dispatcher drains — an all-hands
        # wake per request made every other tenant (and the fault-in
        # janitor's queue scan) pay for each arrival
        server._on_enqueue = self._wake_model
        for m in sorted(models):
            self._spawn(m, models[m], generations.get(m, 0))
        # fault-in janitor: requests for a cataloged-but-evicted model sit
        # queued until their tenant is re-admitted — this thread watches
        # for them and reloads the model from its saved stage OFF the
        # handler threads (an LRU fault must never block the door)
        self._fault_thread = threading.Thread(
            target=self._fault_loop, name="tenant-fault-in", daemon=True)
        self._fault_thread.start()

    # -- engine plumbing ---------------------------------------------------
    def engines(self) -> Dict[str, ContinuousServingEngine]:
        with self._lock:
            return {m: self.residency.get(m, touch=False)
                    for m in self.residency.resident()}

    def _wake_all(self) -> None:
        for eng in self.engines().values():
            if eng is not None:
                eng.wake()
        self._fault_wake.set()

    def _wake_model(self, model: Optional[str] = None) -> None:
        """Per-arrival wake: the tenant's own dispatcher when resident,
        the fault-in janitor when not (an LRU fault), everyone when the
        tenant is unknown (defensive — the door always stamps one)."""
        if model is None:
            self._wake_all()
            return
        eng = self.residency.get(model, touch=False)
        if eng is not None:
            eng.wake()
        else:
            self._fault_wake.set()

    def _spawn(self, model: str, pipeline: Transformer,
               generation: int = 0) -> ContinuousServingEngine:
        eng = ContinuousServingEngine(
            self.server, pipeline, reply_col=self.reply_col,
            max_batch=self.max_batch, admission_schema=None,
            generation=generation, model=model).start()
        with self._lock:
            self.residency.admit(model, eng)
        return eng

    def _on_evict(self, model: str, eng) -> None:
        """ResidencySet eviction callback: stop the tenant dispatcher
        (without closing the shared server) and detach its lifecycle
        slot. The catalog entry SURVIVES eviction — the model's next
        request faults it back in from its saved stage."""
        if eng is not None:
            eng.stop(close_server=False)
        self.server.lifecycles.pop(model, None)
        self.server.swap_prewarms.pop(model, None)
        _logger.info("tenant %s evicted from residency", model)

    # -- control plane (/control/load, /control/unload) --------------------
    def _tenant_admit(self, model: str, stage_path: Optional[str],
                      generation: int = 0) -> None:
        """Load (or reload) ``model``: from ``stage_path`` when given,
        else from its catalog entry. Registers the catalog entry when
        new; admission may LRU-evict another tenant."""
        entry = self.catalog.get(model)
        if stage_path is None:
            if entry is None or not entry.stage_path:
                raise KeyError(f"unknown model {model!r} and no stage_path")
            stage_path = entry.stage_path
            generation = entry.generation
        from ..core.serialization import load_stage

        pipeline = load_stage(stage_path)
        if entry is None:
            self.catalog.register(model, stage_path, generation=generation)
        else:
            self.catalog.bump(model, stage_path, generation)
        old = self.residency.get(model, touch=False)
        if old is not None:
            # reload of a resident tenant: swap its slot in place rather
            # than tearing the dispatcher down
            old.lifecycle.install(pipeline, generation)
            return
        self._spawn(model, pipeline, generation)

    def _tenant_evict(self, model: str) -> None:
        """Explicit unload: residency eviction AND catalog removal, so
        subsequent requests 404 instead of queueing for a tenant that
        will never come back on its own."""
        if model not in self.catalog:
            raise KeyError(f"unknown model {model!r}")
        self.residency.evict(model)
        self.catalog.unregister(model)
        if self.server.default_model == model:
            remaining = self.catalog.models()
            self.server.default_model = remaining[0] if remaining else None

    # -- LRU fault-in ------------------------------------------------------
    def _queued_nonresident(self) -> List[str]:
        with self.server._lock:
            queued = {s.model for rid in self.server._queue
                      if (s := self.server._pending.get(rid)) is not None
                      and s.model is not None}
        return sorted(m for m in queued
                      if m in self.catalog and m not in self.residency)

    def _fault_loop(self) -> None:
        while not self._stop.is_set():
            self._fault_wake.wait(timeout=0.2)
            self._fault_wake.clear()
            for model in self._queued_nonresident():
                try:
                    self._tenant_admit(model, None)
                    _logger.info("tenant %s faulted back into residency",
                                 model)
                except Exception:
                    _logger.exception("fault-in of tenant %s failed", model)

    def start(self) -> "MultiTenantServingEngine":
        return self  # tenant dispatchers start at spawn; symmetry helper

    def stop(self) -> None:
        # one drain for the shared server, then every tenant dispatcher,
        # then the listener — same drain-then-stop contract as the
        # single-tenant engines
        self.server.begin_shutdown()
        drain_engine(self.server, self._stop)
        self._stop.set()
        self._fault_wake.set()
        join_or_leak(self._fault_thread, 2.0,
                     f"tenant-fault-in:{self.server.server_label}")
        for model, eng in self.engines().items():
            if eng is not None:
                eng.stop(close_server=False)
        self.server.close()


class ServiceRegistry:
    """Driver-side service registry: name -> worker addresses
    (reference ``DriverServiceUtils``/``HTTPSourceStateHolder:338``)."""

    def __init__(self):
        self._services: Dict[str, List[str]] = {}
        self._lock = threading.Lock()

    def register(self, name: str, address: str) -> None:
        """Idempotent: re-registering a live address (a re-admission probe
        racing a concurrent one) must not double its routing weight."""
        with self._lock:
            addrs = self._services.setdefault(name, [])
            if address not in addrs:
                addrs.append(address)

    def unregister(self, name: str, address: str) -> None:
        with self._lock:
            if name in self._services and address in self._services[name]:
                self._services[name].remove(address)

    def lookup(self, name: str) -> List[str]:
        with self._lock:
            return list(self._services.get(name, []))

    def routing_table(self) -> Dict[str, List[str]]:
        with self._lock:
            return {k: list(v) for k, v in self._services.items()}


class RoutingServer:
    """Public front door: resilient routing over the worker fleet.

    Round-robin forwarding (the reference's load-balancer + routing-table
    path, ``MultiChannelMap:24-85``) hardened with the control plane from
    ``io/resilience.py`` — the first consumer of the observability stack:

    - **Health-probing eviction with re-admission**: a contact failure
      marks a worker suspect; ``evict_after`` consecutive failures evict
      it from the routing table, and a background prober re-admits it when
      its ``/metrics`` answers again (jittered exponential backoff) — a
      worker restart heals the fleet instead of shrinking it permanently.
    - **Per-worker circuit breakers** over the observed error rate and
      per-attempt latency; an open breaker skips the worker, a half-open
      one lets a single trial through.
    - **A fleet-wide retry budget**: failover re-sends and hedges together
      stay ≤ ``retry_budget_ratio`` × primaries (+ floor); denied retries
      fail fast with 503 ``retry budget exhausted`` and a counter.
    - **Hedged requests** (idempotent methods only): when the primary has
      not answered within the live-p95-derived hedge delay, a second
      attempt races on another worker; the first answer wins and both
      attempts are tagged in the trace (``hedged``/``hedge_winner``).
    - **Deadline propagation**: every forwarded request carries an
      absolute ``X-SMT-Deadline-Ms`` (the client's, or now + the router
      timeout), so workers can shed work that cannot answer in time.
    """

    def __init__(self, registry: ServiceRegistry, service: str,
                 host: str = "127.0.0.1", port: int = 0, timeout: float = 30.0,
                 resilience: Optional[ResilienceConfig] = None,
                 catalog: Optional[ModelCatalog] = None,
                 isolate_workers: int = 1):
        self.registry = registry
        self.service = service
        self.timeout = timeout
        self.resilience = (resilience if resilience is not None
                           else ResilienceConfig.from_env())
        # multi-tenant front door (io/tenancy.py): with a catalog, the
        # router validates the model id at the door (404 on unknown —
        # bounded label cardinality starts HERE), keys breakers / retry
        # budgets / SLO monitors per model, and orders candidates by the
        # cost-driven placement plan
        self.catalog = catalog
        self.placement = (PlacementBoard(catalog,
                                         isolate_workers=isolate_workers)
                          if catalog is not None else None)
        self.models_rejected = 0
        # handler threads are concurrent (ThreadingHTTPServer): bare += on
        # these from multiple threads loses updates, so every mutation
        # takes the lock (lint SMT006 enforces the discipline from here on)
        self.requests_routed = 0
        self.workers_evicted = 0
        self.workers_readmitted = 0
        self.retries_denied = 0
        self.hedges_sent = 0
        self.hedge_wins = 0
        self.hedges_suppressed = 0
        self.deadline_rejected = 0
        self._lock = threading.Lock()
        self._rr = count()
        self._state_targets: set = set()
        # drain-then-stop bookkeeping: handler threads inside _route
        self._closing = False
        self._active_forwards = 0
        # load-aware routing over live per-worker signals (pick-2 by
        # attempt p99 × in-flight; RR while cold)
        lcfg = LifecycleConfig.from_env()
        self._balancer = LoadAwareBalancer(
            min_samples=lcfg.pick2_min_samples, window=lcfg.latency_window,
            seed=(resilience.seed if resilience is not None else None))
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def _forward(self, method: str):
                op_path = self.path.partition("?")[0]
                if method == "GET" and op_path == "/metrics":
                    # the FLEET view: this front door scrapes every worker's
                    # /metrics?format=json reply (the snapshot rides in the
                    # ordinary HTTP reply — no side channel) and merges.
                    # Worker histograms share the fixed bucket layout, so
                    # fleet quantiles come from the combined distribution.
                    serve_metrics_exposition(self, outer.fleet_snapshot())
                    return
                if method == "GET" and op_path == "/traces":
                    # stitched fleet traces: worker fragments merge into
                    # the routed trace by trace id (merge.merge_traces)
                    serve_traces_exposition(self, outer.fleet_traces())
                    return
                if method == "GET" and op_path == "/timeline":
                    # the stitched fleet view as ONE Chrome-trace JSON:
                    # spans carry their recording process's pid, so the
                    # router and every worker render as separate tracks
                    serve_timeline_exposition(self, outer.fleet_traces())
                    return
                if method == "GET" and op_path == "/slo":
                    # the FLEET burn-rate/budget view: sampled from the
                    # merged worker snapshots, exactly like /metrics
                    outer._serve_slo(self)
                    return
                if method == "GET" and op_path == "/placement":
                    # the live cost-driven placement plan + per-model
                    # cost/class rows + recent decisions (io/tenancy.py)
                    outer._serve_placement(self)
                    return
                # tenant validation AT THE FRONT DOOR: an unknown model id
                # is a client error answered here — it never reaches a
                # worker, never opens a breaker, never burns any budget
                model: Optional[str] = None
                if outer.catalog is not None:
                    model = model_from_request(self.headers, self.path)
                    if model is not None and model not in outer.catalog:
                        payload = json.dumps({
                            "error": f"unknown model {model!r}",
                            "models": outer.catalog.models(),
                        }).encode()
                        with outer._lock:
                            outer.models_rejected += 1
                            outer.requests_routed += 1
                        try:
                            self.send_response(404)
                            self.send_header("Content-Type",
                                             "application/json")
                            self.send_header("Content-Length",
                                             str(len(payload)))
                            self.end_headers()
                            self.wfile.write(payload)
                        except OSError:
                            pass
                        return
                if outer._closing:
                    # drain-then-stop: the listener stays up while
                    # in-flight forwards finish, but NEW work is refused
                    # with honest backpressure instead of a torn socket
                    outer._m_shed.labels(outer.server_label,
                                         "shutdown").inc()
                    with outer._lock:
                        outer.requests_routed += 1
                    try:
                        self.send_response(503)
                        self.send_header("Retry-After", "1")
                        self.send_header("Content-Length", "0")
                        self.end_headers()
                    except OSError:
                        pass
                    return
                targets = outer.registry.lookup(outer.service)
                if not targets:
                    self.send_error(503, "no workers registered")
                    return
                if model is not None and outer.placement is not None:
                    # cost-driven placement narrows the candidate set
                    # (heavy tenants on their isolated workers, cheap ones
                    # on the shared pool); an empty/stale intersection
                    # falls back to the full registry — placement is an
                    # optimization, never an availability constraint
                    placed = outer.placement.targets(model)
                    if placed:
                        live = [t for t in targets if t in placed]
                        if live:
                            targets = live
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else None
                # DEADLINE: the client's absolute X-SMT-Deadline-Ms, or
                # now + the router timeout; propagated to the worker so
                # its queue can shed work that cannot answer in time
                deadline = parse_deadline(self.headers)
                if deadline is None:
                    deadline = time.time() + outer.timeout
                if remaining_s(deadline) <= 0:
                    with outer._lock:
                        outer.deadline_rejected += 1
                        outer.requests_routed += 1
                    try:
                        self.send_error(504, "deadline already expired")
                    except OSError:
                        pass
                    return
                # the ROUTED trace's root (or, when the client sent its own
                # traceparent, the local root continuing the client trace):
                # every worker-side span hangs off this via the header each
                # forward attempt injects
                route_span = None
                if tracing.is_enabled():
                    attrs = {"server": f"{outer.host}:{outer.port}",
                             "method": method, "path": self.path}
                    if model is not None:
                        attrs["model"] = model
                    route_span = tracing.get_tracer().begin_span(
                        "route",
                        parent=tracing.extract_context(self.headers),
                        attributes=attrs)
                # Delivery contract (unchanged from the plain failover
                # router): a DEAD worker (refused/reset) never received the
                # request — always safe to retry; a TIMEOUT may still
                # complete, so only idempotent methods fail over past one
                # (hedges are idempotent-only for the same reason);
                # AT-LEAST-ONCE when a worker dies mid-request
                # (``HTTPv2Suite.scala:328``), worker-side request-id
                # dedup being the escalation path for strict exactly-once.
                idempotent = method in ("GET", "HEAD")
                # hop-by-hop-ish headers the ROUTER owns. When tracing is
                # ON, traceparent is replaced per-attempt with the forward
                # span's context; when tracing is OFF the client's own
                # traceparent passes through untouched — a disabled router
                # must not sever the client->worker trace.
                drop = {"host", "content-length"}
                if route_span is not None:
                    drop.add("traceparent")
                fwd_headers = {k: v for k, v in self.headers.items()
                               if k.lower() not in drop}
                inject_deadline(fwd_headers, deadline)
                # load-aware candidate order (io/lifecycle.py): weighted
                # pick-2 by observed per-worker attempt p99 × in-flight,
                # degrading to round-robin while the windows are cold
                order = outer._balancer.order(targets, next(outer._rr))
                with outer._lock:
                    outer._active_forwards += 1
                try:
                    reply, fail = outer._route(order, method, self.path,
                                               body, fwd_headers, deadline,
                                               idempotent, route_span,
                                               model=model)
                finally:
                    with outer._lock:
                        outer._active_forwards -= 1
                if route_span is not None:
                    if reply is None:
                        status = {"timeout": 504, "deadline": 504,
                                  "budget": 503}.get(fail, 502)
                        route_span.set_attribute("status", status)
                        route_span.end(error={
                            "timeout": "worker timed out (not retried)",
                            "deadline": "deadline expired during routing",
                            "budget": "retry budget exhausted",
                        }.get(fail, "no reachable workers"))
                    else:
                        route_span.set_attribute("status", reply[0])
                        route_span.end(error=f"HTTP {reply[0]}"
                                       if reply[0] >= 500 else None)
                # client write OUTSIDE the routing machinery: a client
                # that hung up must not evict a healthy worker or re-send
                # the request (duplicate side effects)
                try:
                    if reply is not None:
                        status, ct, ent = reply
                        self.send_response(status)
                        if ct:
                            self.send_header("Content-Type", ct)
                        self.send_header("Content-Length", str(len(ent)))
                        self.end_headers()
                        self.wfile.write(ent)
                    elif fail == "timeout":
                        self.send_error(
                            504, "worker timed out; not retried "
                                 "(non-idempotent method)")
                    elif fail == "deadline":
                        self.send_error(504, "deadline expired during "
                                             "routing")
                    elif fail == "budget":
                        self.send_error(503, "retry budget exhausted")
                    else:
                        self.send_error(502, "no reachable workers")
                except OSError:
                    pass  # client went away; the reply is simply dropped
                with outer._lock:
                    outer.requests_routed += 1

            def do_GET(self):
                self._forward("GET")

            def do_POST(self):
                self._forward("POST")

            def log_message(self, fmt, *args):
                _logger.debug("routing: " + fmt, *args)

        class Server(ThreadingHTTPServer):
            daemon_threads = True
            # the front door absorbs many tenants' connection bursts at
            # once; the http.server default backlog (5) resets the
            # overflow at the TCP layer before any shed/deadline logic
            # can answer honestly
            request_queue_size = 128

        self._httpd = Server((host, port), Handler)
        self.host, self.port = self._httpd.server_address[:2]
        label = self.server_label = f"{self.host}:{self.port}"
        cfg = self.resilience
        reg = self._m_reg = get_registry()
        self._m_routed = reg.counter(
            "smt_routing_requests_total", "requests forwarded to workers",
            ("server",)).labels(label)
        self._m_evicted = reg.counter(
            "smt_routing_evictions_total", "workers evicted as unreachable",
            ("server",)).labels(label)
        self._m_readmitted = reg.counter(
            "smt_routing_readmissions_total",
            "evicted workers re-admitted after a successful probe",
            ("server",)).labels(label)
        self._m_budget_denied = reg.counter(
            "smt_routing_retry_budget_denied_total",
            "retries/hedges denied by the fleet retry budget",
            ("server",)).labels(label)
        self._m_hedges = reg.counter(
            "smt_routing_hedges_total", "hedge requests issued",
            ("server",)).labels(label)
        self._m_hedge_wins = reg.counter(
            "smt_routing_hedge_wins_total",
            "hedged requests won by the hedge attempt",
            ("server",)).labels(label)
        self._m_hedges_suppressed = reg.counter(
            "smt_routing_hedges_suppressed_total",
            "hedges withheld by the defensive SLO posture "
            "(hedging amplifies offered load exactly when the error "
            "budget is burning)",
            ("server",)).labels(label)
        self._m_slo_posture = reg.gauge(
            "smt_slo_defensive_posture",
            "1 while the fleet SLO monitor is in the defensive posture "
            "(budget near exhaustion or fast-window burn active)",
            ("server",), merge="max").labels(label)
        self._m_deadline_rejected = reg.counter(
            "smt_routing_deadline_rejected_total",
            "requests 504'd at the door for an already-expired deadline",
            ("server",)).labels(label)
        # the LIVE per-attempt latency distribution: drives the hedge
        # delay (p95) and the breaker's slow-attempt criterion — the
        # router's own merged view over every worker it talks to
        self._m_attempt_lat = reg.histogram(
            "smt_routing_attempt_latency_seconds",
            "per-forward-attempt latency",
            ("server",)).labels(label)
        # drained-at-shutdown requests share the worker-side shed family
        # (one place to alert on shed work, whatever the reason)
        self._m_shed = reg.counter(
            "smt_serving_shed_total",
            "requests shed by deadline-aware admission",
            ("server", "reason"))
        self._m_breaker_trans = reg.counter(
            "smt_routing_breaker_transitions_total",
            "circuit-breaker state transitions",
            ("server", "state"))
        self._m_worker_state = reg.gauge(
            "smt_routing_worker_state",
            "per-worker health state (1 = the worker's current state)",
            ("server", "target", "state"), merge="max")
        # the FLEET SLO monitor (observability/slo.py): fed from the
        # merged fleet snapshot on every GET /slo and by the autoscaler's
        # adapter; its posture gates hedging — near budget exhaustion a
        # hedge is pure load amplification
        self.slo = SLOMonitor(SLOConfig.from_env(), name=f"fleet:{label}")
        # synthetic zero baseline (NOT a worker scrape: a router must not
        # generate fleet traffic at construction — deterministic fault
        # plans would see it): the first real /slo sample diffs against
        # this, so the ledger spans the router's lifetime
        self.slo.observe({"families": {}}, force=True)
        # control-plane policy objects (io/resilience.py), created before
        # the accept thread starts so handlers never race them
        self._health = FleetHealth(cfg)
        self._hedge_policy = HedgePolicy(cfg, self._m_attempt_lat)
        self._breakers = BreakerBoard(cfg, slow_s=self._hedge_policy.slow_s,
                                      on_transition=self._breaker_transition)
        self._budget = RetryBudget(cfg)
        # per-MODEL keyed boards (multi-tenant only): model A browning out
        # on a worker opens only (A, worker)'s breaker and spends only A's
        # retry budget — B's traffic keeps flowing. Untagged traffic keeps
        # the flat board/budget above. Per-model SLO monitors are created
        # lazily per cataloged model over the model-labeled families.
        self._model_breakers = (
            KeyedBreakerBoards(cfg, slow_s=self._hedge_policy.slow_s,
                               on_transition=self._breaker_transition)
            if catalog is not None else None)
        self._model_budgets = (KeyedRetryBudgets(cfg)
                               if catalog is not None else None)
        self._model_slos: Dict[str, SLOMonitor] = {}
        self._pool = ThreadPoolExecutor(
            max_workers=16, thread_name_prefix=f"routing-hedge-{self.port}")
        # synced from the plain ints at snapshot time (hot-path-free)
        reg.register_collector(self._collect_metrics)
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name=f"routing-{self.port}", daemon=True)
        self._thread.start()
        self._prober = HealthProber(self._health, cfg, self._readmit).start()

    # -- control-plane callbacks -------------------------------------------
    def _note_dead(self, target: str) -> None:
        """A contact failure (refused/reset — the request never ran).
        Eviction is NO LONGER permanent: the prober re-admits the worker
        when its /metrics answers again."""
        if self._health.record_failure(target):
            self.registry.unregister(self.service, target)
            with self._lock:
                self.workers_evicted += 1
            _logger.warning("evicted unreachable worker %s "
                            "(probing for re-admission)", target)

    def _readmit(self, target: str) -> None:
        """Prober callback: the evicted worker answered its liveness probe
        — put it back in the routing table with a clean breaker."""
        self.registry.register(self.service, target)
        self._breakers.reset(target)
        if self._model_breakers is not None:
            # the worker restarted: no tenant's stale breaker history
            # applies (resets the target on EVERY model's board)
            self._model_breakers.reset(target)
        # a restarted worker's latency history is stale: start it cold
        # (round-robin) until its window re-warms
        self._balancer.forget(target)
        with self._lock:
            self.workers_readmitted += 1
        _logger.info("re-admitted worker %s after a successful probe", target)

    def _breaker_transition(self, target: str, state: str) -> None:
        self._m_breaker_trans.labels(self.server_label, state).inc()
        _logger.info("circuit breaker for %s -> %s", target, state)

    def _breakers_for(self, model: Optional[str]) -> BreakerBoard:
        """The breaker board an attempt consults: the flat per-target
        board for untagged traffic, the MODEL's own board otherwise."""
        if model is None or self._model_breakers is None:
            return self._breakers
        return self._model_breakers.board(model)

    def _budget_for(self, model: Optional[str]) -> RetryBudget:
        """The retry budget a failover/hedge spends from: one tenant's
        retry storm must not starve another's legitimate failover."""
        if model is None or self._model_budgets is None:
            return self._budget
        return self._model_budgets.budget(model)

    def _model_slo(self, model: str) -> SLOMonitor:
        """The per-model SLO monitor (lazy; keyed by catalog entries, so
        the monitor count is bounded by deployment configuration). Reads
        the ``smt_serving_model_*`` families via
        ``label_filter={"model": ...}``."""
        mon = self._model_slos.get(model)
        if mon is None:
            mon = SLOMonitor(SLOConfig.from_env(),
                             label_filter={"model": {model}},
                             name=f"model:{model}@{self.server_label}")
            # synthetic zero baseline, same contract as the fleet monitor
            mon.observe({"families": {}}, force=True)
            self._model_slos[model] = mon
        return mon

    # -- routing core ------------------------------------------------------
    def _route(self, order: List[str], method: str, path: str,
               body: Optional[bytes], headers: Dict[str, str],
               deadline: float, idempotent: bool, route_span,
               model: Optional[str] = None
               ) -> Tuple[Optional[tuple], Optional[str]]:
        """Walk the candidates with breaker-gated, budget-limited failover
        (and a hedged first attempt for idempotent methods). Returns
        ``(reply, fail)``: a ``(status, content_type, entity)`` reply, or
        ``fail`` in ``timeout | budget | deadline | unreachable``.
        ``model`` keys the breaker board and retry budget per tenant."""
        cfg = self.resilience
        breakers = self._breakers_for(model)
        budget = self._budget_for(model)
        attempted = 0
        tried_as_hedge: set = set()
        for i, target in enumerate(order):
            if target in tried_as_hedge:
                # already attempted (and failed) as a hedge leg — a second
                # send would waste budget on a known-bad worker
                continue
            rem = remaining_s(deadline)
            if rem is not None and rem <= 0:
                return None, "deadline"
            if not breakers.allow(target):
                continue  # skipped, never sent: costs no budget
            if attempted == 0:
                budget.note_primary()
            elif not budget.try_spend():
                # retry budget exhausted (the MODEL's own when tagged):
                # fail FAST — failover under brownout must not amplify
                # offered load into a retry storm (the distinct 503 +
                # counter is the signal). The allow() slot was consumed
                # but nothing will be sent.
                breakers.release(target)
                with self._lock:
                    self.retries_denied += 1
                return None, "budget"
            alternates = order[i + 1:]
            if (attempted == 0 and idempotent and cfg.hedge_enabled
                    and alternates):
                if self.slo.defensive():
                    # posture escalation: the budget is burning — a hedge
                    # would amplify offered load exactly when the fleet
                    # can least afford it. Plain single attempt instead.
                    with self._lock:
                        self.hedges_suppressed += 1
                    kind, reply = self._attempt(target, method, path, body,
                                                headers, deadline,
                                                route_span, attempted,
                                                model=model)
                else:
                    kind, reply = self._hedged_attempt(
                        target, alternates, method, path, body, headers,
                        deadline, route_span, tried_as_hedge, model=model)
            else:
                kind, reply = self._attempt(target, method, path, body,
                                            headers, deadline, route_span,
                                            attempted, model=model)
            attempted += 1
            if kind == "reply":
                return reply, None
            if kind == "deadline":
                # the attempt was never sent (deadline expired first): the
                # accurate answer is 504-deadline, NOT 504-timeout — a
                # non-idempotent client must not be told its request may
                # have executed when nothing went on the wire
                return None, "deadline"
            if kind == "timeout" and not idempotent:
                return None, "timeout"
            # timeout (idempotent) or dead: fail over to the next candidate
        return None, "unreachable"

    def _attempt(self, target: str, method: str, path: str,
                 body: Optional[bytes], headers: Dict[str, str],
                 deadline: float, route_span, attempt: int,
                 hedge: bool = False,
                 model: Optional[str] = None) -> Tuple[str, Optional[tuple]]:
        """One forward attempt; records the breaker outcome, the health
        transition, the attempt-latency sample, and a ``forward`` span.
        Returns ``(kind, reply)``: ``reply`` (the worker answered —
        application errors are relayed, 5xx feeding the breaker),
        ``timeout`` (alive but slow; no eviction), ``dead`` (contact
        failure; may evict), or ``deadline`` (expired before anything was
        sent — no worker was contacted)."""
        import socket as _socket

        rem = remaining_s(deadline)
        if rem is not None and rem <= 0:
            # never sent: hand back the breaker trial slot allow() may
            # have reserved, and report the accurate outcome
            self._breakers_for(model).release(target)
            return ("deadline", None)
        per_attempt = max(0.001, min(self.timeout, rem))
        fwd_span = None
        if route_span is not None:
            attrs = {"target": target, "attempt": attempt}
            if hedge:
                attrs["hedge"] = True
            fwd_span = route_span.tracer.begin_span(
                "forward", parent=route_span, attributes=attrs)
            # per-attempt copy: concurrent hedge attempts must not fight
            # over one traceparent header dict
            headers = dict(headers)
            tracing.inject_headers(headers, fwd_span)
        kind: str = "dead"
        ok = False
        reply = None
        error: Optional[BaseException] = None
        self._balancer.note_start(target)
        t0 = time.perf_counter()
        try:
            rule = faultinject.act("router.forward",
                                   f"{method} {target}{path}")
            if rule is not None:
                faultinject.raise_transport_fault(rule, target + path,
                                                  timeout=per_attempt)
            fwd = urllib.request.Request(
                target + path, data=body, method=method,
                headers=dict(headers))
            with urllib.request.urlopen(fwd, timeout=per_attempt) as r:
                reply = (r.status, r.headers.get("Content-Type"), r.read())
            kind, ok = "reply", True
        except urllib.error.HTTPError as e:
            # the worker ANSWERED (an application error): relay it — not
            # a routing fault, but 5xx counts against its breaker
            reply = (e.code, None, e.read())
            kind, ok = "reply", e.code < 500
        except (TimeoutError, _socket.timeout) as e:
            kind, error = "timeout", e
        except urllib.error.URLError as e:
            if isinstance(e.reason, (TimeoutError, _socket.timeout)):
                kind, error = "timeout", e
            else:
                kind, error = "dead", e
        except (OSError, http.client.HTTPException) as e:
            # connection resets and mid-body disconnects land here
            kind, error = "dead", e
        latency = time.perf_counter() - t0
        # only a SUCCESSFUL reply feeds the routing score: an instant 4xx
        # must not make a broken worker the pick-2 favourite
        self._balancer.note_end(target, latency,
                                success=(kind == "reply"
                                         and reply[0] < 400))
        self._m_attempt_lat.observe(latency)
        self._breakers_for(model).on_result(target, ok, latency)
        if kind == "reply":
            self._health.record_success(target)  # it answered: alive
        elif kind == "dead":
            self._note_dead(target)
        if fwd_span is not None:
            if kind == "reply":
                fwd_span.set_attribute("status", reply[0])
                fwd_span.end()
            else:
                fwd_span.end(error=error)
        return (kind, reply)

    def _hedged_attempt(self, primary: str, alternates: List[str],
                        method: str, path: str, body: Optional[bytes],
                        headers: Dict[str, str], deadline: float, route_span,
                        tried: set,
                        model: Optional[str] = None
                        ) -> Tuple[str, Optional[tuple]]:
        """Tail-at-scale hedging (Dean & Barroso): when the primary has
        not answered within the live-p95 hedge delay, race one hedge on
        the next breaker-allowed worker; the first worker ANSWER wins, the
        loser is cancelled/abandoned, and both attempts are tagged in the
        trace (``hedge`` on the attempt span, ``hedge_winner`` on the
        route span) so ``tools/trace_dump.py`` can prove who won. Hedges
        draw from the same retry budget as failover; the hedge target is
        added to ``tried`` so a failed race does not re-attempt it."""
        delay = self._hedge_policy.delay_s(self.timeout)
        breakers = self._breakers_for(model)
        try:
            f1 = self._pool.submit(self._attempt, primary, method, path,
                                   body, headers, deadline, route_span,
                                   0, False, model)
        except RuntimeError:
            # the pool is shut down (router closing with traffic in
            # flight): degrade to a plain inline attempt, never a crash
            return self._attempt(primary, method, path, body, headers,
                                 deadline, route_span, 0, model=model)
        rem = remaining_s(deadline)
        try:
            return f1.result(timeout=min(delay, max(rem, 0.001)))
        except FutureTimeout:
            pass  # the primary is straggling ... OR never started
        if f1.cancel():
            # the pool is saturated — the "straggler" was never even sent.
            # Hedging a queued request is pure amplification; run the
            # attempt inline on this handler thread instead.
            return self._attempt(primary, method, path, body, headers,
                                 deadline, route_span, 0, model=model)
        hedge_target = next(
            (t for t in alternates if breakers.allow(t)), None)
        if hedge_target is None or not self._budget_for(model).try_spend():
            if hedge_target is not None:
                # allow() reserved a (possibly half-open) trial slot but
                # the budget denied the send: hand the slot back
                breakers.release(hedge_target)
            # no affordable hedge: wait the primary out (bounded by the
            # deadline plus the attempt's own timeout slack)
            try:
                return f1.result(
                    timeout=max(remaining_s(deadline), 0.001) + 1.0)
            except FutureTimeout:
                return ("timeout", None)
        try:
            f2 = self._pool.submit(self._attempt, hedge_target, method,
                                   path, body, headers, deadline,
                                   route_span, 1, True, model)
        except RuntimeError:
            breakers.release(hedge_target)
            try:
                return f1.result(
                    timeout=max(remaining_s(deadline), 0.001) + 1.0)
            except FutureTimeout:
                return ("timeout", None)
        tried.add(hedge_target)
        with self._lock:
            self.hedges_sent += 1
        if route_span is not None:
            route_span.set_attribute("hedged", True)
        by_future = {f1: (primary, False), f2: (hedge_target, True)}
        pending = set(by_future)
        last: Tuple[str, Optional[tuple]] = ("timeout", None)
        while pending:
            rem = remaining_s(deadline)
            if rem is not None and rem <= 0:
                break
            done, pending = futures_wait(pending, timeout=rem,
                                         return_when=FIRST_COMPLETED)
            if not done:
                break  # deadline expired with both legs still in flight
            for f in done:
                kind, reply = f.result()
                target, was_hedge = by_future[f]
                if kind != "reply":
                    last = (kind, reply)
                    continue
                if route_span is not None:
                    route_span.set_attribute("hedge_winner", target)
                if was_hedge:
                    with self._lock:
                        self.hedge_wins += 1
                for p in pending:
                    # best-effort cancel; a cancelled leg never ran, so
                    # hand back any breaker trial slot it reserved — an
                    # in-flight loser just runs out its own attempt
                    # timeout, abandoned, and reports its own outcome
                    if p.cancel():
                        breakers.release(by_future[p][0])
                return (kind, reply)
        return last

    def _serve_slo(self, handler) -> None:
        """``GET /slo``: sample the MERGED fleet snapshot (the same
        worker-scrape path ``/metrics`` rides) into the fleet monitor and
        serve its status — fleet burn rates from combined bucket deltas,
        exactly like fleet quantiles."""
        try:
            snap = self.fleet_snapshot()
            self.slo.observe(snap, force=True)
        except Exception:
            _logger.debug("fleet SLO sample failed", exc_info=True)
            snap = None
        status = self.slo.status()
        status["fleet"] = True
        status["workers"] = len(self.registry.lookup(self.service))
        if self.catalog is not None:
            # per-tenant monitors over the same merged snapshot, reading
            # the model mirror families — one tenant's burn is visible
            # (and alertable) without the aggregate moving
            models: Dict[str, dict] = {}
            for m in self.catalog.models():
                mon = self._model_slo(m)
                if snap is not None:
                    try:
                        mon.observe(snap, force=True)
                    except Exception:
                        _logger.debug("model SLO sample failed",
                                      exc_info=True)
                models[m] = mon.status()
            status["models"] = models
        serve_slo_exposition(handler, status)

    def _serve_placement(self, handler) -> None:
        """``GET /placement``: the placement board's current view —
        per-model resource class, cost EWMAs, assigned workers, and the
        bounded decision log. 404 on a single-tenant router (no catalog:
        there is nothing to place)."""
        if self.placement is None:
            body = json.dumps({"error": "placement requires a model "
                                        "catalog (multi-tenant mode)"}
                              ).encode()
            handler.send_response(404)
            handler.send_header("Content-Type", "application/json")
            handler.send_header("Content-Length", str(len(body)))
            handler.end_headers()
            handler.wfile.write(body)
            return
        try:
            # the grouped-merge cost path: per-tenant engines publish
            # profiled cost histograms (engine="tenant:<model>"), the
            # fleet snapshot merges them across workers, and the ROUTER's
            # catalog folds the fleet-wide per-request means into its
            # EWMAs — so placement classes come from measured device cost,
            # not from whatever this process happened to serve itself
            from ..observability.merge import model_cost_per_request

            for m, per in model_cost_per_request(
                    self.fleet_snapshot()).items():
                if self.catalog is not None and m in self.catalog:
                    self.catalog.note_cost(m, per)
            self.placement.refresh(self.registry.lookup(self.service))
        except Exception:
            _logger.debug("placement refresh failed", exc_info=True)
        body = json.dumps(self.placement.status(), indent=2).encode()
        handler.send_response(200)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.wfile.write(body)

    def _collect_metrics(self) -> None:
        self._m_routed.sync_total(self.requests_routed)
        self._m_evicted.sync_total(self.workers_evicted)
        self._m_readmitted.sync_total(self.workers_readmitted)
        self._m_budget_denied.sync_total(self.retries_denied)
        self._m_hedges.sync_total(self.hedges_sent)
        self._m_hedge_wins.sync_total(self.hedge_wins)
        self._m_hedges_suppressed.sync_total(self.hedges_suppressed)
        self._m_deadline_rejected.sync_total(self.deadline_rejected)
        # posture is a pure function of the monitor's retained samples —
        # no snapshot is taken here (a snapshot-time collector taking a
        # snapshot would recurse)
        self._m_slo_posture.set(1.0 if self.slo.defensive() else 0.0)
        # one-hot worker-state gauges: the scrape-time view of the state
        # machine (registered-but-never-failed workers show as healthy)
        states = self._health.states()
        for t in self.registry.lookup(self.service):
            states.setdefault(t, HEALTHY)
        with self._lock:
            self._state_targets.update(states)
        for t, st in states.items():
            for s in WORKER_STATES:
                self._m_worker_state.labels(self.server_label, t, s).set(
                    1.0 if s == st else 0.0)

    @property
    def address(self) -> str:
        return f"http://{self.host}:{self.port}"

    def _scrape_workers(self, path: str) -> List[dict]:
        """Fetch ``path`` as JSON from every registered worker,
        concurrently (one wedged worker costs its own timeout, not
        timeout x fleet size serialized inside the handler thread);
        unreachable workers are skipped — a scrape must not fail because
        one worker died."""
        from ..core.clock import buffered_map

        def scrape(target):
            try:
                with urllib.request.urlopen(
                        target + path,
                        timeout=min(self.timeout, 5.0)) as r:
                    return json.loads(r.read().decode())
            except Exception:
                return None

        return [p for p in buffered_map(
            scrape, self.registry.lookup(self.service), concurrency=8)
            if p is not None]

    def fleet_snapshot(self) -> dict:
        """Merged registry snapshot: this process's registry + every
        registered worker's ``/metrics?format=json`` reply.

        In-process fleets share the process-default registry, so the scraped
        snapshots carry the SAME ``registry_id`` and dedupe instead of
        double-counting; cross-process workers have distinct ids and sum
        (``observability.merge``)."""
        return merge_snapshots([get_registry().snapshot()]
                               + self._scrape_workers("/metrics?format=json"))

    def fleet_traces(self) -> dict:
        """Stitched fleet trace view: this process's flight recorder plus
        every registered worker's ``/traces`` reply, merged BY TRACE ID
        (``observability.merge_traces``) — a routed request's ``route``/
        ``forward`` spans (recorded here) and its ``request``/``pipeline``/
        stage spans (recorded in the worker process) reassemble into one
        span tree because the forward hop carried the ``traceparent``."""
        return merge_traces([tracing.get_tracer().snapshot()]
                            + self._scrape_workers("/traces"))

    def close(self, drain_s: float = 5.0) -> None:
        # drain-then-stop: refuse NEW work (503 + Retry-After, counted in
        # smt_serving_shed_total{reason=shutdown}) while handler threads
        # already inside _route finish their forwards, bounded by
        # ``drain_s`` ∧ the router timeout — in-flight requests are never
        # cut off by the listener disappearing under them
        self._closing = True

        def _idle() -> bool:
            with self._lock:
                return self._active_forwards == 0

        wait_until(_idle, max(0.0, min(drain_s, self.timeout)), poll_s=0.02)
        self._prober.request_stop()
        join_or_leak(self._prober.thread, 2.0,
                     f"routing-prober:{self.server_label}")
        # stop accepting BEFORE shutting the hedge pool: handler threads
        # already inside _forward may still submit attempts (and the
        # submit paths degrade to inline on a closed pool regardless)
        self._httpd.shutdown()
        self._httpd.server_close()
        # the accept loop previously leaked silently when wedged; now a
        # failed join is logged + counted (smt_thread_leaks_total)
        join_or_leak(self._thread, 5.0,
                     f"routing-server:{self.server_label}")
        self._pool.shutdown(wait=False)
        self._m_reg.unregister_collector(self._collect_metrics)
        for series in (self._m_routed, self._m_evicted, self._m_readmitted,
                       self._m_budget_denied, self._m_hedges,
                       self._m_hedge_wins, self._m_hedges_suppressed,
                       self._m_deadline_rejected, self._m_attempt_lat,
                       self._m_slo_posture):
            series.remove()
        for state in ("closed", "open", "half_open"):
            self._m_breaker_trans.remove(self.server_label, state)
        self._m_shed.remove(self.server_label, "shutdown")
        with self._lock:
            targets = set(self._state_targets)
        for t in targets:
            for s in WORKER_STATES:
                self._m_worker_state.remove(self.server_label, t, s)


class DistributedServingEngine:
    """Worker fleet + registry + routing front door."""

    def __init__(self, pipeline: Transformer, n_workers: int = 2,
                 service: str = "default", host: str = "127.0.0.1",
                 reply_col: str = "reply", mode: str = "continuous",
                 interval: float = 0.01, reply_timeout: float = 30.0,
                 admission_schema="auto",
                 resilience: Optional[ResilienceConfig] = None):
        self.registry = ServiceRegistry()
        self.service = service
        self.generation = 0
        # serializes concurrent swap() calls (and guards `generation`)
        self._swap_lock = threading.Lock()
        self.workers = []
        for _ in range(n_workers):
            server = ServingServer(host, 0, reply_timeout=reply_timeout)
            if mode == "continuous":
                eng = ContinuousServingEngine(
                    server, pipeline, reply_col=reply_col,
                    admission_schema=admission_schema).start()
            else:
                eng = MicroBatchServingEngine(
                    server, pipeline, reply_col=reply_col,
                    interval=interval,
                    admission_schema=admission_schema).start()
            self.workers.append(eng)
            self.registry.register(service, server.address)
        self.router = RoutingServer(self.registry, service, host, 0,
                                    timeout=reply_timeout,
                                    resilience=resilience)

    @property
    def address(self) -> str:
        return self.router.address

    def routing_table(self) -> Dict[str, List[str]]:
        return self.registry.routing_table()

    def swap(self, pipeline: Transformer,
             cfg: Optional[LifecycleConfig] = None) -> int:
        """Zero-downtime rolling hot swap across the in-process fleet:
        one worker at a time is drained (unregistered from the routing
        table, in-flight requests allowed to finish), its slot flipped to
        the new pipeline (pre-warmed off the request path), then
        re-admitted — at every instant the remaining workers keep
        serving, so no request is ever dropped. Returns the new
        generation."""
        cfg = cfg or LifecycleConfig.from_env()
        with self._swap_lock:
            gen = self.generation + 1
            for eng in self.workers:
                addr = eng.server.address
                eng.lifecycle.begin_drain()
                self.registry.unregister(self.service, addr)
                try:
                    wait_until(lambda: eng.server.inflight() == 0,
                               cfg.drain_timeout_s, cfg.poll_interval_s)
                    if not eng.lifecycle.swap_async(lambda: pipeline, gen,
                                                    prewarm=eng._prewarm):
                        raise RuntimeError("a swap is already in flight")
                    if not wait_until(
                            lambda: eng.lifecycle.generation == gen,
                            cfg.swap_timeout_s, cfg.poll_interval_s):
                        raise RuntimeError(
                            f"swap did not complete: "
                            f"{eng.lifecycle.swap_error()}")
                finally:
                    eng.lifecycle.resume()
                    self.registry.register(self.service, addr)
            self.generation = gen
        return gen

    def latency_p50(self) -> Optional[float]:
        """FLEET p50 from the workers' latency histograms merged bucket-wise.

        A mean of per-worker p50s (the old implementation) is not a fleet
        p50 — a slow worker serving 1% of traffic would shift the "median"
        by its full latency. Bucket-wise merging computes the quantile of
        the combined distribution (same estimator Prometheus's
        ``histogram_quantile`` applies to a summed fleet histogram).

        Like any Prometheus histogram this is CUMULATIVE over the servers'
        lifetimes; for a recent-window view scrape ``/metrics`` and rate()
        the buckets, or use the per-engine ``latency_p50`` (bounded recent
        deque) on a single worker."""
        labels = {"server": {w.server.server_label for w in self.workers}}
        return histogram_quantile(get_registry().snapshot(),
                                  "smt_serving_latency_seconds", 0.5,
                                  label_filter=labels)

    def stop(self) -> None:
        self.router.close()
        for w in self.workers:
            w.stop()


class ProcessServingFleet:
    """Worker fleet as REAL OS processes behind the routing front door.

    The reference's distributed serving runs per-executor ``WorkerServer``s
    in separate JVMs; ``DistributedServingEngine`` simulates that with
    threads (fine for routing logic), but the fault contract — kill a
    worker mid-stream, the service keeps answering — only means something
    across process boundaries. Each worker is
    ``python -m synapseml_tpu.io.serving_worker`` serving a SAVED copy of
    the pipeline; the router's failover evicts dead workers from the
    routing table on first contact failure.
    """

    def __init__(self, pipeline: Optional[Transformer], n_workers: int = 2,
                 service: str = "default", host: str = "127.0.0.1",
                 mode: str = "continuous", reply_timeout: float = 30.0,
                 startup_timeout: float = 60.0,
                 import_modules: Optional[List[str]] = None,
                 trace_knobs: Optional[Dict[str, float]] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 fault_plan=None,
                 lifecycle: Optional[LifecycleConfig] = None,
                 models: Optional[Dict[str, Transformer]] = None,
                 isolate_workers: int = 1):
        import json as _json
        import os
        import shutil
        import sys
        import tempfile

        from ..core.serialization import save_stage

        if pipeline is None and not models:
            raise ValueError("either a pipeline or a models dict is "
                             "required")
        self._tmp = tempfile.mkdtemp(prefix="serving_fleet_")
        self.generation = 0
        # multi-tenant mode: every worker process serves EVERY cataloged
        # model (one MultiTenantServingEngine per worker); the fleet keeps
        # a per-model generation ledger and a catalog the router validates
        # against + places with
        self.generations: Dict[str, int] = {}
        self._models_spec: Dict[str, Dict[str, Any]] = {}
        self.catalog: Optional[ModelCatalog] = None
        if models:
            self.catalog = ModelCatalog()
            for m, pipe in sorted(models.items()):
                spath = os.path.join(self._tmp, f"{m}_g0")
                save_stage(pipe, spath)
                self.generations[m] = 0
                self._models_spec[m] = {"stage_path": spath,
                                        "generation": 0}
                self.catalog.register(m, spath, generation=0)
            self._stage_path = None
        else:
            self._stage_path = os.path.join(self._tmp, "pipeline_g0")
            save_stage(pipeline, self._stage_path)
        self.registry = ServiceRegistry()
        self.service = service
        self.startup_timeout = startup_timeout
        self.lifecycle_cfg = lifecycle or LifecycleConfig.from_env()
        self._autoscaler = None
        # the autoscaler mutates the fleet from its own thread: _ops_lock
        # serializes the slow mutators (swap/add/remove/restart) against
        # each other; _lists_lock keeps the procs/addresses PAIR coherent
        # for readers (it is never held across I/O)
        self._ops_lock = threading.RLock()
        self._lists_lock = threading.Lock()
        self.procs = []
        self.addresses = []
        self._stderr_paths: Dict[int, str] = {}  # worker pid -> its log
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        if fault_plan is not None:
            # the deterministic chaos plan reaches the worker PROCESSES
            # through the environment (io/faultinject.py reads it lazily);
            # router-side seams take an in-process install_plan instead
            env[faultinject.ENV_VAR] = (
                fault_plan if isinstance(fault_plan, str)
                else _json.dumps(fault_plan))
        self._env = env
        flags = ["--host", host, "--mode", mode,
                 "--reply-timeout", str(reply_timeout)]
        for mod in (import_modules or []):
            flags += ["--import-module", mod]
        # tail-sampling knobs for the worker processes' flight recorders
        # (keys: sample_rate, slow_ms, capacity); unset keys keep the
        # worker's env/default configuration
        for key, flag, conv in (("sample_rate", "--trace-sample-rate", str),
                                ("slow_ms", "--trace-slow-ms", str),
                                ("capacity", "--trace-capacity",
                                 lambda v: str(int(v)))):
            if trace_knobs and trace_knobs.get(key) is not None:
                flags += [flag, conv(trace_knobs[key])]
        self._cmd_flags = flags
        import time as _time

        try:
            # launch ALL workers first, then handshake: each interpreter
            # pays its import/pipeline-load cost concurrently, and
            # startup_timeout stays a shared total budget
            for _ in range(n_workers):
                self.procs.append(self._launch_worker())
            handshake_deadline = _time.monotonic() + startup_timeout
            for p in self.procs:
                addr = self._handshake(p, handshake_deadline)
                self.addresses.append(addr)
                self.registry.register(service, addr)
            self.router = RoutingServer(self.registry, service, host, 0,
                                        timeout=reply_timeout,
                                        resilience=resilience,
                                        catalog=self.catalog,
                                        isolate_workers=isolate_workers)
            self._refresh_placement()
        except BaseException:
            # failed startup must not orphan already-spawned workers or
            # leak the saved-pipeline tempdir (stop() is unreachable when
            # __init__ raises)
            for p in self.procs:
                if p.poll() is None:
                    p.kill()
            shutil.rmtree(self._tmp, ignore_errors=True)
            raise

    def _worker_cmd(self, port: int = 0) -> List[str]:
        """The worker argv for the CURRENT generation: a swap updates
        ``_stage_path``/``generation`` (or the per-model spec in
        multi-tenant mode), so restarts and scale-ups always serve the
        fleet's live pipelines, never the boot-time ones."""
        import json as _json
        import sys

        cmd = [sys.executable, "-m", "synapseml_tpu.io.serving_worker"]
        if self._models_spec:
            cmd += ["--models-json", _json.dumps(self._models_spec)]
        else:
            cmd += [self._stage_path, "--generation", str(self.generation)]
        cmd += list(self._cmd_flags)
        if port:
            cmd += ["--port", str(port)]
        return cmd

    def _refresh_placement(self) -> None:
        """Re-plan cost-driven placement over the CURRENT worker set
        (no-op for a single-tenant fleet); decisions land in the
        telemetry ring and ``GET /placement``."""
        if self.router.placement is None:
            return
        try:
            self.router.placement.refresh(
                self.registry.lookup(self.service))
        except Exception:
            _logger.debug("placement refresh failed", exc_info=True)

    def _launch_worker(self, port: int = 0):
        """Popen one worker process (no handshake yet). ``port`` pins the
        listen port — how ``restart_worker`` resurrects a kill victim at
        its old address so the router's prober can re-admit it. The
        worker's stderr goes to a file under the fleet's directory, so a
        worker that dies can say why (``_startup_error``)."""
        import os
        import subprocess

        log_path = os.path.join(
            self._tmp, f"worker-{len(self._stderr_paths) + 1}.stderr")
        with open(log_path, "ab") as log:
            p = subprocess.Popen(self._worker_cmd(port),
                                 stdout=subprocess.PIPE, stderr=log,
                                 text=True, env=self._env)
        self._stderr_paths[p.pid] = log_path
        return p

    def _startup_error(self, p, what: str) -> str:
        """The message for a worker that did not come up: what happened,
        the end of the worker's own stderr, and — when the environment asks
        for an accelerator — the one-process-per-chip rule, since a chip
        that is already held is the usual cause."""
        msg = f"serving worker {what}"
        if p.poll() is not None:
            msg += f" (exit code {p.returncode})"
        try:
            with open(self._stderr_paths[p.pid], "rb") as f:
                tail = f.read()[-2000:].decode(errors="replace").strip()
        except (KeyError, OSError):
            tail = ""
        msg += (f"; its stderr ends:\n{tail}" if tail
                else "; it wrote nothing to stderr")
        from ..runtime.topology import requested_platform

        if requested_platform(self._env) not in (None, "cpu"):
            msg += (
                f"\nJAX_PLATFORMS={self._env['JAX_PLATFORMS']} asks every "
                f"worker to open that device before it announces itself, "
                f"and an accelerator chip belongs to one process at a "
                f"time: a worker cannot open a chip that the launching "
                f"process (once it has initialised jax) or another worker "
                f"already holds. Use n_workers=1 per chip from a process "
                f"that stays off jax, or set JAX_PLATFORMS=cpu for workers "
                f"that do no device work.")
        return msg

    def _handshake(self, p, deadline: float) -> str:
        """Read the worker's ``ADDRESS ...`` announcement (bounded by the
        monotonic ``deadline``) and start the forever-drain; returns the
        address."""
        import select
        import time

        line = ""
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(self._startup_error(
                    p, "did not announce its address within "
                       f"{self.startup_timeout}s"))
            # select enforces the deadline even when the worker prints
            # NOTHING (a bare readline would block forever)
            ready, _, _ = select.select([p.stdout], [], [],
                                        min(remaining, 0.5))
            if not ready:
                if p.poll() is not None:
                    raise RuntimeError(
                        self._startup_error(p, "died during startup"))
                continue
            line = p.stdout.readline()
            if line.startswith("ADDRESS "):
                break
            if not line and p.poll() is not None:
                raise RuntimeError(
                    self._startup_error(p, "died during startup"))
        addr = line.split(None, 1)[1].strip()
        # drain further worker stdout forever: a pipeline stage that
        # print()s would otherwise fill the 64KB pipe and wedge the
        # worker mid-request
        threading.Thread(target=self._drain, args=(p.stdout,),
                         daemon=True).start()
        return addr

    @staticmethod
    def _drain(pipe):
        try:
            for _ in pipe:
                pass
        except Exception:
            pass

    @property
    def address(self) -> str:
        return self.router.address

    def routing_table(self):
        return self.registry.routing_table()

    def metrics_snapshot(self) -> dict:
        """Merged fleet snapshot (router + every live worker PROCESS — each
        worker's registry rides in its ``/metrics?format=json`` reply)."""
        return self.router.fleet_snapshot()

    def traces_snapshot(self) -> dict:
        """Stitched fleet traces: router fragments + worker-process
        fragments merged by trace id (what ``GET /traces`` on the front
        door serves)."""
        return self.router.fleet_traces()

    def timeline_snapshot(self) -> dict:
        """The stitched fleet traces rendered as Chrome-trace JSON (what
        ``GET /timeline`` on the front door serves): one timeline, one
        ``pid`` track per worker PROCESS plus the router's own."""
        from ..observability.profiling import render_chrome_trace

        return render_chrome_trace(self.router.fleet_traces())

    def latency_p50(self) -> Optional[float]:
        """Fleet p50 across worker processes, from merged histogram buckets
        (never a mean of per-worker quantiles). Filtered to THIS fleet's
        workers: the router process's registry may carry latency series from
        unrelated in-process servers."""
        labels = {a[len("http://"):] for a in self.addresses}
        return histogram_quantile(self.metrics_snapshot(),
                                  "smt_serving_latency_seconds", 0.5,
                                  label_filter={"server": labels})

    def kill_worker(self, i: int) -> str:
        """SIGKILL worker ``i`` (the fault-injection hook); returns its
        address. The router evicts it on the next failed forward."""
        self.procs[i].kill()
        self.procs[i].wait()
        return self.addresses[i]

    def restart_worker(self, i: int) -> str:
        """Respawn a (killed) worker at its OLD address; returns it. The
        replacement is deliberately NOT re-registered here — the router's
        health prober must discover it via the liveness probe and re-admit
        it, which is exactly the kill -> failover -> re-admission round
        trip ``tests/test_serving_process_fleet.py`` proves."""
        import time

        with self._ops_lock:
            addr = self.addresses[i]
            port = int(addr.rsplit(":", 1)[1])
            if self.procs[i].poll() is None:
                self.procs[i].kill()
                self.procs[i].wait()
            p = self._launch_worker(port=port)
            try:
                new_addr = self._handshake(
                    p, time.monotonic() + self.startup_timeout)
            except BaseException:
                p.kill()
                raise
            assert new_addr == addr, (new_addr, addr)
            with self._lists_lock:
                self.procs[i] = p
        return addr

    def live_addresses(self) -> List[str]:
        """Addresses whose worker process is still alive."""
        with self._lists_lock:
            pairs = list(zip(self.addresses, self.procs))
        return [a for a, p in pairs if p.poll() is None]

    # -- zero-downtime lifecycle -------------------------------------------
    def swap(self, pipeline: Transformer,
             cfg: Optional[LifecycleConfig] = None,
             model: Optional[str] = None) -> int:
        """Zero-downtime rolling hot swap across the worker PROCESSES.

        The new pipeline is saved once (``core.serialization.save_stage``)
        and each worker, one at a time, is: told to drain (its ``/healthz``
        reports ``draining``, so the router's prober cannot re-admit it
        mid-roll), unregistered from the routing table, waited to
        ``inflight == 0``, told to ``/control/swap`` (the worker loads +
        pre-warms OFF the request path and flips between batches), then
        resumed and re-registered. The rest of the fleet serves throughout
        — no request is ever dropped. A worker that DIES mid-roll is
        skipped (it stays out of the routing table) and the roll completes
        on the survivors. Returns the new generation.

        With ``model=`` (multi-tenant fleets) the roll is PER-MODEL and
        deliberately drain-free: only that model's engine flips, so the
        other tenants keep serving on every worker throughout — the whole
        point of slot-isolated generations. Completion is detected via the
        per-model generation in ``/healthz`` (``lifecycle.model_generation``)."""
        import os

        cfg = cfg or self.lifecycle_cfg
        from ..core.serialization import save_stage

        if model is not None:
            if model not in self._models_spec:
                raise KeyError(f"unknown model {model!r}")
            with self._ops_lock:
                gen = self.generations[model] + 1
                stage_path = os.path.join(self._tmp, f"{model}_g{gen}")
                save_stage(pipeline, stage_path)
                for addr in self.live_addresses():
                    if not self._swap_one_model(addr, model, stage_path,
                                                gen, cfg):
                        _logger.warning(
                            "per-model swap of %r did not land on worker "
                            "%s; continuing on the rest", model, addr)
                self.generations[model] = gen
                self._models_spec[model] = {"stage_path": stage_path,
                                            "generation": gen}
                if self.catalog is not None:
                    self.catalog.bump(model, stage_path, gen)
            return gen
        if self._models_spec:
            raise ValueError("multi-tenant fleet: pass model= to swap "
                             "one tenant's pipeline")
        with self._ops_lock:  # serialized against autoscaler add/remove
            gen = self.generation + 1
            stage_path = os.path.join(self._tmp, f"pipeline_g{gen}")
            save_stage(pipeline, stage_path)
            for addr in self.live_addresses():
                if not self._swap_one(addr, stage_path, gen, cfg):
                    _logger.warning(
                        "rolling swap did not land on worker %s "
                        "(re-admitted if still alive); continuing on "
                        "the rest", addr)
            # restarts/scale-ups from here on serve the new generation
            self._stage_path = stage_path
            self.generation = gen
        return gen

    def _swap_one(self, addr: str, stage_path: str, gen: int,
                  cfg: LifecycleConfig) -> bool:
        """Drain -> swap -> re-admit ONE worker; False when the swap did
        not land. EVERY exit path re-admits a worker that still answers —
        a transient swap failure (409 from a straggling prior swap, a slow
        load) must not strand a LIVE worker in ``draining`` forever (the
        prober refuses draining workers, so nothing else would ever bring
        it back). Only a worker that stopped answering stays out."""
        status, _ = post_control(addr, "drain",
                                 timeout=cfg.healthz_timeout_s)
        if status != 200:
            self.registry.unregister(self.service, addr)
            return False
        self.registry.unregister(self.service, addr)
        swapped = False
        try:
            wait_until(
                lambda: (lifecycle_healthz(addr, cfg.healthz_timeout_s)
                         or {}).get("inflight") == 0,
                cfg.drain_timeout_s, cfg.poll_interval_s)
            status, _ = post_control(
                addr, "swap",
                {"stage_path": stage_path, "generation": gen},
                timeout=cfg.healthz_timeout_s)
            if status == 202:
                swapped = wait_until(
                    lambda: (lifecycle_healthz(addr, cfg.healthz_timeout_s)
                             or {}).get("generation") == gen,
                    cfg.swap_timeout_s, cfg.poll_interval_s)
        except Exception:
            swapped = False
        # re-admission is unconditional-if-alive: even when the flip did
        # not (yet) land, a worker serving the OLD generation is strictly
        # better than a stranded one (and an accepted-but-slow swap still
        # flips between batches whenever it finishes)
        status, _ = post_control(addr, "resume",
                                 timeout=cfg.healthz_timeout_s)
        if status != 200:
            return False  # stopped answering: stays unregistered
        self.registry.register(self.service, addr)
        return swapped

    def _swap_one_model(self, addr: str, model: str, stage_path: str,
                        gen: int, cfg: LifecycleConfig) -> bool:
        """Swap ONE model on ONE worker, with NO drain and NO
        unregistration: the other tenants' engines keep draining the
        shared queue, so their traffic never notices the roll. The
        worker's per-model lifecycle loads + pre-warms off the request
        path and flips between batches; completion is the model's own
        generation in ``/healthz`` (top-level generation is some OTHER
        tenant's in a multi-tenant worker)."""
        status, _ = post_control(
            addr, "swap",
            {"model": model, "stage_path": stage_path, "generation": gen},
            timeout=cfg.healthz_timeout_s)
        if status != 202:
            return False
        return wait_until(
            lambda: model_generation(
                lifecycle_healthz(addr, cfg.healthz_timeout_s),
                model) == gen,
            cfg.swap_timeout_s, cfg.poll_interval_s)

    def add_worker(self) -> Optional[str]:
        """Scale UP: spawn one more worker serving the CURRENT generation.
        Its first request of each shape compiles, through jax's persistent
        cache where the deployment has one. Returns the new address (None
        on startup failure)."""
        import time as _time

        with self._ops_lock:
            try:
                p = self._launch_worker()
                addr = self._handshake(
                    p, _time.monotonic() + self.startup_timeout)
            except BaseException:
                _logger.exception("scale-up worker failed to start")
                return None
            with self._lists_lock:
                self.procs.append(p)
                self.addresses.append(addr)
            self.registry.register(self.service, addr)
            self._refresh_placement()
        return addr

    def remove_worker(self, i: Optional[int] = None,
                      cfg: Optional[LifecycleConfig] = None
                      ) -> Optional[str]:
        """Scale DOWN via drain, never kill: the victim is marked draining
        (prober-proof), unregistered, waited to ``inflight == 0``, and
        only then terminated. Returns its address (None when the fleet is
        already at one live worker — a scale-down must not empty it)."""
        cfg = cfg or self.lifecycle_cfg
        with self._ops_lock:
            with self._lists_lock:
                live = [k for k, p in enumerate(self.procs)
                        if p.poll() is None]
                if len(live) <= 1:
                    return None
                if i is None:
                    i = live[-1]
                addr = self.addresses[i]
                p = self.procs[i]
            post_control(addr, "drain", timeout=cfg.healthz_timeout_s)
            self.registry.unregister(self.service, addr)
            wait_until(
                lambda: (lifecycle_healthz(addr, cfg.healthz_timeout_s)
                         or {"inflight": 0}).get("inflight") == 0,
                cfg.drain_timeout_s, cfg.poll_interval_s)
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=10)
                except Exception:
                    p.kill()
            with self._lists_lock:
                self.procs.pop(i)
                self.addresses.pop(i)
            self._refresh_placement()
        return addr

    def start_autoscaler(self, cfg: Optional[LifecycleConfig] = None):
        """Attach + start the SLO control loop (``io/lifecycle.py``) over
        this fleet; returns the :class:`Autoscaler` (stopped by
        ``fleet.stop()``)."""
        from .lifecycle import Autoscaler, ProcessFleetAdapter

        cfg = cfg or self.lifecycle_cfg
        # share the ROUTER's fleet monitor: the adapter samples it with
        # the merged snapshot every tick, so the hedge gate and the
        # posture gauge react to a burn even when nobody polls /slo
        self._autoscaler = Autoscaler(
            ProcessFleetAdapter(self, cfg, slo_monitor=self.router.slo),
            cfg).start()
        return self._autoscaler

    def stop(self) -> None:
        import shutil

        if self._autoscaler is not None:
            self._autoscaler.stop()
        self.router.close()
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        shutil.rmtree(self._tmp, ignore_errors=True)


def serve_continuous(pipeline: Transformer, host: str = "127.0.0.1",
                     port: int = 0, reply_col: str = "reply",
                     reply_timeout: float = 30.0,
                     admission_schema="auto") -> ContinuousServingEngine:
    """Fluent entry for the low-latency path
    (``spark.readStream.continuousServer()`` analogue)."""
    server = ServingServer(host, port, reply_timeout=reply_timeout)
    return ContinuousServingEngine(
        server, pipeline, reply_col=reply_col,
        admission_schema=admission_schema).start()


def serve_distributed(pipeline: Transformer, n_workers: int = 2,
                      **kw) -> DistributedServingEngine:
    """Fluent entry for the per-host fleet
    (``spark.readStream.distributedServer()`` analogue)."""
    return DistributedServingEngine(pipeline, n_workers=n_workers, **kw)
