"""Model serving: HTTP sources/sinks and the micro-batch serving engine.

Reference: Spark Serving (SURVEY.md §2.4) —
- ``HTTPSource``/``DistributedHTTPSource`` (``org/apache/spark/sql/execution/
  streaming/DistributedHTTPSource.scala:202-423``): per-executor ``JVMSharedServer``
  web servers (``:87-199``) with batch-keyed request maps; the sink replies on the
  held-open ``HttpExchange`` (``:144-147``);
- ``ServingUDFs`` (``request_to_string`` / ``string_to_response``);
- fluent entry ``spark.readStream.server()...`` (``core/.../io/IOImplicits.scala``).

Here: ``ServingServer`` holds each request's handler thread on a condition
variable until the pipeline's reply arrives (the HttpExchange analogue);
``MicroBatchServingEngine`` drains pending requests every ``interval`` into a
Table, runs the pipeline, and replies row-by-row. ``serve(...)`` is the fluent
one-liner.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core import Param, Table, Transformer
from ..core.telemetry import get_logger
from ..observability import CONTENT_TYPE as _PROM_CONTENT_TYPE
from ..observability import OPENMETRICS_CONTENT_TYPE as \
    _OPENMETRICS_CONTENT_TYPE
from ..observability import (SLOConfig, SLOMonitor, get_registry,
                             render_openmetrics, render_prometheus, tracing)
from ..runtime.shared import shared_singleton
from . import faultinject
from .http_schema import HTTPRequestData, HTTPResponseData
from .resilience import parse_deadline, remaining_s
from .tenancy import model_from_request

__all__ = ["ServingServer", "MicroBatchServingEngine", "serve",
           "serve_metrics_exposition", "serve_traces_exposition",
           "serve_timeline_exposition", "serve_slo_exposition",
           "join_or_leak", "drain_engine", "choose_batch_size",
           "attribute_batch_cost", "microbatch_target_s",
           "prewarm_pipeline", "request_to_string", "string_to_response"]

_logger = get_logger("io.serving")


class _Pending:
    __slots__ = ("request", "response", "event", "t_enqueue", "trace",
                 "deadline", "model")

    def __init__(self, request: HTTPRequestData,
                 deadline: Optional[float] = None,
                 model: Optional[str] = None):
        self.request = request
        self.response: Optional[HTTPResponseData] = None
        self.event = threading.Event()
        self.t_enqueue = time.perf_counter()
        # absolute deadline (epoch seconds) parsed from X-SMT-Deadline-Ms;
        # None = the request carries no deadline (legacy clients)
        self.deadline = deadline
        # the tenant this request belongs to (io/tenancy.py): None on a
        # single-tenant server. Drives same-model-only displacement and the
        # per-model metric families
        self.model = model
        # server-side request span (enqueue -> reply); begun in the handler
        # thread, ended in respond() — continues the client's traceparent
        # when one arrived, else roots a fresh trace
        self.trace: Optional[tracing.TraceSpan] = None


class ServingServer:
    """Threaded HTTP server holding exchanges open until ``respond`` is called.

    The ``JVMSharedServer`` analogue: requests land in a map keyed by an id;
    the serving engine drains them with ``get_requests`` and replies with
    ``respond`` — the handler thread then completes the held-open exchange."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 api_path: str = "/", reply_timeout: float = 30.0):
        self.reply_timeout = reply_timeout
        self._pending: Dict[str, _Pending] = {}
        self._queue: List[str] = []
        self._lock = threading.Lock()
        from collections import deque
        self._latencies = deque(maxlen=4096)
        self.requests_received = 0  # JVMSharedServer request counters (:96-105)
        self.responses_sent = 0
        # admission-time request validation: when an engine installs the
        # pipeline's declared input schema here, malformed POST bodies are
        # answered 400 WITH THE SCHEMA DIFF in the handler thread — they
        # never occupy a batch slot or 500 deep inside a worker pipeline
        self.admission_schema = None
        self.admission_rejections = 0
        # deadline-aware shedding state: a per-request service-time EWMA
        # (reported by the engines per processed batch) drives the
        # queue-wait estimate behind the 429 admission check. Written only
        # by the single engine thread; read lock-free in handler threads
        # (a stale float makes the estimate slightly stale, never wrong).
        self._svc_ewma_s: Optional[float] = None
        # per-request device-cost model (engines report each batch's
        # profiled FLOPs via note_batch_cost): an EWMA of FLOPs/request
        # and FLOPs/entity-byte. Written only by the engine thread, read
        # lock-free in handlers — the cost-aware shedder uses it to
        # displace the most EXPENSIVE queued work first under overload.
        self._cost_per_req: Optional[float] = None
        self._cost_per_byte: Optional[float] = None
        # multi-tenancy (io/tenancy.py): a multi-model engine attaches its
        # ModelCatalog here; requests then carry a model id (header or
        # ?model=) validated against it (404 on unknown — a CLIENT error,
        # so it never burns SLO budget). ``default_model`` keeps untagged
        # legacy traffic working. Per-model service/cost EWMAs mirror the
        # flat ones so the shedder estimates each tenant's OWN queue and
        # displacement stays within one tenant.
        self.catalog = None
        self.default_model: Optional[str] = None
        self._model_svc: Dict[str, float] = {}
        self._model_cost_per_req: Dict[str, float] = {}
        self._model_cost_per_byte: Dict[str, float] = {}
        # fleet-lifecycle wiring (io/lifecycle.py): the engine attaches its
        # generation-tagged pipeline slot here so /healthz can report
        # {state, generation, inflight} and /control/{drain,resume,swap}
        # can drive rolling swaps. ``swap_loader(stage_path)`` produces the
        # new pipeline (default: core.serialization.load_stage);
        # ``swap_prewarm(pipeline)`` runs it once off the request path.
        # Multi-model workers keep one lifecycle slot PER model in
        # ``lifecycles`` — a swap of model A flips A's slot and never
        # touches B's (the tenancy generation contract).
        self.lifecycle = None
        self.lifecycles: Dict[str, object] = {}
        self.swap_loader = None
        self.swap_prewarm = None
        self.swap_prewarms: Dict[str, Callable] = {}
        # tenant admission hooks: the multi-tenant engine host installs
        # these so /control/load and /control/unload can fault a cataloged
        # model in (or evict it) at runtime
        self.tenant_admit = None
        self.tenant_evict = None
        # the most recent real request: the pre-warm replay sample a swap
        # uses to compile the incoming pipeline before the flip (per model
        # on a multi-tenant worker — each tenant pre-warms with ITS shape)
        self.last_request: Optional[HTTPRequestData] = None
        self.last_request_by_model: Dict[str, HTTPRequestData] = {}
        # drain-then-stop: once set, new work is answered 503 + Retry-After
        # (counted in smt_serving_shed_total{reason=shutdown}) while
        # in-flight requests finish — close() never yanks the listener out
        # from under held-open exchanges
        self._shutting_down = False
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def _handle(self, method: str):
                op_path = self.path.partition("?")[0]
                # chaos seam: a fault plan (io/faultinject.py) can wedge,
                # 5xx, disconnect or delay THIS worker's handling — how the
                # router's breakers/hedges/failover are exercised in CI
                rule = faultinject.act(
                    "server.handle",
                    f"{outer.server_label} {method} {op_path}")
                if rule is not None and faultinject.apply_server_fault(
                        rule, self):
                    return
                if method == "GET" and op_path == "/metrics":
                    # answered by the SERVER, not the pipeline: scrapes must
                    # work even when the engine is wedged, and must never
                    # occupy a micro-batch slot
                    serve_metrics_exposition(self)
                    return
                if method == "GET" and op_path == "/traces":
                    # same rule for the flight recorder: reading traces of
                    # a wedged engine is exactly when you need them
                    serve_traces_exposition(self)
                    return
                if method == "GET" and op_path == "/timeline":
                    # the flight recorder as Chrome-trace JSON (open in
                    # Perfetto); same server-answers rule as /traces
                    serve_timeline_exposition(self)
                    return
                if method == "GET" and op_path == "/slo":
                    # burn-rate / error-budget state (observability/slo.py);
                    # server-answered like /metrics — reading the budget of
                    # a wedged engine is exactly when you need it
                    outer._serve_slo(self)
                    return
                if method == "GET" and op_path == "/healthz":
                    # the dedicated cheap liveness/lifecycle endpoint: the
                    # router's re-admission prober and the autoscaler read
                    # it, so it must answer even mid-drain or mid-swap and
                    # never occupy a batch slot
                    outer._serve_healthz(self)
                    return
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else None
                if method == "POST" and op_path.startswith("/control/"):
                    # lifecycle control plane (drain/resume/swap): answered
                    # in the handler thread, valid even while draining —
                    # resume must work on a drained worker
                    outer._serve_control(self, op_path[len("/control/"):],
                                         body)
                    return
                if outer._shutting_down:
                    # drain-then-stop: the listener is still up so
                    # in-flight exchanges can finish, but NEW work gets an
                    # honest 503 + Retry-After instead of riding into a
                    # closing server
                    outer._shed("shutdown", count_received=True)
                    try:
                        self.send_response(503)
                        self.send_header("Retry-After", "1")
                        self.send_header("Content-Length", "0")
                        self.end_headers()
                    except OSError:
                        pass
                    return
                # schema admission BEFORE displacement: a request that is
                # going to be 400'd anyway must never evict valid queued
                # work via the cost-displacement path below
                if method == "POST" and outer.admission_schema is not None:
                    errs = admission_errors(outer.admission_schema, body)
                    if errs:
                        payload = json.dumps({
                            "error": "request schema validation failed",
                            "errors": errs,
                            "expected_schema":
                                outer.admission_schema.to_dict(),
                        }).encode()
                        with outer._lock:
                            outer.requests_received += 1
                            outer.admission_rejections += 1
                        try:
                            self.send_response(400)
                            self.send_header("Content-Type",
                                             "application/json")
                            self.send_header("Content-Length",
                                             str(len(payload)))
                            self.end_headers()
                            self.wfile.write(payload)
                        except OSError:
                            pass  # client went away
                        return
                # tenant selection (io/tenancy.py): with a catalog
                # attached, the model id comes from the X-SMT-Model header
                # (or ?model=), bounded by the catalog — an UNKNOWN model
                # is a client error (404 + admission_rejections), never an
                # SLO-burning shed
                model: Optional[str] = None
                if outer.catalog is not None:
                    model = model_from_request(self.headers, self.path) \
                        or outer.default_model
                    if model is None or model not in outer.catalog:
                        payload = json.dumps({
                            "error": f"unknown model {model!r}",
                            "models": outer.catalog.models(),
                        }).encode()
                        with outer._lock:
                            outer.requests_received += 1
                            outer.admission_rejections += 1
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
                # deadline-aware load shedding AT THE DOOR: work that
                # cannot possibly answer in time must never occupy a batch
                # slot. Requests without the deadline header (legacy
                # clients talking straight to a worker) keep the old
                # behavior; the routing front door always stamps one.
                deadline = parse_deadline(self.headers)
                if deadline is not None:
                    rem = remaining_s(deadline)
                    if rem <= 0:
                        outer._shed("expired", count_received=True,
                                    model=model)
                        try:
                            self.send_error(504, "deadline already expired")
                        except OSError:
                            pass
                        return
                    # per-tenant estimate: only the arriving model's OWN
                    # queue counts against its deadline — another tenant's
                    # backlog must not shed this one's traffic
                    est = outer.estimated_queue_wait_s(model)
                    # posture escalation (observability/slo.py): with the
                    # error budget near exhaustion the margin drops below
                    # 1.0 and shedding starts BEFORE the queue estimate
                    # fully swallows the deadline
                    allowed = rem * outer.slo.shed_margin()
                    if est > allowed:
                        # the queue ahead of this request already costs
                        # more than its remaining deadline: before 429'ing
                        # the newcomer, try displacing strictly MORE
                        # EXPENSIVE queued work (per-stage cost EWMA) —
                        # under 429-pressure the costly requests shed
                        # first, not whoever arrived last. Displacement is
                        # SAME-MODEL only: one tenant's overload displaces
                        # only its own queue.
                        if not outer._admit_by_displacement(
                                body, est, allowed, model=model):
                            outer._shed("overload", count_received=True,
                                        model=model)
                            try:
                                self.send_response(429)
                                self.send_header(
                                    "Retry-After",
                                    str(max(1, int(est - rem) + 1)))
                                self.send_header("Content-Length", "0")
                                self.end_headers()
                            except OSError:
                                pass
                            return
                req = HTTPRequestData(
                    url=self.path, method=method,
                    headers=dict(self.headers.items()), entity=body)
                # the swap pre-warm replay sample (a torn read is impossible
                # — this is a single reference assignment)
                outer.last_request = req
                if model is not None:
                    outer.last_request_by_model[model] = req
                rid = uuid.uuid4().hex
                slot = _Pending(req, deadline=deadline, model=model)
                if tracing.is_enabled():
                    attrs = {"server": outer.server_label,
                             "method": method, "path": self.path}
                    if model is not None:
                        attrs["model"] = model
                    slot.trace = tracing.get_tracer().begin_span(
                        "request",
                        parent=tracing.extract_context(req.headers),
                        attributes=attrs)
                with outer._lock:
                    outer._pending[rid] = slot
                    outer._queue.append(rid)
                    outer.requests_received += 1
                outer._on_enqueue(model)
                # never park past the request's own deadline: a client with
                # 200ms left gets its 504 in 200ms, not reply_timeout later
                wait_s = outer.reply_timeout
                if deadline is not None:
                    wait_s = max(0.0, min(wait_s, remaining_s(deadline)))
                if not slot.event.wait(wait_s):
                    # the pop decides the race: whoever removes the slot
                    # (this handler or a concurrent respond()) owns its
                    # finalization — both ending the trace span would let
                    # a request that was really answered 200 get recorded
                    # in /traces as a 504 error trace
                    with outer._lock:
                        won = outer._pending.pop(rid, None) is not None
                    if won:
                        if (deadline is not None
                                and time.time() >= deadline):
                            # the 504 below is the DEADLINE firing (the
                            # wait was deadline-bounded): count the shed
                            # here — the drain-time path only sees slots
                            # this handler has not already reclaimed
                            outer._shed("expired", model=slot.model)
                        if slot.trace is not None:
                            slot.trace.set_attribute("status", 504)
                            slot.trace.end(error="serving engine timed out")
                        try:
                            self.send_error(504, "serving engine timed out")
                        except OSError:
                            pass  # client already gone
                        return
                    # respond() won the slot between the timeout firing and
                    # the pop: the real reply is landing — wait it out
                    slot.event.wait(5.0)
                    if slot.response is None:  # respond() died mid-flight
                        try:
                            self.send_error(504, "serving engine timed out")
                        except OSError:
                            pass
                        return
                resp = slot.response
                try:
                    self.send_response(resp.status_code or 200)
                    # Content-Length is computed below; hop-by-hop headers are
                    # the server's to manage (RFC 7230 §6.1) — forwarding either
                    # from a pipeline-supplied response would emit
                    # duplicates/mis-framing.
                    skip = {"content-length", "transfer-encoding", "connection",
                            "keep-alive", "upgrade", "proxy-authenticate",
                            "proxy-authorization", "te", "trailer"}
                    for k, v in resp.headers.items():
                        if k.lower() not in skip:
                            self.send_header(k, v)
                    ent = resp.entity or b""
                    self.send_header("Content-Length", str(len(ent)))
                    self.end_headers()
                    self.wfile.write(ent)
                except (BrokenPipeError, ConnectionResetError, OSError):
                    _logger.debug("serving: client disconnected before reply")
                    return
                with outer._lock:
                    outer.responses_sent += 1

            def do_GET(self):
                self._handle("GET")

            def do_POST(self):
                self._handle("POST")

            def log_message(self, fmt, *args):  # route into framework logging
                _logger.debug("serving: " + fmt, *args)

        class Server(ThreadingHTTPServer):
            # handler threads must not block interpreter shutdown (they park on
            # reply events for up to reply_timeout) — source of the fatal-exit
            # flake when a test tears down mid-request
            daemon_threads = True
            # burst headroom: the default backlog (5) TCP-resets overflow
            # connections instead of letting the shedder answer 429
            request_queue_size = 128

        self._httpd = Server((host, port), Handler)
        self.host, self.port = self._httpd.server_address[:2]
        # registry metrics, labeled by this server's address so fleets of
        # in-process servers share one registry without colliding; created
        # BEFORE the accept thread starts so handlers never race them.
        # Request/response COUNTERS sync from the existing plain ints at
        # snapshot time (registry collector) — zero added locking on the
        # request hot path, which is measurably tail-latency sensitive
        # under the GIL; only the latency histogram observes per reply.
        self.server_label = f"{self.host}:{self.port}"
        reg = self._reg = get_registry()
        # SLO burn-rate monitor over THIS server's series (GET /slo; the
        # deadline shedder consults its posture): fed passively once per
        # rate-limit gap from the engine's per-batch hook, and on every
        # /slo read — the worker reacts to budget state without waiting
        # for anyone to scrape it
        self.slo = SLOMonitor(SLOConfig.from_env(),
                              label_filter={"server": {self.server_label}},
                              name=self.server_label)
        # ledger baseline: deltas (and therefore the budget) count from
        # server start — this server's labeled series don't exist yet, so
        # the baseline reads zero even on a long-lived shared registry
        try:
            self.slo.observe(reg.snapshot(), force=True)
        except Exception:
            _logger.debug("SLO baseline sample failed", exc_info=True)
        self._m_requests = reg.counter(
            "smt_serving_requests_total", "HTTP requests received",
            ("server",)).labels(self.server_label)
        self._m_responses = reg.counter(
            "smt_serving_responses_total", "pipeline replies sent",
            ("server",)).labels(self.server_label)
        self._m_latency = reg.histogram(
            "smt_serving_latency_seconds", "enqueue->reply latency",
            ("server",)).labels(self.server_label)
        self._m_admission_rejects = reg.counter(
            "smt_serving_admission_rejections_total",
            "POST bodies answered 400 by schema admission",
            ("server",)).labels(self.server_label)
        # deadline shedding: "expired" = the deadline passed (504 at the
        # door or in the queue), "overload" = the queue-wait estimate
        # exceeded the remaining deadline (429 + Retry-After)
        self._m_shed = reg.counter(
            "smt_serving_shed_total",
            "requests shed by deadline-aware admission",
            ("server", "reason"))
        # per-MODEL mirrors of the SLI families (io/tenancy.py): the flat
        # families above keep their fixed (server[,reason]) schemas — every
        # existing scraper/merge/SLO path is untouched — and a request that
        # carries a cataloged model id ALSO lands here. Model values are
        # bounded by the catalog (SMT014-safe); per-model SLO monitors
        # (label_filter={"model": ...}) read these instead of the flat ones.
        self._m_model_latency = reg.histogram(
            "smt_serving_model_latency_seconds",
            "enqueue->reply latency per tenant model",
            ("server", "model"))
        self._m_model_shed = reg.counter(
            "smt_serving_model_shed_total",
            "requests shed by deadline-aware admission per tenant model",
            ("server", "model", "reason"))
        self._m_model_errors = reg.counter(
            "smt_serving_model_errors_total",
            "batches answered 500 per tenant model",
            ("server", "model"))
        self._models_seen: set = set()  # label hygiene for close()
        reg.register_collector(self._collect_metrics)
        # device-memory gauges sync at scrape time (graceful no-op until a
        # backend with allocator stats exists): every worker's /metrics
        # carries its HBM watermarks into the fleet merge
        from ..observability.profiling import install_memory_collector

        install_memory_collector(reg)
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name=f"serving-{self.port}", daemon=True)
        self._thread.start()

    def _on_enqueue(self, model: Optional[str] = None) -> None:
        """Hook for push-mode engines (continuous serving overrides).
        ``model`` is the arriving request's tenant so a multi-tenant host
        can wake ONLY that tenant's dispatcher (single-tenant engines
        ignore it)."""

    def _collect_metrics(self) -> None:
        """Snapshot-time sync of the plain-int request counters into the
        registry (see the collector note in ``__init__``)."""
        self._m_requests.sync_total(self.requests_received)
        self._m_responses.sync_total(self.responses_sent)
        self._m_admission_rejects.sync_total(self.admission_rejections)

    @property
    def address(self) -> str:
        return f"http://{self.host}:{self.port}"

    def _shed(self, reason: str, count_received: bool = False,
              model: Optional[str] = None) -> None:
        """Count one shed request (and, for door-side sheds, the receive —
        handler threads that return early never hit the normal counters).
        ``model`` additionally lands the shed in the per-model mirror
        family — the flat aggregate ALWAYS counts, so single-tenant
        dashboards and the fleet autoscaler see the same totals."""
        if count_received:
            with self._lock:
                self.requests_received += 1
        self._m_shed.labels(self.server_label, reason).inc()
        if model is not None:
            self._models_seen.add(model)
            self._m_model_shed.labels(self.server_label, model,
                                      reason).inc()

    def note_model_error(self, model: str) -> None:
        """Per-tenant engines report a 500'd batch here (the per-model
        mirror of ``smt_serving_pipeline_errors_total``)."""
        self._models_seen.add(model)
        self._m_model_errors.labels(self.server_label, model).inc()

    def note_batch(self, n_requests: int, seconds: float,
                   model: Optional[str] = None) -> None:
        """Engines report each processed batch here; feeds the per-request
        service-time EWMA behind ``estimated_queue_wait_s`` and (rate-
        limited) the SLO monitor's sample ring. ``model`` also updates
        that tenant's own EWMA — the per-tenant queue-wait estimator."""
        if n_requests <= 0 or seconds < 0:
            return
        per = seconds / n_requests
        cur = self._svc_ewma_s
        self._svc_ewma_s = per if cur is None else 0.8 * cur + 0.2 * per
        if model is not None:
            cur = self._model_svc.get(model)
            self._model_svc[model] = per if cur is None \
                else 0.8 * cur + 0.2 * per
        try:
            # deferred-snapshot form: a busy engine pays one registry
            # snapshot per sample gap, not one per batch
            self.slo.maybe_observe(self._reg.snapshot)
        except Exception:
            _logger.debug("SLO sample failed", exc_info=True)

    def note_batch_cost(self, flops: float, n_requests: int,
                        total_entity_bytes: int,
                        model: Optional[str] = None) -> None:
        """Engines report each batch's profiled device cost
        (``observability.profiling.cost_snapshot`` delta). Maintains the
        FLOPs-per-request and FLOPs-per-entity-byte EWMAs behind
        ``estimated_request_cost`` — the cost-aware shedder's model.
        ``model`` also feeds that tenant's EWMAs AND the attached catalog
        (``ModelCatalog.note_cost``) — the signal behind cost-driven
        placement."""
        if flops <= 0 or n_requests <= 0:
            return
        per = flops / n_requests
        cur = self._cost_per_req
        self._cost_per_req = per if cur is None else 0.8 * cur + 0.2 * per
        pb = flops / total_entity_bytes if total_entity_bytes > 0 else None
        if pb is not None:
            cur = self._cost_per_byte
            self._cost_per_byte = pb if cur is None \
                else 0.8 * cur + 0.2 * pb
        if model is not None:
            cur = self._model_cost_per_req.get(model)
            self._model_cost_per_req[model] = per if cur is None \
                else 0.8 * cur + 0.2 * per
            if pb is not None:
                cur = self._model_cost_per_byte.get(model)
                self._model_cost_per_byte[model] = pb if cur is None \
                    else 0.8 * cur + 0.2 * pb
            if self.catalog is not None:
                self.catalog.note_cost(model, per)

    def estimated_request_cost(self, n_entity_bytes: int,
                               model: Optional[str] = None) -> float:
        """Estimated device FLOPs for a request with this payload size:
        the per-byte EWMA when the model has one (payload size is the one
        admission-time signal that differentiates requests), else the flat
        per-request EWMA, else 0.0 — on ignorance every request costs the
        same and the shedder keeps its old arrival-order behavior. With a
        ``model``, that tenant's own EWMAs are preferred (falling back to
        the flat ones until its first profiled batch)."""
        if model is not None:
            pb = self._model_cost_per_byte.get(model)
            if pb is not None:
                return pb * n_entity_bytes
            per = self._model_cost_per_req.get(model)
            if per is not None:
                return per
        pb = self._cost_per_byte
        if pb is not None:
            return pb * n_entity_bytes
        return self._cost_per_req or 0.0

    def _admit_by_displacement(self, body: Optional[bytes], est: float,
                               allowed_s: float,
                               model: Optional[str] = None) -> bool:
        """Cost-aware overload admission: try to admit the arriving
        request by shedding strictly MORE EXPENSIVE queued requests
        (429, ``reason="cost"``) until the queue estimate fits inside
        ``allowed_s``. Only deadline-carrying queued work is displaceable
        (legacy no-deadline requests keep their never-shed contract), and
        only SAME-MODEL work: tenant isolation means one model's overload
        can never evict another model's queued requests (untagged
        traffic, ``model=None``, likewise only displaces untagged work —
        the exact single-tenant behavior). False = displacement cannot
        free enough: the caller sheds the newcomer exactly as before the
        cost model existed."""
        svc = (self._model_svc.get(model) if model is not None else None) \
            or self._svc_ewma_s
        if svc is None or svc <= 0:
            return False
        need = est - allowed_s
        k = int(need / svc) + 1  # queued requests to displace
        arriving = self.estimated_request_cost(len(body or b""), model)
        victims: List[_Pending] = []
        with self._lock:
            cand = []
            for rid in self._queue:
                slot = self._pending.get(rid)
                if slot is None or slot.deadline is None:
                    continue
                if slot.model != model:
                    continue  # never displace another tenant's work
                cost = self.estimated_request_cost(
                    len(slot.request.entity or b""), model)
                if cost > arriving:
                    cand.append((cost, rid))
            if len(cand) < k:
                return False
            cand.sort(reverse=True)  # most expensive first
            for _cost, rid in cand[:k]:
                victims.append(self._pending.pop(rid))
                self._queue.remove(rid)
        for slot in victims:
            self._shed("cost", model=slot.model)
            self._finish(slot, HTTPResponseData(
                429, "shed for cheaper work under overload",
                {"Retry-After": "1"}), shed=True)
        return True

    def _slots_for(self, rids) -> Dict[str, "_Pending"]:
        """rid -> still-pending slot (cost attribution joins batch results
        back to their request spans)."""
        with self._lock:
            return {rid: self._pending[rid] for rid in rids
                    if rid in self._pending}

    def _serve_slo(self, handler) -> None:
        """``GET /slo``: sample the registry NOW (force — a human asking
        for the budget deserves a fresh number) and serve the monitor's
        status as JSON."""
        try:
            self.slo.observe(self._reg.snapshot(), force=True)
        except Exception:
            _logger.debug("SLO sample failed during /slo", exc_info=True)
        serve_slo_exposition(handler, self.slo.status())

    def estimated_queue_wait_s(self, model: Optional[str] = None) -> float:
        """Queue depth × observed per-request service time (from the
        engines' per-batch reports): what a request admitted NOW would wait
        before its reply starts. 0.0 until the first batch completes — the
        estimator must never shed on ignorance. With ``model``, only that
        tenant's OWN queued requests count (per-tenant engines drain each
        model's queue independently, so another tenant's backlog is not
        ahead of this request)."""
        if model is None:
            svc = self._svc_ewma_s
            if svc is None:
                return 0.0
            return len(self._queue) * svc
        svc = self._model_svc.get(model) or self._svc_ewma_s
        if svc is None:
            return 0.0
        with self._lock:
            depth = sum(1 for rid in self._queue
                        if (s := self._pending.get(rid)) is not None
                        and s.model == model)
        return depth * svc

    def attach_lifecycle(self, lifecycle, swap_loader=None,
                         swap_prewarm=None, model: Optional[str] = None
                         ) -> None:
        """Wire the engine's generation-tagged pipeline slot
        (``io/lifecycle.py``) into ``/healthz`` + ``/control/*``. On a
        multi-model worker each tenant engine attaches with its ``model``
        — one slot per model, so a swap of one never flips another; the
        FIRST attached slot also serves as the untagged default."""
        if model is not None:
            self.lifecycles[model] = lifecycle
            if swap_prewarm is not None:
                self.swap_prewarms[model] = swap_prewarm
            if self.lifecycle is None:
                self.lifecycle = lifecycle
        else:
            self.lifecycle = lifecycle
            if swap_prewarm is not None:
                self.swap_prewarm = swap_prewarm
        if swap_loader is not None:
            self.swap_loader = swap_loader

    def begin_shutdown(self) -> None:
        """Start refusing new work (503 + Retry-After, counted as
        ``reason=shutdown`` sheds) while in-flight requests finish; the
        engines call this first so their dispatcher can drain the queue
        before the listener goes away."""
        self._shutting_down = True

    def inflight(self) -> int:
        """Held-open exchanges right now (the /healthz ``inflight``)."""
        with self._lock:
            return len(self._pending)

    def _serve_healthz(self, handler) -> None:
        lc = self.lifecycle
        payload = lc.healthz() if lc is not None else {
            "state": "serving", "generation": 0}
        if self._shutting_down:
            payload["state"] = "draining"
        payload["inflight"] = self.inflight()
        payload["queue_wait_s"] = round(self.estimated_queue_wait_s(), 6)
        if self.lifecycles:
            # the per-tenant view: each resident model's own lifecycle
            # slot (the fleet's per-model roll waits on models[m].generation)
            payload["models"] = {m: slot.healthz()
                                 for m, slot in
                                 sorted(self.lifecycles.items())}
        body = json.dumps(payload).encode()
        try:
            handler.send_response(200)
            handler.send_header("Content-Type", "application/json")
            handler.send_header("Content-Length", str(len(body)))
            handler.end_headers()
            handler.wfile.write(body)
        except OSError:
            pass

    def _serve_control(self, handler, op: str, body) -> None:
        """``POST /control/{drain,resume,swap,load,unload}`` — the worker
        half of the fleet's rolling swap and, on a multi-tenant worker,
        the tenant control plane. Every op accepts an optional ``model``
        in its JSON body: drain/resume/swap then act on THAT model's
        lifecycle slot only. ``load``/``unload`` fault a cataloged model
        in / evict it via the engine host's hooks. Answered entirely in
        the handler thread; the expensive swap work runs on its own
        thread (lifecycle.swap_async), never here and never on the
        request path."""
        try:
            payload = json.loads((body or b"{}").decode())
            if not isinstance(payload, dict):
                payload = {}
        except Exception:
            payload = {}
        model = payload.get("model")
        if model is not None:
            lc = self.lifecycles.get(model)
        else:
            lc = self.lifecycle
        status, reply = 200, {"ok": True}
        if op in ("load", "unload"):
            hook = self.tenant_admit if op == "load" else self.tenant_evict
            if hook is None:
                status, reply = 503, {"error": "not a multi-tenant worker"}
            elif model is None:
                status, reply = 400, {"error": f"{op} needs a model id"}
            else:
                try:
                    if op == "load":
                        hook(model, payload.get("stage_path"),
                             int(payload.get("generation", 0)))
                    else:
                        hook(model)
                    reply = {"ok": True, "model": model}
                except KeyError as e:
                    status, reply = 404, {"error": str(e)}
                except Exception as e:
                    status, reply = 400, {"error": f"{op} failed: {e}"}
        elif lc is None:
            status, reply = (404, {"error": f"unknown model {model!r}"}) \
                if model is not None else \
                (503, {"error": "no lifecycle attached"})
        elif op == "drain":
            lc.begin_drain()
            reply = lc.healthz()
        elif op == "resume":
            lc.resume()
            reply = lc.healthz()
        elif op == "swap":
            try:
                stage_path = payload["stage_path"]
                generation = int(payload["generation"])
            except Exception as e:
                status, reply = 400, {"error": f"bad swap body: {e}"}
            else:
                loader = self.swap_loader or _default_swap_loader
                prewarm = self.swap_prewarm if model is None \
                    else self.swap_prewarms.get(model)
                accepted = lc.swap_async(
                    lambda: loader(stage_path), generation,
                    prewarm=prewarm)
                if accepted:
                    status, reply = 202, {"generation": generation}
                    if model is not None:
                        reply["model"] = model
                        # the catalog follows the accepted swap so
                        # /placement and snapshot() report the NEW
                        # generation once it lands
                        if self.catalog is not None \
                                and model in self.catalog:
                            self.catalog.bump(model, stage_path,
                                              generation)
                else:
                    status, reply = 409, {"error": "a swap is already "
                                                   "in flight"}
        else:
            status, reply = 404, {"error": f"unknown control op {op!r}"}
        data = json.dumps(reply).encode()
        try:
            handler.send_response(status)
            handler.send_header("Content-Type", "application/json")
            handler.send_header("Content-Length", str(len(data)))
            handler.end_headers()
            handler.wfile.write(data)
        except OSError:
            pass

    def get_requests(self, max_n: Optional[int] = None,
                     model: Optional[str] = None
                     ) -> List[Tuple[str, HTTPRequestData]]:
        """Drain up to ``max_n`` queued request ids (the getBatch analogue).

        Queued work whose deadline already passed is shed HERE — answered
        504 immediately and never handed to the engine, so an expired
        request cannot occupy a batch slot ahead of in-deadline work.

        ``model`` drains only THAT tenant's queued requests (per-tenant
        engines each pull their own work; other tenants' requests keep
        their queue positions untouched). ``model=None`` keeps the exact
        single-tenant drain-the-prefix behavior."""
        now = time.time()
        expired: List[_Pending] = []
        out: List[Tuple[str, HTTPRequestData]] = []
        with self._lock:
            if model is None:
                take = self._queue if max_n is None else self._queue[:max_n]
                for rid in take:
                    slot = self._pending.get(rid)
                    if slot is None:
                        continue
                    if slot.deadline is not None and slot.deadline <= now:
                        # claim the slot HERE (the pop decides the race,
                        # same rule as respond vs the handler timeout):
                        # whoever pops owns finalization, so the shed is
                        # counted once
                        self._pending.pop(rid)
                        expired.append(slot)
                    else:
                        out.append((rid, slot.request))
                del self._queue[:len(take)]
            else:
                keep: List[str] = []
                for rid in self._queue:
                    slot = self._pending.get(rid)
                    if slot is None:
                        continue  # claimed by a handler timeout: drop
                    if slot.model != model or (
                            max_n is not None and len(out) >= max_n):
                        keep.append(rid)
                        continue
                    if slot.deadline is not None and slot.deadline <= now:
                        self._pending.pop(rid)
                        expired.append(slot)
                    else:
                        out.append((rid, slot.request))
                self._queue[:] = keep
        for slot in expired:
            self._shed("expired", model=slot.model)
            self._finish(slot, HTTPResponseData(
                504, "deadline expired in queue"), shed=True)
        return out

    def _trace_slots(self, rids) -> List[_Pending]:
        """The still-pending slots for a drained batch (trace plumbing —
        ``get_requests`` pops the queue but keeps slots until reply)."""
        with self._lock:
            return [self._pending[rid] for rid in rids
                    if rid in self._pending]

    def respond(self, rid: str, response: HTTPResponseData) -> None:
        with self._lock:
            slot = self._pending.pop(rid, None)
        if slot is None:
            _logger.warning("respond: unknown or timed-out request %s", rid)
            return
        self._finish(slot, response)

    def _finish(self, slot: _Pending, response: HTTPResponseData,
                shed: bool = False) -> None:
        """Finalize an already-claimed slot (the caller popped it from
        ``_pending``): release the handler thread, record latency + trace.
        ``shed=True`` (queue-expiry / cost displacement) skips the latency
        recording: the shed is already counted in
        ``smt_serving_shed_total``, and the SLI (``observability/slo.py``)
        counts every shed as one bad event on the invariant that sheds
        NEVER reach the latency histogram — a second, fast "reply" sample
        would double-count the request in ``total`` and deflate burn
        rates exactly during a shed-heavy overload."""
        slot.response = response
        slot.event.set()
        exemplar = None
        tr = slot.trace
        if tr is not None:
            status = response.status_code or 200
            tr.set_attribute("status", status)
            # a 5xx reply marks the trace as an ERROR trace (tail sampling
            # always retains it); the span still measures enqueue->reply
            tr.end(error=f"HTTP {status}" if status >= 500 else None)
            # only point /metrics at a trace the tail sampler KEPT — the
            # root just ended, so the retention decision is known here,
            # and a dangling exemplar is worse than none
            if tr.tracer.is_retained(tr.trace_id):
                exemplar = tr.trace_id
        if shed:
            return
        lat = time.perf_counter() - slot.t_enqueue
        self._latencies.append(lat)
        # same sample into the MERGEABLE histogram: fleet quantiles come
        # from these buckets combined across workers (merge.py). The
        # exemplar is passed explicitly — respond() runs after the
        # pipeline span closed, so there is no ambient trace here.
        self._m_latency.observe(lat, exemplar=exemplar)
        if slot.model is not None:
            # the per-tenant mirror: the model's own SLO monitor reads
            # this family instead of the flat aggregate
            self._models_seen.add(slot.model)
            self._m_model_latency.labels(
                self.server_label, slot.model).observe(
                    lat, exemplar=exemplar)

    def latency_quantile(self, q: float = 0.5) -> Optional[float]:
        """Enqueue->reply latency quantile in seconds over recent requests."""
        lat = list(self._latencies)
        return float(np.quantile(lat, q)) if lat else None

    def close(self, drain_s: float = 0.5) -> None:
        # drain-then-stop: refuse new work (503 + Retry-After via the
        # handler's shutdown check) while in-flight requests finish,
        # bounded by ``drain_s`` — the engines drain their queue before
        # calling close(), so this wait is normally zero
        self._shutting_down = True
        from .lifecycle import wait_until

        wait_until(lambda: not self.inflight(), max(0.0, drain_s),
                   poll_s=0.02)
        # release every STILL-held exchange with 503 so handler threads
        # finish promptly instead of parking out their reply timeout;
        # these were drained-at-shutdown — count them
        with self._lock:
            pending = list(self._pending.items())
            self._pending.clear()
            self._queue.clear()
        for _rid, slot in pending:
            self._shed("shutdown", model=slot.model)
            slot.response = HTTPResponseData(503, "server shutting down")
            slot.event.set()
            if slot.trace is not None:
                slot.trace.set_attribute("status", 503)
                slot.trace.end(error="server shutting down")
        self._httpd.shutdown()
        self._httpd.server_close()
        # retire this server's series + collector: ephemeral ports mean a
        # churning process would otherwise grow the registry without bound
        self._reg.unregister_collector(self._collect_metrics)
        for series in (self._m_requests, self._m_responses, self._m_latency,
                       self._m_admission_rejects):
            series.remove()
        for reason in ("expired", "overload", "cost", "shutdown"):
            self._m_shed.remove(self.server_label, reason)
        for model in self._models_seen:
            self._m_model_latency.remove(self.server_label, model)
            self._m_model_errors.remove(self.server_label, model)
            for reason in ("expired", "overload", "cost", "shutdown"):
                self._m_model_shed.remove(self.server_label, model, reason)


def _default_swap_loader(stage_path: str):
    """The cross-process swap loader: the fleet saved the new pipeline
    with ``core.serialization.save_stage``; the worker loads it back."""
    from ..core.serialization import load_stage

    return load_stage(stage_path)


def join_or_leak(thread: threading.Thread, timeout: float,
                 component: str) -> bool:
    """Join ``thread``; when it fails to exit within ``timeout`` (a wedged
    dispatcher/accept loop), LOG it and count it in
    ``smt_thread_leaks_total{component}`` instead of silently leaking —
    the process-fleet tests assert clean shutdown by this family staying
    empty. Returns True on a clean join."""
    thread.join(timeout)
    if not thread.is_alive():
        return True
    get_registry().counter(
        "smt_thread_leaks_total",
        "threads that failed to join at shutdown",
        ("component",)).labels(component).inc()
    _logger.warning("thread %s (%s) failed to join within %.1fs at "
                    "shutdown; leaking it as a daemon", thread.name,
                    component, timeout)
    return False


def admission_errors(schema, body: Optional[bytes]) -> List[str]:
    """Validate a request body against the pipeline's declared input
    schema (``core.schema.TableSchema``). Empty list = admit. The body
    must be a JSON object (one row) or array of objects."""
    if not body:
        return [f"empty body; expected a JSON object with fields "
                f"{schema.columns}"]
    try:
        payload = json.loads(body.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        return [f"body is not valid JSON ({e}); expected an object with "
                f"fields {schema.columns}"]
    return schema.validate_json_payload(payload)


def resolve_admission_schema(pipeline, admission_schema):
    """Resolve an engine's ``admission_schema`` knob to a TableSchema (or
    None = admission off).

    - ``"auto"`` (the default): the pipeline's declared JSON-body
      contract, ``pipeline.request_schema()`` — the method a serving
      stage uses to describe its request payload fields (distinct from
      ``input_schema()``, which describes TABLE columns: the engine feeds
      ``{id, request}`` tables, so table schemas are not body schemas).
      Pipelines that don't declare a request schema keep admission off.
    - a ``TableSchema`` or ``{name: "dtype:role"}`` dict: used as-is.
    - ``None``: off.
    """
    from ..core.schema import TableSchema

    if admission_schema is None:
        return None
    if isinstance(admission_schema, TableSchema):
        return admission_schema if admission_schema.columns else None
    if isinstance(admission_schema, dict):
        return resolve_admission_schema(pipeline,
                                        TableSchema(admission_schema))
    if admission_schema == "auto":
        get = getattr(pipeline, "request_schema", None)
        schema = get() if callable(get) else None
        return schema if schema is not None and schema.columns else None
    raise ValueError(f"admission_schema must be 'auto', None, a "
                     f"TableSchema or a dict; got {admission_schema!r}")


def engine_metrics(reg, server_label: str, engine: str):
    """The per-engine metric series shared by the micro-batch and continuous
    engines: (batches counter, batch-size histogram, pipeline-error counter,
    request-FLOPs histogram, request-HBM-bytes histogram, chosen-batch-size
    gauge), labeled (server, engine). One definition so the two engines
    cannot fork the family schema."""
    batches = reg.counter(
        "smt_serving_batches_total", "pipeline batches processed",
        ("server", "engine")).labels(server_label, engine)
    batch_size = reg.histogram(
        "smt_serving_batch_size", "requests fused per pipeline batch",
        ("server", "engine")).labels(server_label, engine)
    errors = reg.counter(
        "smt_serving_pipeline_errors_total", "batches answered 500",
        ("server", "engine")).labels(server_label, engine)
    # per-request device-cost attribution (observability ISSUE 15): the
    # profiled FLOPs/bytes of each batch split over its fused requests,
    # with each sample tagged by ITS request's trace-id exemplar
    req_flops = reg.histogram(
        "smt_request_flops",
        "profiled device FLOPs attributed per request "
        "(batch cost / fused requests)",
        ("server", "engine")).labels(server_label, engine)
    req_bytes = reg.histogram(
        "smt_request_hbm_bytes",
        "profiled bytes accessed attributed per request",
        ("server", "engine")).labels(server_label, engine)
    chosen = reg.gauge(
        "smt_serving_chosen_batch_size",
        "adaptive micro-batch size chosen for the next drain "
        "(queue depth x service-time EWMA vs the batch latency target)",
        ("server", "engine")).labels(server_label, engine)
    return batches, batch_size, errors, req_flops, req_bytes, chosen


def microbatch_target_s() -> float:
    """The adaptive batch-sizing latency target (``SMT_MICROBATCH_TARGET_MS``,
    default 250 ms; <= 0 disables adaptive sizing)."""
    try:
        return float(os.environ.get("SMT_MICROBATCH_TARGET_MS", 250.0)) / 1e3
    except (TypeError, ValueError):
        return 0.25


def choose_batch_size(server: "ServingServer", max_batch: int,
                      target_s: float) -> int:
    """Pick the next drain's batch bound from the live signals the server
    already tracks (ROADMAP item 4's last leftover).

    Latency mode: ``n = target_s / svc_ewma`` bounded to [1, max_batch] —
    a batch should take about the target, so one slow batch cannot tax
    every fused request with multi-target latency. Backlog mode: when the
    queue ALONE already costs more than 2x the target (depth x svc), the
    target is unmeetable and throughput wins — drain at ``max_batch`` so
    fusion amortizes the overhead. Cold signals (no EWMA yet) keep the
    old fixed ``max_batch`` behavior."""
    svc = server._svc_ewma_s
    if target_s <= 0 or svc is None or svc <= 0:
        return max_batch
    depth = len(server._queue)  # lock-free len read: staleness is fine
    if depth * svc > 2.0 * target_s:
        return max_batch
    return max(1, min(int(target_s / svc) or 1, max_batch))


def attribute_batch_cost(server: "ServingServer", rids, reqs, cost0,
                         flops_hist, bytes_hist,
                         model: Optional[str] = None) -> None:
    """Attribute one batch's profiled device cost to its requests.

    ``cost0`` is the engine's ``profiling.cost_snapshot()`` read from
    before ``pipeline.transform``; the delta is the batch's cost. Each
    fused request gets an equal share observed into
    ``smt_request_flops`` / ``smt_request_hbm_bytes`` (exemplar = that
    request's own trace id) and stamped onto its request span, so the
    cost is visible in ``/traces`` and ``tools/trace_dump.py``; the
    batch totals land on the active pipeline span. The per-batch totals
    also feed the server's cost EWMAs (``note_batch_cost``) — the model
    behind expensive-first shedding. Must run INSIDE the batch's traced
    context and in the engine thread (the cost accumulator is
    thread-local). Never raises: accounting must never turn a
    successfully-transformed batch into 500s (same invariant as the
    span profiler hook)."""
    try:
        _attribute_batch_cost(server, rids, reqs, cost0,
                              flops_hist, bytes_hist, model)
    except Exception:
        _logger.exception("per-request cost attribution failed")


def _attribute_batch_cost(server: "ServingServer", rids, reqs, cost0,
                          flops_hist, bytes_hist,
                          model: Optional[str] = None) -> None:
    from ..observability.profiling import cost_snapshot

    f1, b1 = cost_snapshot()
    dflops, dbytes = f1 - cost0[0], b1 - cost0[1]
    n = len(rids)
    if n <= 0:
        return
    total_bytes = sum(len(r.entity or b"") for r in reqs)
    server.note_batch_cost(dflops, n, total_bytes, model=model)
    if dflops <= 0 and dbytes <= 0:
        return  # nothing profiled ran: no zero-noise series
    share_f, share_b = dflops / n, dbytes / n
    sp = tracing.current_span()
    if sp is not None:  # the pipeline span carries the batch totals
        sp.set_attribute("flops", dflops)
        if dbytes > 0:
            sp.set_attribute("hbm_bytes", dbytes)
    slots = server._slots_for(rids)
    for rid in rids:
        slot = slots.get(rid)
        tr = slot.trace if slot is not None else None
        tid = tr.trace_id if tr is not None else None
        # ambient=False: a request without its own trace gets NO exemplar
        # — the fallback would stamp the batch leader's trace id on it
        if dflops > 0:
            flops_hist.observe(share_f, exemplar=tid, ambient=False)
        if dbytes > 0:
            bytes_hist.observe(share_b, exemplar=tid, ambient=False)
        if tr is not None:
            tr.set_attribute("flops", share_f)
            if dbytes > 0:
                tr.set_attribute("hbm_bytes", share_b)


def serve_metrics_exposition(handler, snapshot: Optional[dict] = None) -> None:
    """Answer a ``/metrics`` GET on ``handler`` (a BaseHTTPRequestHandler).

    Content negotiation: an ``Accept`` header naming
    ``application/openmetrics-text`` gets the OpenMetrics rendering WITH
    per-bucket trace-id exemplars (exemplar syntax is OpenMetrics-only — a
    0.0.4 parser would fail the whole scrape on it, so the plain text
    default stays exemplar-free). ``?format=json`` returns the raw registry
    snapshot (exemplars included) — the machine-readable side the routing
    front door scrapes and merges (snapshots ride in ordinary worker
    replies; no side channel).
    """
    if snapshot is None:
        snapshot = get_registry().snapshot()
    query = handler.path.partition("?")[2]
    if "format=json" in query.split("&"):
        body = json.dumps(snapshot).encode()
        ctype = "application/json"
    elif "openmetrics-text" in (handler.headers.get("Accept") or ""):
        body = render_openmetrics(snapshot).encode()
        ctype = _OPENMETRICS_CONTENT_TYPE
    else:
        body = render_prometheus(snapshot).encode()
        ctype = _PROM_CONTENT_TYPE
    try:
        handler.send_response(200)
        handler.send_header("Content-Type", ctype)
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.wfile.write(body)
    except OSError:
        pass  # scraper went away


def serve_traces_exposition(handler, payload: Optional[dict] = None) -> None:
    """Answer a ``/traces`` GET on ``handler``: the tail-sampled flight
    recorder as JSON (``payload`` overrides — the routing front door passes
    its stitched fleet view). Always JSON; ``tools/trace_dump.py`` renders
    the waterfall client-side."""
    if payload is None:
        payload = tracing.get_tracer().snapshot()
    body = json.dumps(payload).encode()
    try:
        handler.send_response(200)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.wfile.write(body)
    except OSError:
        pass  # reader went away


def serve_slo_exposition(handler, status: dict) -> None:
    """Answer a ``GET /slo`` on ``handler``: the burn-rate monitor's
    :meth:`~synapseml_tpu.observability.slo.SLOMonitor.status` dict as
    JSON. Callers sample their monitor first (a worker forces a fresh
    registry sample; the routing front door samples its MERGED fleet
    snapshot) — this helper only renders. ``tools/slo_report.py`` renders
    the human view client-side."""
    body = json.dumps(status).encode()
    try:
        handler.send_response(200)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.wfile.write(body)
    except OSError:
        pass  # reader went away


def serve_timeline_exposition(handler, payload: Optional[dict] = None) -> None:
    """Answer a ``GET /timeline``: the flight recorder rendered as
    Chrome-trace/Perfetto JSON (``observability.render_chrome_trace``),
    with recent telemetry events merged in as instant events. ``payload``
    overrides the trace source — the routing front door passes its
    stitched fleet view, so one download shows every worker process as
    its own track."""
    from ..core.telemetry import recent_events
    from ..observability.profiling import render_chrome_trace

    if payload is None:
        payload = tracing.get_tracer().snapshot()
    # default=str: telemetry event extras are caller-supplied (numpy
    # scalars etc.) and must never 500 the timeline endpoint
    body = json.dumps(render_chrome_trace(payload, recent_events()),
                      default=str).encode()
    try:
        handler.send_response(200)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.wfile.write(body)
    except OSError:
        pass  # reader went away


@contextlib.contextmanager
def traced_batch(server: ServingServer, rids, engine: str,
                 model: Optional[str] = None):
    """Per-batch trace plumbing shared by the micro-batch and continuous
    engines: closes each traced request's ``queue_wait`` span (enqueue ->
    drain) and runs the pipeline under ONE ``pipeline`` span parented to
    the first traced request, ACTIVATED in this thread so stage spans
    attach as children. Micro-batch fusion gives N requests one pipeline
    execution — a span tree is single-parent, so the batch leader owns the
    pipeline subtree and the other fused requests' spans carry the
    leader's trace id as ``fused_with``."""
    if not tracing.is_enabled():
        yield
        return
    traced = [s for s in server._trace_slots(rids) if s.trace is not None]
    if not traced:
        yield
        return
    now = time.perf_counter()
    tracer = traced[0].trace.tracer
    for s in traced:
        tracer.record("queue_wait", parent=s.trace,
                      duration_s=max(0.0, now - s.t_enqueue))
    leader = traced[0].trace
    for s in traced[1:]:
        s.trace.set_attribute("fused_with", leader.trace_id)
    attrs = {"engine": engine, "batch_size": len(rids)}
    if model is not None:
        attrs["model"] = model
    pipeline_span = tracer.begin_span(
        "pipeline", parent=leader, attributes=attrs)
    try:
        with tracing.use_span(pipeline_span):
            yield
    except BaseException as e:
        pipeline_span.end(error=e)
        raise
    else:
        pipeline_span.end()


class MicroBatchServingEngine:
    """Drain -> transform -> reply loop (the structured-streaming microbatch loop).

    The pipeline sees a Table with columns ``id`` (str) and ``request``
    (HTTPRequestData); it must produce ``reply_col`` holding HTTPResponseData,
    dicts, or strings (wrapped as 200 text/json)."""

    def __init__(self, server: ServingServer, pipeline: Transformer,
                 reply_col: str = "reply", interval: float = 0.01,
                 max_batch: int = 1024, admission_schema="auto",
                 generation: int = 0):
        from .lifecycle import WorkerLifecycle

        self.server = server
        self.pipeline = pipeline
        self.reply_col = reply_col
        self.interval = interval
        self.max_batch = max_batch
        # install the pipeline's declared input schema for admission-time
        # 400s (a schema diff at the door instead of a worker 500)
        self._admission_knob = admission_schema
        server.admission_schema = resolve_admission_schema(pipeline,
                                                           admission_schema)
        # the generation-tagged pipeline slot: read once per batch, so a
        # hot swap flips atomically BETWEEN batches; /healthz + /control
        # on the server drive it
        self.lifecycle = WorkerLifecycle(pipeline, generation,
                                         on_swap=self._on_swap)
        server.attach_lifecycle(self.lifecycle,
                                swap_prewarm=self._prewarm)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, name="serving-engine",
                                        daemon=True)
        self.batches_processed = 0
        # adaptive drain (ported from the continuous engine): request
        # arrival wakes the loop, and pending work drains immediately after
        # each batch. ``interval`` is the idle-wait bound (the trigger's
        # staleness guarantee), NOT a minimum gap between batches — the old
        # sleep-out-the-tick loop taxed every request with up to a full
        # tick (measured p99 11.4 ms vs the continuous engine's 1.6 ms);
        # micro-batches still form naturally from whatever arrives while
        # the previous batch transforms
        self._work = threading.Event()
        server._on_enqueue = lambda _model=None: self._work.set()
        self._batch_target_s = microbatch_target_s()
        self._m_reg = get_registry()
        (self._m_batches, self._m_batch_size, self._m_pipeline_errors,
         self._m_req_flops, self._m_req_bytes, self._m_chosen) = \
            engine_metrics(self._m_reg, server.server_label, "microbatch")
        self._m_reg.register_collector(self._collect_metrics)

    def _collect_metrics(self) -> None:
        self._m_batches.sync_total(self.batches_processed)

    def _on_swap(self, pipeline) -> None:
        """Slot-flip hook: the engine's view of the pipeline (and the
        admission schema derived from it) follows the new generation."""
        self.pipeline = pipeline
        self.server.admission_schema = resolve_admission_schema(
            pipeline, self._admission_knob)

    def _prewarm(self, pipeline) -> None:
        prewarm_pipeline(self.server, pipeline)

    def start(self) -> "MicroBatchServingEngine":
        self._thread.start()
        return self

    def _run(self):
        from ..observability.profiling import cost_snapshot

        while not self._stop.is_set():
            # adaptive micro-batch sizing from the live queue-depth and
            # service-EWMA signals (bounded by max_batch); the chosen
            # bound is a scrapeable gauge
            limit = choose_batch_size(self.server, self.max_batch,
                                      self._batch_target_s)
            batch = self.server.get_requests(limit)
            if not batch:
                self._work.wait(timeout=self.interval)
                self._work.clear()
                continue
            self._m_chosen.set(limit)
            ids = [rid for rid, _ in batch]
            reqs = np.empty(len(batch), dtype=object)
            reqs[:] = [r for _, r in batch]
            table = Table({"id": np.array(ids, dtype=object), "request": reqs})
            # one slot read per batch: the atomic hot-swap flip point
            pipeline, _generation = self.lifecycle.current()
            t0 = time.perf_counter()
            c0 = cost_snapshot()
            try:
                with traced_batch(self.server, ids, "microbatch"):
                    out = pipeline.transform(table)
                    replies = out[self.reply_col]
                    out_ids = out["id"]
                    # observed INSIDE the batch trace so the bucket gets
                    # the leader request's exemplar
                    self._m_batch_size.observe(len(batch))
                    # per-request device-cost attribution (same trace
                    # context: the batch totals land on the pipeline span)
                    attribute_batch_cost(self.server, ids, reqs, c0,
                                         self._m_req_flops,
                                         self._m_req_bytes)
            except Exception as e:  # reply 500s rather than hanging clients
                _logger.exception("serving pipeline failed")
                for rid in ids:
                    self.server.respond(rid, HTTPResponseData(
                        500, "pipeline error", entity=str(e).encode()))
                self._error = e
                self._m_pipeline_errors.inc()
                continue
            try:
                respond_batch(self.server, ids, out_ids, replies)
            except Exception as e:
                # the REPLY path failed (bad output table shape): the
                # drained requests must still be answered, and the
                # dispatcher thread must survive — a dead loop would leave
                # every future request hanging to its reply timeout
                _logger.exception("serving reply path failed")
                for rid in ids:  # respond() ignores already-answered ids
                    self.server.respond(rid, HTTPResponseData(
                        500, "reply path error", entity=str(e).encode()))
                self._error = e
                self._m_pipeline_errors.inc()
                continue
            self.server.note_batch(len(batch), time.perf_counter() - t0)
            self.batches_processed += 1

    def stop(self) -> None:
        # drain-then-stop: refuse new work first, let the dispatcher
        # answer what is already in flight (bounded), THEN stop the loop
        # and the listener — a shutdown never drops accepted requests
        self.server.begin_shutdown()
        drain_engine(self.server, self._stop)
        self._stop.set()
        self._work.set()
        join_or_leak(self._thread, 5.0,
                     f"serving-engine:{self.server.server_label}")
        self.server.close()
        self._m_reg.unregister_collector(self._collect_metrics)
        for series in (self._m_batches, self._m_batch_size,
                       self._m_pipeline_errors, self._m_req_flops,
                       self._m_req_bytes, self._m_chosen):
            series.remove()
        if self._error is not None:
            _logger.warning("serving engine saw pipeline errors; last: %s", self._error)


def prewarm_pipeline(server: ServingServer, pipeline,
                     model: Optional[str] = None) -> bool:
    """Run ``pipeline`` once on a replay of the server's most recent real
    request — the off-request-path compile a hot swap pays BEFORE the
    flip, so the first post-swap batch is warm. False when no request has
    been seen yet (nothing to replay). With ``model``, the replay
    sample is that tenant's OWN last request — another tenant's payload
    shape would compile the wrong signature."""
    req = server.last_request_by_model.get(model) if model is not None \
        else server.last_request
    if req is None:
        return False
    reqs = np.empty(1, dtype=object)
    reqs[0] = req
    pipeline.transform(Table({"id": np.array(["_warmup"], dtype=object),
                              "request": reqs}))
    return True


def drain_engine(server: ServingServer, stop_event: threading.Event,
                 timeout_s: float = 2.0) -> bool:
    """Wait (bounded) for the server's held-open exchanges to be answered
    while the engine's dispatcher is still running — the engine half of
    drain-then-stop. The server must already be refusing new work
    (``begin_shutdown``), so the in-flight set can only shrink. True when
    fully drained."""
    deadline = time.monotonic() + min(timeout_s, server.reply_timeout)
    while time.monotonic() < deadline and not stop_event.is_set():
        with server._lock:
            busy = bool(server._pending) or bool(server._queue)
        if not busy:
            return True
        time.sleep(0.02)
    return not server.inflight()


def respond_batch(server, batch_ids, out_ids, replies) -> None:
    """Reply to every request in the batch: pipeline outputs get their reply;
    rows the pipeline dropped/filtered get 204 immediately instead of leaving
    the client blocked until reply_timeout -> 504. One un-coercible reply
    (e.g. a non-JSON-serializable object) 500s ITS row — it must not take
    down the rest of the batch or the dispatcher loop."""
    answered = set()
    for rid, rep in zip(out_ids, replies):
        try:
            resp = _coerce_response(rep)
        except Exception as e:
            _logger.exception("reply coercion failed for request %s", rid)
            resp = HTTPResponseData(
                500, "reply coercion failed",
                entity=f"{type(e).__name__}: {e}".encode())
        server.respond(rid, resp)
        answered.add(rid)
    for rid in batch_ids:
        if rid not in answered:
            server.respond(rid, HTTPResponseData(204, "row dropped by pipeline"))


def _coerce_response(rep) -> HTTPResponseData:
    if isinstance(rep, HTTPResponseData):
        return rep
    if rep is None:
        return HTTPResponseData(204, "no content")
    if isinstance(rep, (dict, list)):
        return HTTPResponseData(200, "OK", {"Content-Type": "application/json"},
                                json.dumps(rep, default=_np_default).encode())
    if isinstance(rep, bytes):
        return HTTPResponseData(200, "OK", {}, rep)
    return HTTPResponseData(200, "OK", {"Content-Type": "text/plain"},
                            str(rep).encode())


def _np_default(v):
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, np.generic):
        return v.item()
    raise TypeError(f"not JSON-serializable: {type(v)}")


def serve(pipeline: Transformer, host: str = "127.0.0.1", port: int = 0,
          reply_col: str = "reply", shared: bool = False,
          reply_timeout: float = 30.0,
          admission_schema="auto") -> MicroBatchServingEngine:
    """Fluent entry (the ``spark.readStream.server()...writeStream.server()``
    analogue). ``shared=True`` reuses one server per (host, port) process-wide
    via the SharedSingleton pool, like ``JVMSharedServer``."""
    if shared:
        if port == 0:
            raise ValueError("serve(shared=True) needs an explicit port: the "
                             "singleton is keyed by (host, port) and ephemeral "
                             "port 0 would alias unrelated services")
        server = shared_singleton(
            f"serving:{host}:{port}",
            lambda: ServingServer(host, port, reply_timeout=reply_timeout))
    else:
        server = ServingServer(host, port, reply_timeout=reply_timeout)
    return MicroBatchServingEngine(
        server, pipeline, reply_col=reply_col,
        admission_schema=admission_schema).start()


def request_to_string(req: HTTPRequestData) -> str:
    """Reference ``ServingUDFs.request_to_string``."""
    return req.entity.decode("utf-8", "replace") if req.entity else ""


def string_to_response(s: str, status: int = 200) -> HTTPResponseData:
    """Reference ``ServingUDFs.string_to_response``."""
    return HTTPResponseData(status, "OK", {"Content-Type": "text/plain"},
                            s.encode("utf-8"))
