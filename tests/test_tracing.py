"""Distributed request tracing (ISSUE 7): trace-context propagation, span
trees, tail-sampled retention, histogram exemplars, and end-to-end stitching
through a REAL cross-process serving fleet.

Acceptance contract: one request through ``ProcessServingFleet`` produces a
SINGLE stitched trace at the front door's ``/traces`` containing router,
worker-forward, and pipeline stage spans with consistent parent/child
timing; histogram buckets touched by traced traffic carry resolvable
exemplar trace ids; slow/error traces survive a flood of fast ones.
"""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from synapseml_tpu import observability as obs
from synapseml_tpu.core import Table, Transformer
from synapseml_tpu.io.serving import string_to_response
from synapseml_tpu.observability import merge_traces, tracing

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_tracer():
    """Isolated process-default tracer retaining everything."""
    tr = tracing.Tracer(capacity=128, sample_rate=1.0,
                        latency_threshold_s=60.0, seed=0)
    prev = tracing.set_tracer(tr)
    try:
        yield tr
    finally:
        tracing.set_tracer(prev)


# ---------------------------------------------------------------------------
# W3C traceparent round trip
# ---------------------------------------------------------------------------

def test_traceparent_format_and_parse_round_trip():
    tid, sid = tracing.new_trace_id(), tracing.new_span_id()
    assert len(tid) == 32 and len(sid) == 16
    ctx = tracing.parse_traceparent(f"00-{tid}-{sid}-01")
    assert ctx.trace_id == tid and ctx.span_id == sid and ctx.sampled


@pytest.mark.parametrize("bad", [
    "",
    "garbage",
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span id
    "00-" + "a" * 31 + "-" + "1" * 16 + "-01",   # short trace id
    "00-" + "g" * 32 + "-" + "1" * 16 + "-01",   # non-hex
    "00-0x" + "a" * 30 + "-" + "1" * 16 + "-01",  # int()-only "hex"
    "ff-" + "a" * 32 + "-" + "1" * 16 + "-01",   # forbidden version
])
def test_traceparent_rejects_malformed(bad):
    assert tracing.parse_traceparent(bad) is None


def test_extract_context_case_insensitive():
    tid = tracing.new_trace_id()
    hdr = f"00-{tid}-{'1' * 16}-01"
    for key in ("traceparent", "Traceparent", "TRACEPARENT", "TrAcEpArEnT"):
        ctx = tracing.extract_context({key: hdr})
        assert ctx is not None and ctx.trace_id == tid, key
    assert tracing.extract_context({"other": "x"}) is None


# ---------------------------------------------------------------------------
# span trees + contextvar nesting
# ---------------------------------------------------------------------------

def test_span_tree_parent_child_ids(fresh_tracer):
    with tracing.start_span("root", parent=None) as root:
        assert tracing.current_span() is root
        assert tracing.current_trace_id() == root.trace_id
        with tracing.start_span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            with tracing.start_span("grandchild") as g:
                assert g.parent_id == child.span_id
    assert tracing.current_span() is None
    traces = fresh_tracer.snapshot()["traces"]
    assert len(traces) == 1
    spans = {s["name"]: s for s in traces[0]["spans"]}
    assert spans["root"]["parent_id"] is None
    assert spans["child"]["parent_id"] == spans["root"]["span_id"]
    assert spans["grandchild"]["parent_id"] == spans["child"]["span_id"]
    # children finished before the root: durations nest
    assert spans["child"]["duration_s"] <= spans["root"]["duration_s"]


def test_remote_parent_marks_local_root(fresh_tracer):
    ctx = tracing.parse_traceparent(
        f"00-{tracing.new_trace_id()}-{'2' * 16}-01")
    span = fresh_tracer.begin_span("request", parent=ctx)
    span.end()
    traces = fresh_tracer.snapshot()["traces"]
    assert len(traces) == 1  # finishing the local root completed the trace
    assert traces[0]["trace_id"] == ctx.trace_id
    assert traces[0]["spans"][0]["parent_id"] == ctx.span_id


def test_stage_spans_attach_to_active_trace(fresh_tracer):
    class _Probe(Transformer):  # _ prefix: stays out of the registry
        def _transform(self, table):
            return table

    t = Table({"x": np.arange(3.0)})
    stage = _Probe()
    with tracing.start_span("pipeline", parent=None):
        stage.transform(t)
    stage.transform(t)  # outside any trace: must NOT create a new trace
    traces = fresh_tracer.snapshot()["traces"]
    assert len(traces) == 1
    names = [s["name"] for s in traces[0]["spans"]]
    assert "_Probe.transform" in names
    stage_span = next(s for s in traces[0]["spans"]
                      if s["name"] == "_Probe.transform")
    pipe = next(s for s in traces[0]["spans"] if s["name"] == "pipeline")
    assert stage_span["parent_id"] == pipe["span_id"]
    assert stage_span["attributes"]["rows"] == 3


def test_nested_spans_form_a_tree_with_true_start_times(fresh_tracer):
    """A span entered while a trace span is current is that span's child and
    is current itself until it exits: ``ONNXModel.dispatch`` hangs under
    ``ONNXModel.transform``, not beside it under the pipeline span, and each
    span's start is when it began (not its end less its duration)."""
    from synapseml_tpu.models.zoo import build_model_bytes
    from synapseml_tpu.onnx import ONNXModel

    model = ONNXModel(model_bytes=build_model_bytes("BERTTiny", seed=0),
                      feed_dict={"input_ids": "input_ids"},
                      fetch_dict={"logits": "logits"}, batch_size=4)
    t = Table({"input_ids": np.zeros((6, 8), np.int64)})
    with tracing.start_span("pipeline", parent=None):
        model.transform(t)
        assert tracing.current_span().name == "pipeline"  # restored
    (trace,) = fresh_tracer.snapshot()["traces"]
    by_name = {}
    for s in trace["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    (pipe,), (transform,) = by_name["pipeline"], by_name["ONNXModel.transform"]
    assert transform["parent_id"] == pipe["span_id"]
    for phase, n in (("gather", 1), ("pad", 2), ("dispatch", 2), ("fetch", 2),
                     ("assemble", 1)):
        children = by_name[f"ONNXModel.{phase}"]
        assert len(children) == n
        for child in children:
            assert child["parent_id"] == transform["span_id"], phase
            assert child["attributes"]["method"] == phase
            assert transform["start_ts"] <= child["start_ts"]
            assert child["start_ts"] + child["duration_s"] <= \
                transform["start_ts"] + transform["duration_s"] + 1e-3
    dispatches = by_name["ONNXModel.dispatch"]
    for execute, dispatch in zip(by_name["ProfiledJit.execute"], dispatches):
        assert execute["parent_id"] == dispatch["span_id"]
    # two buckets in order: the second dispatch began after the first ended
    assert dispatches[0]["start_ts"] + dispatches[0]["duration_s"] <= \
        dispatches[1]["start_ts"] + 1e-3
    assert transform["attributes"]["rows"] == 6


def test_disable_makes_serving_untraced(fresh_tracer):
    """tracing.disable() gates the CREATION sites: a served request opens
    no spans, records no trace, and tags no exemplars."""
    from synapseml_tpu.io.serving_v2 import serve_continuous

    reg = obs.MetricsRegistry()
    prev_reg = obs.set_registry(reg)
    tracing.disable()
    try:
        eng = serve_continuous(_SlowEchoReply())
        try:
            with urllib.request.urlopen(eng.server.address + "/",
                                        data=b"x", timeout=15) as r:
                assert r.status == 200
            lat = reg.snapshot()["families"]["smt_serving_latency_seconds"]
            assert lat["series"] and \
                all("exemplars" not in s for s in lat["series"])
        finally:
            eng.stop()
    finally:
        tracing.enable()
        obs.set_registry(prev_reg)
    assert fresh_tracer.snapshot()["traces"] == []


# ---------------------------------------------------------------------------
# tail-based sampling: the flight-recorder contract
# ---------------------------------------------------------------------------

def test_tail_sampling_retains_slow_and_error_under_load():
    tr = tracing.Tracer(capacity=16, sample_rate=0.0,
                        latency_threshold_s=0.05, seed=1)
    # a flood of fast, boring traces: sample_rate 0 -> all dropped
    for _ in range(500):
        tr.record("fast", parent=None, duration_s=0.001)
    tr.record("slow", parent=None, duration_s=0.2)
    err = RuntimeError("boom")
    tr.record("failed", parent=None, duration_s=0.001, error=err)
    for _ in range(500):
        tr.record("fast", parent=None, duration_s=0.001)
    snap = tr.snapshot()
    kept = {t["root"]: t["retained"] for t in snap["traces"]}
    assert kept == {"slow": "slow", "failed": "error"}
    assert snap["stats"]["dropped"] == 1000
    failed = next(t for t in snap["traces"] if t["root"] == "failed")
    assert "RuntimeError: boom" in failed["spans"][0]["attributes"]["error"]


def test_tail_sampling_probabilistic_and_ring_bounded():
    tr = tracing.Tracer(capacity=10, sample_rate=0.5, seed=2,
                        latency_threshold_s=60.0)
    for _ in range(400):
        tr.record("fast", parent=None, duration_s=0.0)
    traces = tr.snapshot()["traces"]
    # ring-bounded: at most the sampled half of capacity survives
    assert 0 < len(traces) <= 5
    assert tr.dropped > 100  # roughly half were coin-flipped away


def test_error_anywhere_in_tree_retains_trace(fresh_tracer):
    tr = tracing.Tracer(capacity=8, sample_rate=0.0,
                        latency_threshold_s=60.0)
    root = tr.begin_span("root", parent=None)
    tr.record("inner", parent=root, duration_s=0.0,
              error=ValueError("inner failure"))
    root.end()  # root itself succeeded fast
    traces = tr.snapshot()["traces"]
    assert len(traces) == 1 and traces[0]["retained"] == "error"


def test_late_spans_attach_to_finalized_trace():
    """A request that 504s finalizes its root while the pipeline is still
    running; the pipeline/stage spans arriving later must still land in
    the retained trace — that trace is the one explaining the timeout."""
    tr = tracing.Tracer(capacity=8, sample_rate=0.0,
                        latency_threshold_s=60.0)
    root = tr.begin_span("request", parent=None)
    pipe = tr.begin_span("pipeline", parent=root)
    root.end(error="serving engine timed out")  # 504 path ends root first
    tr.record("Stage.transform", parent=pipe, duration_s=0.01)
    pipe.end()
    traces = tr.snapshot()["traces"]
    assert len(traces) == 1 and traces[0]["retained"] == "error"
    assert sorted(s["name"] for s in traces[0]["spans"]) == \
        ["Stage.transform", "pipeline", "request"]
    assert tr.snapshot()["stats"]["active"] == 0  # no orphan fragment


def test_late_spans_of_dropped_traces_do_not_leak():
    tr = tracing.Tracer(capacity=8, sample_rate=0.0,
                        latency_threshold_s=60.0)
    root = tr.begin_span("request", parent=None)
    pipe = tr.begin_span("pipeline", parent=root)
    root.end()   # fast + clean -> tail-dropped
    pipe.end()   # late span of a dropped trace: swallowed, not leaked
    snap = tr.snapshot()
    assert snap["traces"] == [] and snap["stats"]["active"] == 0


def test_lifetime_spans_never_retained_as_slow():
    """Spans measuring a LIFETIME (TcpForwarder relay connections) are
    exempt from the slow threshold — an hours-long healthy tunnel must not
    churn real slow/error request traces out of the retained ring."""
    tr = tracing.Tracer(capacity=8, sample_rate=0.0,
                        latency_threshold_s=0.01)
    sp = tr.begin_span("tcp.relay", parent=None)
    sp.slow_exempt = True
    sp._t0 -= int(0.5e9)  # backdate: a 500ms connection lifetime
    sp.end()
    snap = tr.snapshot()
    assert snap["traces"] == [] and snap["stats"]["dropped"] == 1
    # errors on a lifetime span still retain (a relay that blew up)
    sp2 = tr.begin_span("tcp.relay", parent=None)
    sp2.slow_exempt = True
    sp2.end(error=OSError("reset"))
    assert tr.snapshot()["traces"][0]["retained"] == "error"


def test_merge_traces_root_pick_is_order_independent():
    """The stitched headline belongs to the fragment holding the true
    (parentless) root, whichever payload order the merger sees — even when
    a worker fragment OUTLIVES the router's (pipeline running past a
    router timeout)."""
    router = {"traces": [{"trace_id": "t1", "root": "route",
                          "duration_s": 2.0,
                          "spans": [{"trace_id": "t1", "span_id": "r1",
                                     "parent_id": None, "name": "route",
                                     "start_ts": 1.0, "duration_s": 2.0}]}]}
    worker = {"traces": [{"trace_id": "t1", "root": "request",
                          "duration_s": 5.0,
                          "spans": [{"trace_id": "t1", "span_id": "w1",
                                     "parent_id": "r1", "name": "request",
                                     "start_ts": 1.1, "duration_s": 5.0}]}]}
    for payloads in ([router, worker], [worker, router]):
        t = merge_traces(payloads)["traces"][0]
        assert t["root"] == "route" and t["duration_s"] == 2.0, payloads


def test_second_local_root_joins_entry_no_double_sampling():
    """In-process fleets (router + worker sharing one tracer) finalize the
    same trace from TWO local roots; the second must join the existing
    entry, not re-run the retention decision — a sample_rate<1 re-flip
    would half-stitch the trace (route-only or worker-only)."""
    tr = tracing.Tracer(capacity=8, sample_rate=0.0,
                        latency_threshold_s=60.0)
    route = tr.begin_span("route", parent=None)
    request = tr.begin_span(
        "request",
        parent=tracing.SpanContext(route.trace_id, route.span_id))
    request.end(error="HTTP 500")  # worker root: retained (error)
    route.end()  # router root: fast+clean — a 2nd decision would drop it
    traces = tr.snapshot()["traces"]
    assert len(traces) == 1
    assert sorted(s["name"] for s in traces[0]["spans"]) == \
        ["request", "route"]
    assert traces[0]["retained"] == "error"
    assert traces[0]["root"] == "route"  # outermost root owns the headline
    assert tr.snapshot()["stats"]["active"] == 0


def test_retention_upgrade_moves_entry_to_protected_ring():
    """When a later local root upgrades a sampled trace to error/slow, the
    entry must MOVE to the protected ring — relabeling alone would leave
    the error trace to be churned out by fast sampled traffic."""
    tr = tracing.Tracer(capacity=8, sample_rate=1.0,
                        latency_threshold_s=60.0)
    route = tr.begin_span("route", parent=None)
    request = tr.begin_span(
        "request",
        parent=tracing.SpanContext(route.trace_id, route.span_id))
    request.end()                 # clean worker root -> sampled ring
    route.end(error="HTTP 504")   # router root errors -> upgrade
    for _ in range(20):           # flood the sampled ring
        tr.record("fast", parent=None, duration_s=0.0)
    traces = {t["trace_id"]: t for t in tr.snapshot()["traces"]}
    assert route.trace_id in traces, sorted(traces)
    assert traces[route.trace_id]["retained"] == "error"


def test_exemplar_hook_gated_on_disable(fresh_tracer):
    reg = obs.MetricsRegistry()
    h = reg.histogram("h", "h", buckets=(1.0,))
    with tracing.start_span("r", parent=None):
        tracing.disable()
        try:
            h.observe(0.5)  # disabled: no exemplar even with a live span
        finally:
            tracing.enable()
        h.observe(2.0)      # enabled again: this one tags its bucket
    exs = reg.snapshot()["families"]["h"]["series"][0]["exemplars"]
    assert list(exs) == ["1"] and exs["1"][1] == 2.0


def test_no_dangling_exemplars_when_trace_sampled_out():
    """With sample_rate<1, /metrics must not point at traces the tail
    sampler dropped: respond() checks retention before stamping."""
    from synapseml_tpu.io.serving_v2 import serve_continuous

    tr = tracing.Tracer(capacity=16, sample_rate=0.0,
                        latency_threshold_s=60.0)
    prev_tr = tracing.set_tracer(tr)
    reg = obs.MetricsRegistry()
    prev_reg = obs.set_registry(reg)
    try:
        eng = serve_continuous(_SlowEchoReply())
        try:
            with urllib.request.urlopen(eng.server.address + "/",
                                        data=b"x", timeout=15) as r:
                assert r.status == 200
            lat = reg.snapshot()["families"]["smt_serving_latency_seconds"]
            assert lat["series"] and \
                all("exemplars" not in s for s in lat["series"])
        finally:
            eng.stop()
    finally:
        obs.set_registry(prev_reg)
        tracing.set_tracer(prev_tr)


def test_span_cap_truncates_runaway_traces():
    tr = tracing.Tracer(capacity=8, sample_rate=1.0, max_spans_per_trace=10,
                        latency_threshold_s=60.0)
    root = tr.begin_span("root", parent=None)
    for i in range(50):
        tr.record(f"s{i}", parent=root, duration_s=0.0)
    root.end()
    t = tr.snapshot()["traces"][0]
    assert len(t["spans"]) == 11  # 10 children kept + the root
    assert t["truncated_spans"] == 40


# ---------------------------------------------------------------------------
# exemplars: /metrics buckets -> /traces
# ---------------------------------------------------------------------------

def test_histogram_exemplars_tag_active_trace(fresh_tracer):
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat", "l", buckets=(0.1, 1.0))
    h.observe(0.05)  # no active trace: no exemplar
    with tracing.start_span("req", parent=None) as sp:
        h.observe(0.5)
        tid = sp.trace_id
    snap = reg.snapshot()
    s = snap["families"]["lat"]["series"][0]
    assert s["exemplars"] == {"1": [tid, 0.5, s["exemplars"]["1"][2]]}
    # explicit exemplar (the respond() path passes the id by hand)
    h.observe(5.0, exemplar="deadbeef" * 4)
    s2 = reg.snapshot()["families"]["lat"]["series"][0]
    assert s2["exemplars"]["2"][0] == "deadbeef" * 4


def test_exemplars_survive_fleet_merge(fresh_tracer):
    a, b = obs.MetricsRegistry(), obs.MetricsRegistry()
    ha = a.histogram("lat", "l", ("server",)).labels("w0")
    hb = b.histogram("lat", "l", ("server",)).labels("w0")
    ha.observe(0.5, exemplar="a" * 32)
    hb.observe(0.5, exemplar="b" * 32)  # same bucket, later wall clock
    merged = obs.merge_snapshots([a.snapshot(), b.snapshot()])
    s = merged["families"]["lat"]["series"][0]
    # same bucket from two workers: the later wall-clock exemplar wins
    assert s["exemplars"][list(s["exemplars"])[0]][0] == "b" * 32
    # and the merged snapshot still JSON-round-trips
    rt = json.loads(json.dumps(merged))
    assert obs.histogram_quantile(rt, "lat", 0.5) is not None


# ---------------------------------------------------------------------------
# merge_traces stitching
# ---------------------------------------------------------------------------

def test_merge_traces_stitches_fragments_by_trace_id():
    router = {"traces": [{"trace_id": "t1", "root": "route",
                          "duration_s": 1.0, "retained": "sampled",
                          "spans": [{"trace_id": "t1", "span_id": "r1",
                                     "parent_id": None, "name": "route",
                                     "start_ts": 10.0, "duration_s": 1.0}]}],
              "stats": {"dropped": 1}}
    worker = {"traces": [{"trace_id": "t1", "root": "request",
                          "duration_s": 0.4, "retained": "error",
                          "spans": [{"trace_id": "t1", "span_id": "w1",
                                     "parent_id": "r1", "name": "request",
                                     "start_ts": 10.2, "duration_s": 0.4},
                                    # duplicate of the router's span (an
                                    # in-process fleet shares the tracer)
                                    {"trace_id": "t1", "span_id": "r1",
                                     "parent_id": None, "name": "route",
                                     "start_ts": 10.0, "duration_s": 1.0}]}],
              "stats": {"dropped": 2}}
    out = merge_traces([router, worker])
    assert len(out["traces"]) == 1
    t = out["traces"][0]
    assert [s["span_id"] for s in t["spans"]] == ["r1", "w1"]  # deduped,
    assert t["root"] == "route"          # sorted by start; outermost root
    assert t["retained"] == "error"      # strongest retention reason
    assert out["stats"]["dropped"] == 3


# ---------------------------------------------------------------------------
# end-to-end: cross-process fleet produces ONE stitched trace
# ---------------------------------------------------------------------------

class _SlowEchoReply(Transformer):  # in-process tests only
    def _transform(self, table):
        reqs = table["request"]
        out = np.empty(len(reqs), dtype=object)
        for i, r in enumerate(reqs):
            out[i] = string_to_response((r.entity or b"").decode())
        return table.with_column("reply", out)


@pytest.fixture
def fleet(fresh_tracer):
    sys.path.insert(0, _REPO)
    from synapseml_tpu.io.serving_v2 import ProcessServingFleet
    from tests.serving_fault_stage import PidEchoReply

    f = ProcessServingFleet(PidEchoReply(), n_workers=2,
                            import_modules=["tests.serving_fault_stage"],
                            reply_timeout=15.0,
                            trace_knobs={"sample_rate": 1.0,
                                         "slow_ms": 60_000})
    try:
        yield f
    finally:
        f.stop()


def test_process_fleet_stitches_one_trace_across_processes(fleet):
    """THE acceptance test: client traceparent -> router -> worker process
    -> pipeline -> stage spans, reassembled at the front door's /traces
    into a single trace with consistent parentage and nested timing."""
    tid = tracing.new_trace_id()
    client_span = "c0ffee00c0ffee00"
    req = urllib.request.Request(
        fleet.address + "/", data=b"ping", method="POST",
        headers={"traceparent": f"00-{tid}-{client_span}-01"})
    with urllib.request.urlopen(req, timeout=15) as r:
        assert r.status == 200
    payload = json.loads(urllib.request.urlopen(
        fleet.address + "/traces", timeout=15).read().decode())
    traces = {t["trace_id"]: t for t in payload["traces"]}
    assert tid in traces, sorted(traces)
    spans = traces[tid]["spans"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], s)
    need = {"route", "forward", "request", "queue_wait", "pipeline",
            "PidEchoReply.transform"}
    assert need <= set(by_name), sorted(by_name)
    route, fwd = by_name["route"], by_name["forward"]
    request, pipe = by_name["request"], by_name["pipeline"]
    stage = by_name["PidEchoReply.transform"]
    # parentage: client -> route -> forward -> (worker) request -> pipeline
    # -> stage; the worker fragment stitched purely by trace id + the
    # traceparent the router injected
    assert route["parent_id"] == client_span
    assert fwd["parent_id"] == route["span_id"]
    assert request["parent_id"] == fwd["span_id"]
    assert by_name["queue_wait"]["parent_id"] == request["span_id"]
    assert pipe["parent_id"] == request["span_id"]
    assert stage["parent_id"] == pipe["span_id"]
    # timing consistency: children nest inside parents (cross-process wall
    # clocks on one host; generous epsilon for clock granularity)
    assert fwd["duration_s"] <= route["duration_s"] + 1e-3
    assert request["duration_s"] <= fwd["duration_s"] + 1e-3
    assert pipe["duration_s"] <= request["duration_s"] + 1e-3
    assert stage["duration_s"] <= pipe["duration_s"] + 1e-3
    assert route["status"] == "OK" and route["attributes"]["status"] == 200
    # every span of the tree carries the SAME trace id
    assert {s["trace_id"] for s in spans} == {tid}


def test_process_fleet_exemplars_resolve_to_traces(fleet):
    """Fleet /metrics histogram buckets touched by traced traffic carry
    exemplar trace ids that resolve in the stitched /traces view."""
    for _ in range(4):
        with urllib.request.urlopen(fleet.address + "/", data=b"x",
                                    timeout=15) as r:
            assert r.status == 200
    snap = json.loads(urllib.request.urlopen(
        fleet.address + "/metrics?format=json", timeout=15).read().decode())
    trace_ids = {t["trace_id"] for t in fleet.traces_snapshot()["traces"]}
    worker_labels = {a[len("http://"):] for a in fleet.addresses}
    lat = snap["families"]["smt_serving_latency_seconds"]["series"]
    mine = [s for s in lat if s["labels"][0] in worker_labels]
    assert mine, lat
    checked = 0
    for s in mine:
        for i, c in enumerate(s["counts"]):
            if c > 0:
                ex = (s.get("exemplars") or {}).get(str(i))
                assert ex is not None, (s["labels"], i)
                assert ex[0] in trace_ids, (ex[0], sorted(trace_ids)[:4])
                checked += 1
    assert checked > 0
    # stage-duration buckets from the worker pipeline resolve too
    dur = snap["families"]["smt_stage_duration_seconds"]["series"]
    stage_series = [s for s in dur if s["labels"][0] == "PidEchoReply"]
    assert any((s.get("exemplars") or {}) for s in stage_series)
    for s in stage_series:
        for ex in (s.get("exemplars") or {}).values():
            assert ex[0] in trace_ids


def test_router_tracing_disabled_still_propagates_client_context(fleet):
    """A router with tracing disabled must forward the CLIENT's
    traceparent untouched — the worker processes (tracing still on)
    continue the client's trace instead of rooting fresh ones."""
    tid = tracing.new_trace_id()
    client_span = "3" * 16
    tracing.disable()
    try:
        req = urllib.request.Request(
            fleet.address + "/", data=b"x", method="POST",
            headers={"traceparent": f"00-{tid}-{client_span}-01"})
        with urllib.request.urlopen(req, timeout=15) as r:
            assert r.status == 200
    finally:
        tracing.enable()
    payload = fleet.traces_snapshot()  # router recorded nothing; workers did
    mine = [t for t in payload["traces"] if t["trace_id"] == tid]
    assert len(mine) == 1, sorted(t["trace_id"] for t in payload["traces"])
    request = next(s for s in mine[0]["spans"] if s["name"] == "request")
    assert request["parent_id"] == client_span


def test_trace_dump_renders_fleet_waterfall(fleet):
    """tools/trace_dump.py against the live front door: waterfall contains
    the full routed span tree."""
    import subprocess

    with urllib.request.urlopen(fleet.address + "/", data=b"x",
                                timeout=15) as r:
        assert r.status == 200
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "trace_dump.py"),
         fleet.address, "--top", "3"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for needle in ("route", "forward", "request", "pipeline",
                   "PidEchoReply.transform"):
        assert needle in out.stdout, (needle, out.stdout)


def test_continuous_server_traces_endpoint(fresh_tracer):
    """Single in-process server: /traces works and micro-batch fusion
    attributes fused requests to the leader's trace."""
    from synapseml_tpu.io.serving_v2 import serve_continuous

    eng = serve_continuous(_SlowEchoReply())
    try:
        for _ in range(3):
            with urllib.request.urlopen(eng.server.address + "/",
                                        data=b"x", timeout=15) as r:
                assert r.status == 200
        payload = json.loads(urllib.request.urlopen(
            eng.server.address + "/traces", timeout=15).read().decode())
        assert payload["traces"]
        for t in payload["traces"]:
            names = [s["name"] for s in t["spans"]]
            assert "request" in names
    finally:
        eng.stop()
