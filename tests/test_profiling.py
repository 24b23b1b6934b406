"""Device-side performance observability (ISSUE 10): compile/HBM/FLOPs
accounting, gauge merge modes, Chrome-trace timeline export, and the
perf-diff bisection toolkit.

Acceptance contract: every XLA compile through a profiled entry point is
timed and cause-attributed; stage spans report the FLOPs they ran; peak
gauges merge as max and live gauges as sum across workers; the timeline
export is schema-valid Chrome trace JSON and a ``ProcessServingFleet``
stitches into one timeline with >= 2 process tracks; and
``tools/perf_diff.py`` reproduces a written diagnosis of a flash regression
of the r4->r5 shape from two small artefacts written under ``tmp_path``.
"""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from synapseml_tpu.observability import (merge_snapshots, profiling, spans,
                                         tracing)
from synapseml_tpu.observability.metrics import MetricsRegistry, set_registry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")
sys.path.insert(0, _TOOLS) if _TOOLS not in sys.path else None

import perf_diff  # noqa: E402
import perf_timeline  # noqa: E402


@pytest.fixture
def fresh_registry():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


def _series(snap, family):
    return {tuple(s["labels"]): s
            for s in snap["families"][family]["series"]}


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

def test_profiled_jit_records_compile_and_recompile_causes(fresh_registry):
    pj = profiling.profiled_jit(lambda x: (x * 2.0).sum(), name="t.fn")
    x32 = np.ones((8,), np.float32)
    assert float(pj(x32)) == 16.0
    assert float(pj(x32)) == 16.0          # warm: no second compile
    pj(np.ones((16,), np.float32))         # shape change
    pj(np.ones((16,), np.int32))           # dtype change

    snap = fresh_registry.snapshot()
    comp = _series(snap, "smt_compile_seconds")
    assert comp[("t.fn", "cpu")]["count"] == 3
    assert comp[("t.fn", "cpu")]["sum"] > 0
    rec = _series(snap, "smt_recompiles_total")
    assert rec[("t.fn", "first")]["value"] == 1
    assert rec[("t.fn", "shape")]["value"] == 1
    assert rec[("t.fn", "dtype")]["value"] == 1


def test_profiled_jit_static_args_recompile_as_static(fresh_registry):
    pj = profiling.profiled_jit(lambda x, n: x * n, name="t.static",
                                static_argnames=("n",))
    x = np.ones((4,), np.float32)
    assert float(pj(x, n=3).sum()) == 12.0
    assert float(pj(x, n=5).sum()) == 20.0
    rec = _series(fresh_registry.snapshot(), "smt_recompiles_total")
    assert rec[("t.static", "first")]["value"] == 1
    assert rec[("t.static", "static")]["value"] == 1


def test_profiled_jit_inside_outer_jit_falls_back_cleanly(fresh_registry):
    """Called on tracers (inside an enclosing jit) the wrapper must inline
    like plain jit and record NO compile of its own — the compilation
    belongs to the outer program."""
    import jax
    import jax.numpy as jnp

    pj = profiling.profiled_jit(lambda x: x + 1.0, name="t.inner")
    out = jax.jit(lambda y: pj(y) * 2)(jnp.zeros((4,)))
    assert float(out.sum()) == 8.0
    assert "smt_compile_seconds" not in fresh_registry.snapshot()["families"]


def test_profiled_jit_user_error_propagates(fresh_registry):
    pj = profiling.profiled_jit(lambda x: x.reshape((3, 3)), name="t.bad")
    with pytest.raises(Exception):  # shape error from the user's fn
        pj(np.ones((8,), np.float32))


def test_compile_event_lands_in_telemetry_ring(fresh_registry):
    from synapseml_tpu.core import telemetry

    telemetry.clear_events()
    pj = profiling.profiled_jit(lambda x: x * 3.0, name="t.evt")
    pj(np.ones((4,), np.float32))
    evts = [e for e in telemetry.recent_events()
            if e.get("method") == "xla_compile" and e.get("uid") == "t.evt"]
    assert evts and evts[0]["cause"] == "first"
    assert "pid" in evts[0] and "duration_s" in evts[0]


# ---------------------------------------------------------------------------
# what a load was (ISSUE 36): trace and compile apart, hit or miss, the
# program's bytes, an entry point that gave the accounting up
# ---------------------------------------------------------------------------

@pytest.fixture
def own_compile_cache(tmp_path):
    """jax's persistent cache on, in a directory of this test's own, with
    every entry kept; the session's settings (``conftest.py``: off) are put
    back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    wanted = {"jax_enable_compilation_cache": True,
              "jax_compilation_cache_dir": str(tmp_path),
              "jax_persistent_cache_min_compile_time_secs": 0.0,
              "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {k: getattr(jax.config, k) for k in wanted}
    for k, v in wanted.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        yield tmp_path
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _same_function():
    """A new function object of one source: jax traces it anew, and XLA's
    program for it is the one the cache holds."""
    def cached_fn(x):
        return (x * 3.0).sum()
    return cached_fn


def test_the_cache_counter_says_miss_then_hit(fresh_registry,
                                              own_compile_cache):
    import jax

    x = np.ones((8,), np.float32)
    first = profiling.profiled_jit(_same_function(), name="t.cache")
    assert float(first(x)) == 24.0
    assert {k: s["value"] for k, s in _series(
        fresh_registry.snapshot(), "smt_compile_cache_total").items()} == \
        {("t.cache", "miss"): 1}
    # a compile that is not a ProfiledJit's, on the same thread: not counted
    assert float(jax.jit(lambda v: v + 1.0)(x).sum()) == 16.0
    second = profiling.profiled_jit(_same_function(), name="t.cache")
    assert float(second(x)) == 24.0
    assert float(second(x)) == 24.0  # warm: no load at all
    snap = fresh_registry.snapshot()
    assert {k: s["value"] for k, s in
            _series(snap, "smt_compile_cache_total").items()} == \
        {("t.cache", "miss"): 1, ("t.cache", "hit"): 1}
    # a load is one sample of the compile's seconds, hit or miss, and one
    # of each of its two spans
    assert _series(snap, "smt_compile_seconds")[("t.cache", "cpu")][
        "count"] == 2
    spans_ = _series(snap, "smt_stage_duration_seconds")
    for method in ("lower", "compile"):
        assert spans_[("ProfiledJit", method, "0")]["count"] == 2
    lowered_and_compiled = sum(
        spans_[("ProfiledJit", m, "0")]["sum"] for m in ("lower", "compile"))
    whole = _series(snap, "smt_compile_seconds")[("t.cache", "cpu")]["sum"]
    assert lowered_and_compiled <= whole < lowered_and_compiled + 0.05


def test_with_no_cache_a_compile_counts_neither_hit_nor_miss(fresh_registry):
    """The session's cache is off: the family is there (a healthy zero is
    a family with no series) and holds nothing."""
    pj = profiling.profiled_jit(lambda x: x - 1.0, name="t.nocache")
    pj(np.ones((4,), np.float32))
    families = fresh_registry.snapshot()["families"]
    assert families["smt_compile_cache_total"]["series"] == []
    assert families["smt_profiled_jit_fallback_total"]["series"] == []


def test_a_compiled_program_says_what_it_holds(fresh_registry):
    pj = profiling.profiled_jit(lambda a, b: a @ b, name="t.mem")
    pj(np.ones((16, 32), np.float32), np.ones((32, 8), np.float32))
    held = {k: s["value"] for k, s in _series(
        fresh_registry.snapshot(), "smt_program_memory_bytes").items()}
    assert held[("t.mem", "arguments")] == (16 * 32 + 32 * 8) * 4
    assert held[("t.mem", "outputs")] == 16 * 8 * 4
    assert set(k for _, k in held) == {"arguments", "outputs",
                                       "temporaries", "code"}
    # a larger signature of the same entry point: the gauge keeps the larger
    pj(np.ones((32, 32), np.float32), np.ones((32, 8), np.float32))
    held = _series(fresh_registry.snapshot(), "smt_program_memory_bytes")
    assert held[("t.mem", "arguments")]["value"] == (32 * 32 + 32 * 8) * 4


def test_a_failed_aot_compile_counts_one_fallback_and_still_answers(
        fresh_registry, monkeypatch, caplog):
    """The AOT machinery breaks for this function (here: ``compile`` is made
    to raise); plain ``jax.jit`` answers, the counter says so once, and no
    compile of it is recorded, now or on a new shape."""
    import jax

    def broken(self, *a, **kw):
        raise RuntimeError("no AOT for you")

    monkeypatch.setattr(jax.stages.Lowered, "compile", broken)
    pj = profiling.profiled_jit(lambda x: x * 2.0 + 1.0, name="t.fallback")
    with caplog.at_level("WARNING", logger="synapseml_tpu"):
        out = pj(np.ones((4,), np.float32))
    monkeypatch.undo()
    assert np.asarray(out).tolist() == [3.0] * 4
    assert np.asarray(pj(np.ones((6,), np.float32))).tolist() == [3.0] * 6
    families = fresh_registry.snapshot()["families"]
    assert {tuple(s["labels"]): s["value"] for s in families[
        "smt_profiled_jit_fallback_total"]["series"]} == \
        {("t.fallback", "compile_failed"): 1}
    assert "smt_compile_seconds" not in families
    assert sum("left the profiled path" in r.getMessage()
               for r in caplog.records) == 1


# ---------------------------------------------------------------------------
# per-stage FLOPs / bytes via the span hook (stage spans only)
# ---------------------------------------------------------------------------

class _ProfStage:
    """Anything with a class name: ``stage_span`` labels by it."""


def test_span_attributes_flops_and_mfu(fresh_registry):
    pj = profiling.profiled_jit(lambda a: a @ a.T, name="t.mm")
    x = np.ones((32, 32), np.float32)
    with spans.stage_span(_ProfStage(), "transform") as sp:
        pj(x)
        sp.set_rows(32)
    snap = fresh_registry.snapshot()
    flops = _series(snap, "smt_stage_flops_total")
    assert flops[("_ProfStage", "transform")]["value"] > 0
    nbytes = _series(snap, "smt_stage_bytes_total")
    assert nbytes[("_ProfStage", "transform")]["value"] > 0
    # utilisation is the benchmark's to compute, from device time
    assert "smt_stage_mfu" not in snap["families"]


def test_span_without_profiled_calls_records_no_flops(fresh_registry):
    with spans.stage_span(_ProfStage(), "transform") as sp:
        sp.set_rows(1)
    assert "smt_stage_flops_total" not in fresh_registry.snapshot()["families"]


def test_profiling_disable_detaches_hook(fresh_registry):
    pj = profiling.profiled_jit(lambda a: a * 2, name="t.off")
    x = np.ones((4,), np.float32)
    profiling.disable()
    try:
        with spans.stage_span(_ProfStage(), "transform"):
            pj(x)
        fams = fresh_registry.snapshot()["families"]
        assert "smt_stage_flops_total" not in fams
        assert "smt_compile_seconds" not in fams  # plain-jit path while off
    finally:
        profiling.enable()


# ---------------------------------------------------------------------------
# memory accounting (injected stats: CPU has none — the graceful no-op)
# ---------------------------------------------------------------------------

def test_update_memory_gauges_noop_on_cpu(fresh_registry):
    assert profiling.update_memory_gauges(fresh_registry) is False
    assert "smt_device_hbm_live_bytes" not in \
        fresh_registry.snapshot()["families"]


def test_update_memory_gauges_and_process_watermark(fresh_registry):
    stats = [("tpu:0", {"bytes_in_use": 100, "peak_bytes_in_use": 900}),
             ("tpu:1", {"bytes_in_use": 50, "peak_bytes_in_use": 700})]
    assert profiling.update_memory_gauges(fresh_registry, stats=stats)
    snap = fresh_registry.snapshot()
    live = _series(snap, "smt_device_hbm_live_bytes")
    assert live[("tpu:0",)]["value"] == 100
    peak = _series(snap, "smt_device_hbm_peak_bytes")
    assert peak[("tpu:1",)]["value"] == 700
    proc = _series(snap, "smt_process_hbm_peak_bytes")
    assert proc[()]["value"] == 1600
    # watermark is monotone: a lower later reading must not regress it
    profiling.update_memory_gauges(fresh_registry, stats=[
        ("tpu:0", {"bytes_in_use": 10, "peak_bytes_in_use": 900})])
    snap = fresh_registry.snapshot()
    assert _series(snap, "smt_process_hbm_peak_bytes")[()]["value"] == 1600
    assert _series(snap, "smt_device_hbm_live_bytes")[("tpu:0",)]["value"] == 10


# ---------------------------------------------------------------------------
# gauge merge modes (the merge.py satellite)
# ---------------------------------------------------------------------------

def test_gauge_merge_modes_max_vs_sum():
    a, b = MetricsRegistry(), MetricsRegistry()
    for reg, peak_v, live_v in ((a, 900.0, 100.0), (b, 700.0, 50.0)):
        reg.gauge("hbm_peak", "wm", ("device",),
                  merge="max").labels("tpu:0").set(peak_v)
        reg.gauge("hbm_live", "live", ("device",)).labels("tpu:0").set(live_v)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    peak = {tuple(s["labels"]): s
            for s in merged["families"]["hbm_peak"]["series"]}
    live = {tuple(s["labels"]): s
            for s in merged["families"]["hbm_live"]["series"]}
    assert peak[("tpu:0",)]["value"] == 900.0   # max across workers
    assert live[("tpu:0",)]["value"] == 150.0   # sum across workers
    # the merge mode survives the merge (second-level mergers apply it too)
    assert merged["families"]["hbm_peak"]["merge"] == "max"
    again = merge_snapshots([merged, merged])
    peak2 = {tuple(s["labels"]): s
             for s in again["families"]["hbm_peak"]["series"]}
    assert peak2[("tpu:0",)]["value"] == 900.0
    # JSON round trip (snapshots travel in worker HTTP replies)
    rt = merge_snapshots([json.loads(json.dumps(a.snapshot())),
                          json.loads(json.dumps(b.snapshot()))])
    assert {tuple(s["labels"]): s["value"]
            for s in rt["families"]["hbm_peak"]["series"]} == \
        {("tpu:0",): 900.0}


def test_gauge_merge_mode_is_schema_checked():
    reg = MetricsRegistry()
    reg.gauge("wm", "w", merge="max")
    with pytest.raises(ValueError):
        reg.gauge("wm", "w", merge="sum")
    with pytest.raises(ValueError):
        reg.gauge("other", "o", merge="median")


# ---------------------------------------------------------------------------
# timeline export: golden + schema validity
# ---------------------------------------------------------------------------

_FIXTURE_TRACES = {
    "traces": [{
        "trace_id": "aa" * 16, "root": "route", "duration_s": 0.02,
        "spans": [
            {"trace_id": "aa" * 16, "span_id": "r1", "parent_id": None,
             "name": "route", "start_ts": 100.0, "duration_s": 0.02,
             "status": "OK", "attributes": {"server": "127.0.0.1:1"},
             "pid": 10},
            {"trace_id": "aa" * 16, "span_id": "w1", "parent_id": "r1",
             "name": "request", "start_ts": 100.005, "duration_s": 0.01,
             "status": "OK", "attributes": {"server": "127.0.0.1:2"},
             "pid": 20},
            {"trace_id": "aa" * 16, "span_id": "w2", "parent_id": "w1",
             "name": "Echo.transform", "start_ts": 100.006,
             "duration_s": 0.004, "status": "ERROR",
             "attributes": {"stage": "Echo"}, "pid": 20},
        ],
    }],
    "stats": {"dropped": 0, "active": 0},
}

_FIXTURE_EVENTS = [
    {"uid": "t.fn", "className": "profiling", "method": "xla_compile",
     "ts": 100.001, "pid": 20, "trace_id": "aa" * 16, "duration_s": 0.5},
]


def _check_chrome_schema(events):
    """Chrome-trace schema: every event needs ph/ts/pid/tid; complete
    events need dur >= 0; phases restricted to the ones we emit."""
    assert events, "no events rendered"
    for e in events:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(e), e
        assert e["ph"] in ("X", "i", "M"), e
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0


def test_chrome_trace_golden_from_fixed_fixture():
    events = profiling.chrome_trace_events(_FIXTURE_TRACES, _FIXTURE_EVENTS)
    _check_chrome_schema(events)
    spans_x = [e for e in events if e["ph"] == "X"]
    assert [e["name"] for e in spans_x] == ["route", "request",
                                            "Echo.transform"]
    route = spans_x[0]
    assert route["pid"] == 10 and route["ts"] == 100.0 * 1e6
    assert route["dur"] == pytest.approx(0.02 * 1e6)
    assert route["args"]["trace_id"] == "aa" * 16
    # worker spans land on the worker process's track
    assert spans_x[1]["pid"] == 20 and spans_x[2]["pid"] == 20
    assert spans_x[2]["args"]["status"] == "ERROR"
    # same trace in the same process shares a row (tid)
    assert spans_x[1]["tid"] == spans_x[2]["tid"]
    # the telemetry event renders as an instant on the worker's trace row
    inst = [e for e in events if e["ph"] == "i"]
    assert len(inst) == 1
    assert inst[0]["pid"] == 20 and inst[0]["tid"] == spans_x[1]["tid"]
    assert inst[0]["name"] == "profiling.xla_compile"
    # metadata names both process tracks
    meta = {(e["pid"], e["name"]): e for e in events if e["ph"] == "M"}
    assert meta[(10, "process_name")]["args"]["name"] == "127.0.0.1:1"
    assert meta[(20, "process_name")]["args"]["name"] == "127.0.0.1:2"
    # the whole rendering is JSON-serializable (it is served over HTTP)
    doc = profiling.render_chrome_trace(_FIXTURE_TRACES, _FIXTURE_EVENTS)
    json.dumps(doc)
    assert doc["traceEvents"] and doc["displayTimeUnit"] == "ms"


def test_perf_timeline_cli_renders_saved_payload(tmp_path):
    src = tmp_path / "traces.json"
    src.write_text(json.dumps(_FIXTURE_TRACES))
    out = tmp_path / "timeline.json"
    rc = perf_timeline.main([str(src), "-o", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    _check_chrome_schema(doc["traceEvents"])
    assert {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"} == \
        {10, 20}


# ---------------------------------------------------------------------------
# perf_diff: the bisection toolkit reproduces the r5 flash diagnosis
# ---------------------------------------------------------------------------

# two round artefacts in the driver's format, cut down to the flash lane:
# the older one damaged the way r4 was (``parsed: null``, the JSON line's
# front truncated out of the recorded tail), the newer one intact and 20%
# slower at every curve point, with neither blocks nor operand mode stamped
_FLASH_OLD = {
    "seq_len": 32768, "ms_per_fwd": 30.34, "tflops_nominal": 72.5,
    "curve": {
        "s8192": {"flash_ms": 14.5, "xla_ms": 21.76},
        "s16384": {"flash_ms": 19.49, "xla_ms": 51.47},
        "s32768": {"flash_ms": 30.34, "xla_ms": None}}}
_FLASH_NEW = {
    "seq_len": 32768, "ms_per_fwd": 37.76, "tflops_nominal": 58.2,
    "curve": {
        "s8192": {"flash_ms": 20.96, "xla_ms": 29.51},
        "s16384": {"flash_ms": 24.38, "xla_ms": 49.48},
        "s32768": {"flash_ms": 37.76, "xla_ms": None}}}


def _write_flash_rounds(tmp_path):
    old, new = tmp_path / "BENCH_r04.json", tmp_path / "BENCH_r05.json"
    tail = ('0444.0, "rows": 500000, "ingest_s": 14.48}, '
            '"flash_attention_32k": ' + json.dumps(_FLASH_OLD) + ', '
            '"serving_latency": {"continuous_p50_ms": 0.303}}}\n')
    old.write_text(json.dumps({"n": 4, "rc": 0, "tail": tail,
                               "parsed": None}))
    new.write_text(json.dumps({"n": 5, "rc": 0, "tail": "", "parsed": {
        "value": 11411.28,
        "extra": {"flash_attention_32k": _FLASH_NEW}}}))
    return str(old), str(new)


def test_perf_diff_flags_flash_regression_with_attribution(tmp_path, capsys):
    rc = perf_diff.main(list(_write_flash_rounds(tmp_path)))
    out = capsys.readouterr().out
    assert rc == 1  # a regressed lane fails the exit code (CI-friendly)
    assert "flash_attention_32k" in out and "x0.803" in out
    assert "REGRESSED" in out
    # the written diagnosis: execute-side, harness confound named, control
    # lane consulted
    assert "EXECUTE side" in out
    assert "operands closed-over -> jit-args" in out
    assert "XLA dense baseline" in out
    assert "uniform across the curve" in out


def test_perf_diff_attributes_block_and_operand_changes(tmp_path, capsys):
    """With provenance stamped (r6+ artifacts), a confounded regression is
    self-describing: changed blocks and operand mode are named outright."""
    old = {"extra": {
        "provenance": {"jax": "0.4.36", "jaxlib": "0.4.36",
                       "operand_mode": "closed-over"},
        "flash_attention_32k": {
            "tflops_nominal": 72.5, "operand_mode": "closed-over",
            "compile_warm_s": 3.0,
            "curve": {"s32768": {"flash_ms": 30.3, "blocks": [2048, 512],
                                 "compile_warm_s": 3.0}}}}}
    new = {"extra": {
        "provenance": {"jax": "0.4.37", "jaxlib": "0.4.36",
                       "operand_mode": "jit-args"},
        "flash_attention_32k": {
            "tflops_nominal": 58.2, "operand_mode": "jit-args",
            "compile_warm_s": 9.0,
            "curve": {"s32768": {"flash_ms": 37.8, "blocks": [2048, 1024],
                                 "compile_warm_s": 9.0}}}}}
    po, pn = tmp_path / "old.json", tmp_path / "new.json"
    po.write_text(json.dumps(old))
    pn.write_text(json.dumps(new))
    rc = perf_diff.main([str(po), str(pn)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "operand-passing mode changed 'closed-over' -> 'jit-args'" in out
    assert "blocks changed" in out and "[2048, 512] -> [2048, 1024]" in out
    assert "COMPILE-side" in out  # compile+warm tripled
    assert "jax changed 0.4.36 -> 0.4.37" in out


def test_perf_diff_json_mode_and_clean_exit(tmp_path, capsys):
    flat = {"extra": {"gbdt_adult_scale": {"train_rows_per_sec": 100.0}}}
    p = tmp_path / "a.json"
    p.write_text(json.dumps(flat))
    rc = perf_diff.main([str(p), str(p), "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert payload["lanes"][0]["status"] == "flat"
    assert payload["lanes"][0]["ratio"] == 1.0


def test_perf_diff_recovers_damaged_artifact_tail(tmp_path):
    extra = perf_diff.load_artifact(_write_flash_rounds(tmp_path)[0])
    assert extra.get("_tail_recovered") is True
    assert extra["flash_attention_32k"]["tflops_nominal"] == 72.5


# ---------------------------------------------------------------------------
# serving integration: /timeline on a live server
# ---------------------------------------------------------------------------

class _TlEcho:  # built inline to avoid registry pollution
    pass


def test_serving_timeline_endpoint_is_valid_chrome_trace():
    from synapseml_tpu.core import Table, Transformer
    from synapseml_tpu.io.serving import (MicroBatchServingEngine,
                                          ServingServer, string_to_response)

    class _TimelineEcho(Transformer):
        def _transform(self, table):
            reqs = table["request"]
            out = np.empty(len(reqs), dtype=object)
            for i, r in enumerate(reqs):
                out[i] = string_to_response((r.entity or b"").decode())
            return table.with_column("reply", out)

    tr = tracing.Tracer(capacity=64, sample_rate=1.0,
                        latency_threshold_s=60.0)
    prev = tracing.set_tracer(tr)
    srv = ServingServer(port=0)
    eng = MicroBatchServingEngine(srv, _TimelineEcho(), interval=0.005).start()
    try:
        with urllib.request.urlopen(srv.address, data=b"x", timeout=10) as r:
            assert r.status == 200
        doc = json.loads(urllib.request.urlopen(
            srv.address + "/timeline", timeout=10).read().decode())
        _check_chrome_schema(doc["traceEvents"])
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert {"request", "pipeline", "_TimelineEcho.transform"} <= names
    finally:
        eng.stop()
        tracing.set_tracer(prev)


# ---------------------------------------------------------------------------
# e2e: a cross-process fleet stitches into ONE timeline with >= 2 process
# tracks (the workers are real OS processes with distinct pids)
# ---------------------------------------------------------------------------

def test_process_fleet_timeline_has_per_process_tracks():
    sys.path.insert(0, _REPO)
    from synapseml_tpu.io.serving_v2 import ProcessServingFleet
    from tests.serving_fault_stage import PidEchoReply

    tr = tracing.Tracer(capacity=128, sample_rate=1.0,
                        latency_threshold_s=60.0)
    prev = tracing.set_tracer(tr)
    fleet = ProcessServingFleet(PidEchoReply(), n_workers=2,
                                import_modules=["tests.serving_fault_stage"],
                                reply_timeout=15.0,
                                trace_knobs={"sample_rate": 1.0,
                                             "slow_ms": 60_000})
    try:
        for _ in range(6):  # round-robin touches both workers
            with urllib.request.urlopen(fleet.address + "/", data=b"t",
                                        timeout=15) as r:
                assert r.status == 200
        doc = json.loads(urllib.request.urlopen(
            fleet.address + "/timeline", timeout=15).read().decode())
        _check_chrome_schema(doc["traceEvents"])
        span_events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        pids = {e["pid"] for e in span_events}
        # router process + 2 worker processes; >= 2 proves cross-process
        # stitching put each OS process on its own track
        assert len(pids) >= 2, sorted(pids)
        worker_pids = {p.pid for p in fleet.procs}
        assert len(worker_pids & pids) >= 2, (sorted(pids),
                                              sorted(worker_pids))
        # one trace's spans spread across router AND worker tracks
        by_trace = {}
        for e in span_events:
            by_trace.setdefault(e["args"]["trace_id"], set()).add(e["pid"])
        assert any(len(ps) >= 2 for ps in by_trace.values()), by_trace
        # python -m synapseml_tpu check: the fleet timeline matches what
        # the CLI renders from the same /traces payload
        traces = json.loads(urllib.request.urlopen(
            fleet.address + "/traces", timeout=15).read().decode())
        cli_events = profiling.chrome_trace_events(traces)
        assert {e["pid"] for e in cli_events if e["ph"] == "X"} == pids
    finally:
        fleet.stop()
        tracing.set_tracer(prev)


def test_perf_timeline_cli_jax_free_on_artifacts(tmp_path):
    """Both CLIs must run jax-free on saved artifacts (the CI/tooling
    satellite) — asserted in a SUBPROCESS immune to this session."""
    src = tmp_path / "traces.json"
    src.write_text(json.dumps(_FIXTURE_TRACES))
    old, new = _write_flash_rounds(tmp_path)
    code = (
        "import sys\n"
        f"sys.path.insert(0, {_TOOLS!r})\n"
        "import perf_timeline, perf_diff\n"
        f"perf_timeline.main([{str(src)!r}])\n"
        f"perf_diff.main([{old!r}, {new!r}])\n"
        "bad = [m for m in sys.modules if m == 'jax' "
        "or m.startswith('jax.')]\n"
        "assert not bad, bad\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
