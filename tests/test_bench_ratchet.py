"""Round-over-round bench ratchet recovery.

The driver records only the tail of bench stdout; r4 proved a multi-KB
embedded traceback can truncate the JSON line's front, leaving
``parsed: null``. These tests pin the armored loader: per-config objects are
brace-matched out of the damaged tail, and configs whose fragments fell
outside the window are reconstructed from the artifact's own
``vs_prev_round`` ratios against the previous round's intact numbers.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402

# a faithful miniature of the r4 failure: front of the JSON line truncated
# away (mid-way through one config), later configs + vs_prev_round intact
_DAMAGED_TAIL = (
    '0444.0, "rows": 500000, "ingest_s": 14.48}, '
    '"vit_to_gbdt_pipeline": {"error": "TracerArrayConversionError: '
    'traced array with shape int8[768]"}, '
    '"flash_attention_32k": {"seq_len": 32768, "ms_per_fwd": 30.34, '
    '"tflops_nominal": 72.5, "mfu_vs_bf16_peak": 0.3679}, '
    '"serving_latency": {"continuous_p50_ms": 0.303, '
    '"microbatch_p99_ms": 1.193}, '
    '"vs_prev_round": {"round": 3, "per_config": {"resnet50_onnx": 0.984, '
    '"gbdt_adult_scale": 0.966, "bert_base_onnx": 1.001, '
    '"gbdt_higgs_scale": 1.002, "flash_attention_32k": 1.608}}}}\n'
)

_R3_PARSED = {
    "metric": "resnet50_onnx_images_per_sec_per_chip",
    "value": 10273.0,
    "extra": {
        "resnet50_onnx": {"images_per_sec_per_chip": 10273.0, "mfu": 0.43},
        "gbdt_adult_scale": {"train_rows_per_sec": 1137000.0},
        "bert_base_onnx": {"sequences_per_sec_per_chip": 1650.0},
        "gbdt_higgs_scale": {"train_rows_per_sec": 7900000.0},
        "vit_to_gbdt_pipeline": {"images_per_sec_end_to_end": 1984.0},
        "flash_attention_32k": {"tflops_nominal": 45.1},
    },
}


def _write_rounds(tmp_path):
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"n": 3, "rc": 0, "tail": "", "parsed": _R3_PARSED}))
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(
        {"n": 4, "rc": 0, "tail": _DAMAGED_TAIL, "parsed": None}))


def test_recover_extra_from_tail_brace_matching():
    extra = bench._recover_extra_from_tail(_DAMAGED_TAIL)
    # intact fragments recovered verbatim
    assert extra["flash_attention_32k"]["tflops_nominal"] == 72.5
    assert extra["serving_latency"]["continuous_p50_ms"] == 0.303
    assert extra["vit_to_gbdt_pipeline"] == {
        "error": "TracerArrayConversionError: traced array with shape int8[768]"}
    assert extra["vs_prev_round"]["round"] == 3
    # the front-truncated config is (correctly) absent, not mangled
    assert "gbdt_sparse_hashed" not in extra


def test_load_prev_round_survives_damaged_artifact(tmp_path):
    _write_rounds(tmp_path)
    got = bench._load_prev_round(here=str(tmp_path))
    assert got is not None
    rnd, headline, extra = got
    assert rnd == 4
    # resnet's fragment fell outside the tail window -> reconstructed from
    # ratio x r3 absolute: 0.984 * 10273
    assert abs(extra["resnet50_onnx"]["images_per_sec_per_chip"]
               - 0.984 * 10273.0) < 0.5
    assert extra["resnet50_onnx"]["reconstructed_from_ratio"] is True
    assert headline == extra["resnet50_onnx"]["images_per_sec_per_chip"]
    assert abs(extra["gbdt_adult_scale"]["train_rows_per_sec"]
               - 0.966 * 1137000.0) < 1.0
    # configs recovered directly from the tail are NOT overwritten by ratios
    assert extra["flash_attention_32k"]["tflops_nominal"] == 72.5
    assert "reconstructed_from_ratio" not in extra["flash_attention_32k"]
    # downstream: _vs_prev computes real per-config deltas against this
    cur = {"resnet50_onnx": {"images_per_sec_per_chip": 10300.0},
           "vit_to_gbdt_pipeline": {"images_per_sec_end_to_end": 2100.0}}
    deltas = bench._vs_prev(cur, got)
    assert "resnet50_onnx" in deltas
    # vit had no number in r4 (error) -> no ratio, correctly absent
    assert "vit_to_gbdt_pipeline" not in deltas


def test_load_prev_round_falls_back_past_unrecoverable_round(tmp_path):
    """A round whose tail holds NO complete fragment must not sever the
    chain — the loader walks back to the newest intact round."""
    _write_rounds(tmp_path)
    (tmp_path / "BENCH_r05.json").write_text(json.dumps(
        {"n": 5, "rc": 1, "tail": "Traceback (most recent call last):\n ...",
         "parsed": None}))
    rnd, headline, extra = bench._load_prev_round(here=str(tmp_path))
    assert rnd == 4  # r5 unrecoverable -> the recovered r4, not None
    assert isinstance(headline, (int, float))


def test_load_prev_round_intact_artifact_unchanged(tmp_path):
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"n": 3, "rc": 0, "tail": "", "parsed": _R3_PARSED}))
    rnd, headline, extra = bench._load_prev_round(here=str(tmp_path))
    assert (rnd, headline) == (3, 10273.0)
    assert extra["gbdt_adult_scale"]["train_rows_per_sec"] == 1137000.0


def test_load_round_file_recovers_damaged_r4_shape(tmp_path):
    """A round file of the damaged r4 shape (``parsed: null``, the JSON
    line's front truncated out of the tail) yields usable numbers through
    ``_load_round_file`` itself, chained through the intact round before
    it. The artefacts are the miniature written under ``tmp_path``."""
    _write_rounds(tmp_path)
    path = str(tmp_path / "BENCH_r04.json")
    with open(path) as f:
        assert json.load(f)["parsed"] is None
    got = bench._load_round_file(path, 4)
    assert got is not None
    _, headline, extra = got
    assert isinstance(
        extra["flash_attention_32k"].get("tflops_nominal"), (int, float))
    # chained reconstruction through the r3 artefact beside it
    assert isinstance(headline, (int, float)) and headline > 0


def test_committed_rounds_have_no_unwaived_regressions():
    """ROADMAP item 5: the ``vs_prev_round`` guard as a FAILING test, not
    advisory JSON — round 5 shipped a 20% flash regression silently. Any
    committed round whose per-lane ratio drops below
    ``bench.RATCHET_THRESHOLD`` (0.95) must carry an explicit waiver row in
    ``BENCH_ACKS.md`` (a reviewed decision with a reason), or CI fails."""
    offenders = bench.unwaived_regressions()
    assert offenders == [], (
        "unwaived bench regressions (lane ratio < "
        f"{bench.RATCHET_THRESHOLD}): {offenders}; either recover the "
        "lane or add a reasoned waiver row to BENCH_ACKS.md")


def test_ratchet_flags_unwaived_and_honors_waivers(tmp_path):
    """The gate itself: a sub-threshold lane fails without a waiver and
    passes with one; recovered (damaged-artifact) ratios count too."""
    (tmp_path / "BENCH_r07.json").write_text(json.dumps({
        "n": 7, "rc": 0, "tail": "", "parsed": {
            "value": 100.0, "extra": {
                "resnet50_onnx": {"images_per_sec_per_chip": 100.0},
                "vs_prev_round": {"round": 6, "per_config": {
                    "resnet50_onnx": 0.90, "gbdt_adult_scale": 0.96}}}}}))
    offenders = bench.unwaived_regressions(here=str(tmp_path))
    assert offenders == [(7, "resnet50_onnx", 0.90)]
    # 0.96 is above the 0.95 line: not an offender
    (tmp_path / "BENCH_ACKS.md").write_text(
        "| round | config | ratio | reason |\n|---|---|---|---|\n"
        "| 7 | resnet50_onnx | 0.90 | known driver change |\n")
    assert bench.unwaived_regressions(here=str(tmp_path)) == []
    # a waiver for a DIFFERENT round does not leak
    assert bench.unwaived_regressions(
        here=str(tmp_path), waivers={(6, "resnet50_onnx")}) == \
        [(7, "resnet50_onnx", 0.90)]


def test_ratchet_sees_through_damaged_artifacts(tmp_path):
    """A damaged round (parsed: null) whose vs_prev_round survived in the
    tail still participates in the ratchet — recovery must not grant
    amnesty."""
    _write_rounds(tmp_path)  # r4 damaged, flash ratio 1.608 in the tail
    tail = _DAMAGED_TAIL.replace('"flash_attention_32k": 1.608',
                                 '"flash_attention_32k": 0.5')
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(
        {"n": 4, "rc": 0, "tail": tail, "parsed": None}))
    offenders = bench.unwaived_regressions(here=str(tmp_path))
    assert (4, "flash_attention_32k", 0.5) in offenders


def test_waiver_file_parses(tmp_path):
    path = tmp_path / "BENCH_ACKS.md"
    path.write_text(
        "# Bench regression waivers\n\nprose before the table\n\n"
        "| round | config | ratio | reason |\n|---|---|---|---|\n"
        "| 5 | flash_attention_32k | 0.803 | two confounds at once |\n"
        "| 5 | flat:vit_to_gbdt_pipeline | 1983.9 img/s | enforcement "
        "first, perf work queued |\n")
    waivers = bench.load_waivers(str(path))
    assert (5, "flash_attention_32k") in waivers
    # prefixed gate waivers (mfu:<lane> / flat:<lane>) parse too
    assert (5, "flat:vit_to_gbdt_pipeline") in waivers


# ---------------------------------------------------------------------------
# MFU ratchet: per-lane floors + the flat-lane stagnation detector
# (ROADMAP item 6: "ViT flat for three rounds" is a failing test now)
# ---------------------------------------------------------------------------

def _write_round(tmp_path, rnd, lanes):
    (tmp_path / f"BENCH_r{rnd:02d}.json").write_text(json.dumps(
        {"n": rnd, "rc": 0, "tail": "",
         "parsed": {"value": 1.0, "extra": lanes}}))


def test_mfu_floor_fails_below_and_passes_above(tmp_path):
    _write_round(tmp_path, 7, {
        "vit_to_gbdt_pipeline": {"images_per_sec_end_to_end": 2000.0,
                                 "mfu_vit_only": 0.21},
        "resnet50_onnx": {"images_per_sec_per_chip": 12000.0, "mfu": 0.47},
    })
    offenders = bench.mfu_violations(here=str(tmp_path), waivers=set())
    assert offenders == [(7, "mfu:vit_to_gbdt_pipeline", 0.21)]
    # a reasoned waiver row clears it
    assert bench.mfu_violations(
        here=str(tmp_path),
        waivers={(7, "mfu:vit_to_gbdt_pipeline")}) == []


def test_mfu_floor_skips_null_mfu_and_old_rounds(tmp_path):
    # a CPU-fallback round reports mfu: null (unknown device peak) — the
    # floor skips it rather than guessing; rounds before the floor's
    # introduction (MFU_FLOOR_FROM_ROUND) are history, not regressions
    _write_round(tmp_path, 7, {
        "vit_to_gbdt_pipeline": {"images_per_sec_end_to_end": 9.0,
                                 "mfu_vit_only": None}})
    _write_round(tmp_path, 2, {
        "resnet50_onnx": {"images_per_sec_per_chip": 4101.0, "mfu": 0.17}})
    assert bench.mfu_violations(here=str(tmp_path), waivers=set()) == []


def test_stagnation_detector_on_synthetic_flat_series(tmp_path):
    # three consecutive rounds flat within 2% while MFU sits at 0.35:
    # stagnating WITH headroom -> violation at the window's last round
    for rnd, v in ((7, 1983.9), (8, 1984.0), (9, 1983.9)):
        _write_round(tmp_path, rnd, {
            "vit_to_gbdt_pipeline": {"images_per_sec_end_to_end": v,
                                     "mfu_vit_only": 0.354}})
    offenders = bench.stagnation_violations(here=str(tmp_path),
                                            waivers=set())
    assert offenders == [(9, "flat:vit_to_gbdt_pipeline", 1983.9)]
    # folded into the one CI gate, honoring waivers
    assert (9, "flat:vit_to_gbdt_pipeline", 1983.9) in \
        bench.unwaived_regressions(here=str(tmp_path), waivers=set())
    assert bench.stagnation_violations(
        here=str(tmp_path),
        waivers={(9, "flat:vit_to_gbdt_pipeline")}) == []


def test_stagnation_exempts_high_mfu_and_moving_lanes(tmp_path):
    for rnd, (vit, bert) in ((7, (1900.0, 4314.0)), (8, (2100.0, 4319.0)),
                             (9, (2350.0, 4353.0))):
        _write_round(tmp_path, rnd, {
            # vit MOVES >2% each round: not flat
            "vit_to_gbdt_pipeline": {"images_per_sec_end_to_end": vit,
                                     "mfu_vit_only": 0.36},
            # bert IS flat but at 0.49 MFU — near the practical ceiling,
            # above STAGNATION_MFU_BAR: exempt
            "bert_base_onnx": {"sequences_per_sec_per_chip": bert,
                               "mfu": 0.494}})
    assert bench.stagnation_violations(here=str(tmp_path),
                                       waivers=set()) == []


def test_stagnation_counts_error_rounds_as_no_progress(tmp_path):
    # the real ViT shape: r+1 errored (no value), r and r+2 unchanged —
    # an error round is not progress, the lane is still flat
    _write_round(tmp_path, 7, {
        "vit_to_gbdt_pipeline": {"images_per_sec_end_to_end": 1983.89,
                                 "mfu_vit_only": 0.354}})
    _write_round(tmp_path, 8, {
        "vit_to_gbdt_pipeline": {"error": "TracerArrayConversionError"}})
    _write_round(tmp_path, 9, {
        "vit_to_gbdt_pipeline": {"images_per_sec_end_to_end": 1983.91,
                                 "mfu_vit_only": 0.354}})
    offenders = bench.stagnation_violations(here=str(tmp_path),
                                            waivers=set())
    assert offenders == [(9, "flat:vit_to_gbdt_pipeline", 1983.91)]


def test_flat_series_is_caught_and_passes_only_through_its_waiver_row(
        tmp_path):
    """The motivating shape: a lane flat over three rounds at 0.354 MFU,
    the middle round an error, is DETECTED (not grandfathered in silently)
    and passes the gate only through a reasoned ``BENCH_ACKS.md`` row read
    from the same directory."""
    for rnd, lane in (
            (3, {"images_per_sec_end_to_end": 1983.89,
                 "mfu_vit_only": 0.354}),
            (4, {"error": "TracerArrayConversionError"}),
            (5, {"images_per_sec_end_to_end": 1983.91,
                 "mfu_vit_only": 0.354})):
        _write_round(tmp_path, rnd, {"vit_to_gbdt_pipeline": lane})
    here = str(tmp_path)
    raw = bench.stagnation_violations(here=here, waivers=set())
    assert (5, "flat:vit_to_gbdt_pipeline", 1983.91) in raw
    (tmp_path / "BENCH_ACKS.md").write_text(
        "| round | config | ratio | reason |\n|---|---|---|---|\n"
        "| 5 | flat:vit_to_gbdt_pipeline | 1983.9 img/s, mfu 0.354 | "
        "enforcement first, perf work queued |\n")
    assert bench.stagnation_violations(here=here) == []  # waived, reasoned


def test_error_strings_capped():
    """bench.main caps recorded errors at 300 chars (source-level pin)."""
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py")) as f:
        src = f.read()
    assert "[:300]" in src


# ---------------------------------------------------------------------------
# stale BENCH_ACKS rows are CI failures; CPU rounds are refused loudly
# ---------------------------------------------------------------------------

def test_stale_waiver_round_without_artifact(tmp_path):
    _write_round(tmp_path, 7, {"resnet50_onnx": {}})
    stale = bench.stale_waivers(here=str(tmp_path),
                                waivers={(9, "resnet50_onnx")})
    assert len(stale) == 1 and stale[0][:2] == (9, "resnet50_onnx")
    assert "no committed BENCH_r" in stale[0][2]


def test_stale_waiver_unknown_lane(tmp_path):
    _write_round(tmp_path, 7, {"resnet50_onnx": {}})
    stale = bench.stale_waivers(here=str(tmp_path),
                                waivers={(7, "resnet50_onxx")})
    assert len(stale) == 1 and "unknown lane" in stale[0][2]
    # gate-prefixed rows judge the lane AFTER stripping mfu:/flat:
    assert bench.stale_waivers(here=str(tmp_path),
                               waivers={(7, "mfu:resnet50_onnx"),
                                        (7, "flat:serving_latency"),
                                        (7, "gbdt_adult_scale")}) == []


def test_committed_bench_acks_have_no_stale_rows():
    """The gate: every committed BENCH_ACKS.md row must still waive a
    committed round and a lane the bench stamps — dead rows silently
    re-arm as blanket suppressions if the lane name ever comes back."""
    assert bench.stale_waivers() == []


def test_bench_refuses_cpu_round():
    """`python bench.py` on a CPU-resolved backend must stamp a refusal
    (exit 2, value null, no lane numbers) instead of publishing host
    throughput as accelerator history."""
    import subprocess
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_ALLOW_CPU", None)
    r = subprocess.run([sys.executable, os.path.join(here, "bench.py")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 2, r.stdout + r.stderr
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["value"] is None and doc["vs_baseline"] is None
    assert "refused" in doc["extra"]
    assert doc["extra"]["platform"] == "cpu"
    assert "allow-cpu" in doc["extra"]["refused"]


def test_cpu_refusal_artifact_shape():
    """The refusal keeps the one-JSON-line stdout contract: same headline
    metric key, null value, and no per-lane numbers the ratchet or MFU
    gates could mistake for measurements."""
    from synapseml_tpu.runtime.topology import require_backend
    doc = bench._cpu_refusal(require_backend(allow_cpu=True))
    json.dumps(doc)  # serializable
    assert doc["metric"] == "resnet50_onnx_images_per_sec_per_chip"
    assert doc["value"] is None
    assert not any(k in doc["extra"] for k in bench._PRIMARY)


# ---------------------------------------------------------------------------
# Beyond-HBM gate: the onnx_fsdp_hbm lane must actually shrink at-rest
# per-device weight bytes (hbm_vs_replicated < 1.0) without giving up
# throughput (rows_per_sec_ratio >= 0.9) — an absolute gate, not a
# round-over-round ratchet, because the whole point of fsdp storage is a
# ratio that holds in every round
# ---------------------------------------------------------------------------

def test_fsdp_hbm_gate_flags_ceiling_and_floor(tmp_path):
    _write_round(tmp_path, 8, {
        "onnx_fsdp_hbm": {"hbm_vs_replicated": 1.02,
                          "rows_per_sec_ratio": 0.85}})
    offenders = bench.fsdp_hbm_violations(here=str(tmp_path), waivers=set())
    assert (8, "hbm:onnx_fsdp_hbm", 1.02) in offenders
    assert (8, "thr:onnx_fsdp_hbm", 0.85) in offenders
    # folded into the one CI gate
    gate = bench.unwaived_regressions(here=str(tmp_path), waivers=set())
    assert (8, "hbm:onnx_fsdp_hbm", 1.02) in gate
    # reasoned waiver rows clear each key independently
    assert bench.fsdp_hbm_violations(
        here=str(tmp_path),
        waivers={(8, "hbm:onnx_fsdp_hbm")}) == [(8, "thr:onnx_fsdp_hbm", 0.85)]
    assert bench.fsdp_hbm_violations(
        here=str(tmp_path),
        waivers={(8, "hbm:onnx_fsdp_hbm"), (8, "thr:onnx_fsdp_hbm")}) == []


def test_fsdp_hbm_gate_passes_healthy_lane(tmp_path):
    _write_round(tmp_path, 8, {
        "onnx_fsdp_hbm": {"hbm_vs_replicated": 0.251,
                          "rows_per_sec_ratio": 0.93}})
    assert bench.fsdp_hbm_violations(here=str(tmp_path), waivers=set()) == []


def test_fsdp_hbm_gate_skips_rounds_without_the_lane(tmp_path):
    # rounds predating the lane (r04-r06) simply don't stamp it; the gate
    # must not invent violations for them, nor for error rounds
    _write_round(tmp_path, 5, {
        "resnet50_onnx": {"images_per_sec_per_chip": 12000.0, "mfu": 0.47}})
    _write_round(tmp_path, 8, {"onnx_fsdp_hbm": {"error": "boom"}})
    assert bench.fsdp_hbm_violations(here=str(tmp_path), waivers=set()) == []
