"""The reduction from a trace to numbers: on intervals worked by hand, and on
a small trace recorded on the chip (three calls of BERTTiny, 64x128, bf16; my
chip run, PR 24)."""

import os

import pytest

from benchmark import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "bert_tiny_64x128_3calls.xplane.pb")


def test_interval_arithmetic():
    merged = tr.merge([(3, 4), (0, 1), (0.5, 2), (6, 7)])
    assert merged == [(0, 2), (3, 4), (6, 7)]
    assert tr.covered(merged, 1, 6.5) == pytest.approx(1 + 1 + 0.5)
    assert tr.gaps(merged, -1, 8) == [(-1, 0), (2, 3), (4, 6), (7, 8)]
    assert tr.gaps(merged, 0.5, 3.5) == [(2, 3)]


def test_op_classes_from_the_ops_own_text():
    ffn = ("%fusion.1815 = (f32[256,512]{1,0}, f32[256,512,768]{2,1,0}) "
           "fusion(bf16[3072,768]{1,0} %custom-call.69), kind=kOutput, "
           "calls=%fused_computation.1964")
    softmax = ("%fusion.7 = f32[256,12,512]{2,1,0} fusion(f32[256,12,512,512]"
               "{2,3,1,0} %x), kind=kLoop, calls=%fused_computation.7")
    conv = "%convolution.3 = bf16[8,8]{1,0} convolution(bf16[8,8] %a, bf16[8,8] %b)"
    copy = "%copy.145 = s32[32,4,8,128]{3,1,2,0} copy(s32[32,4,8,128] %fusion.1)"
    assert [tr.is_matmul(t) for t in (ffn, softmax, conv, copy)] == \
        [True, False, True, False]
    assert tr.op_label(ffn) == "fusion:kOutput (f32[256,512]"
    assert tr.op_label(softmax) == "fusion:kLoop f32[256,12,512]"
    assert tr.op_label(copy) == "copy s32[32,4,8,128]"


def _planes():
    """One device, two calls of 10 s; the program runs 1-5 and 12-19 with a
    2 s hole in its second execution; a stray op of another program at 10.5."""
    ops = [(1, 3, "%fusion.1 = f32[4] fusion(f32[4] %a), kind=kOutput, calls=%c"),
           (3, 5, "%fusion.2 = f32[4] fusion(f32[4] %a), kind=kLoop, calls=%c"),
           (10.5, 11, "%copy.9 = f32[4] copy(f32[4] %a)"),
           (12, 15, "%convolution.1 = f32[4] convolution(f32[4] %a, f32[4] %b)"),
           (17, 19, "%fusion.2 = f32[4] fusion(f32[4] %a), kind=kLoop, calls=%c")]
    return {
        "/device:TPU:0": {
            "XLA Ops": ops,
            "XLA Modules": [(1, 5, "jit_prog(1)"), (10.5, 11, "jit_other(2)"),
                            (12, 19, "jit_prog(1)")]},
        "/host:CPU": {"python3": [
            (0, 10, tr.CALL_ANNOTATION), (11, 21, tr.CALL_ANNOTATION),
            (0, 10, "$model.py:1 transform"), (5, 10, "$array.py:2 _value"),
            (11, 21, "$model.py:1 transform"), (15, 17.5, "$x.py:3 pad")],
            "pjrt-tpu-tasks/7": [(5.5, 9, "Linearize"), (9, 9.5, "Transfer")]},
    }


def test_reduction_by_hand():
    r = tr.reduce_planes(_planes(), "jit_prog")
    assert r["window_s"] == 21 and r["calls"] == 2 and r["devices"] == 1
    assert r["busy_s"] == pytest.approx(4 + 0.5 + 3 + 2)
    assert r["call_idle_s"] == pytest.approx([10 - 4, 10 - 5])
    assert [e["busy_s"] for e in r["executions"]] == pytest.approx([4, 5])
    assert [e["matmul_s"] for e in r["executions"]] == pytest.approx([2, 3])
    gaps = dict(r["idle_gaps"])
    assert gaps["in_call: $array.py:2 _value | Linearize"] == pytest.approx(5)
    assert gaps["in_call: $x.py:3 pad"] == pytest.approx(2)
    assert gaps["in_call: $model.py:1 transform"] == pytest.approx(1 + 1 + 2)
    assert gaps["between_calls"] == pytest.approx(0.5)
    assert r["device_ops"][0] == ["fusion:kLoop f32[4]", pytest.approx(4)]


def test_nothing_to_read_is_none():
    planes = _planes()
    planes["/device:TPU:0"]["XLA Ops"] = []
    assert tr.reduce_planes(planes, "jit_prog") is None
    planes = _planes()
    planes["/host:CPU"]["python3"] = []
    assert tr.reduce_planes(planes, "jit_prog") is None


def test_recorded_trace():
    r = tr.reduce_trace(RECORDED, "_run_positional")
    # window: first call's start 52.541221 ms to last call's end 82.720281 ms
    assert r["window_s"] == pytest.approx(0.03017906, rel=1e-6)
    assert r["calls"] == 3 and len(r["executions"]) == 3
    assert r["busy_s"] == pytest.approx(0.000919752, rel=1e-4)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.9695, abs=1e-3)
    # one execution: 0.313 ms on the device, 0.252 ms of it in the six
    # kOutput fusions a layer (two layers) that hold the matrix products
    assert r["executions"][1]["busy_s"] == pytest.approx(312.9e-6, rel=1e-2)
    assert r["executions"][1]["matmul_s"] == pytest.approx(252.1e-6, rel=1e-2)
    assert r["executions"][1]["ops"] == 101
    assert r["device_ops"][0][0] == "fusion:kOutput (f32[64,128]"
    # the probe slept 10 ms between calls: 30.18 ms of window less 8.94 ms
    # inside the three calls, less the ops that ran before a call's start
    assert dict(r["idle_gaps"])["between_calls"] == pytest.approx(0.02067, rel=1e-2)
