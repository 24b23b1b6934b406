"""ONNX engine tests: wire round-trip, op semantics vs torch, end-to-end models.

Mirrors the reference's ONNXModelSuite strategy (`deep-learning/src/test/.../ONNXModelSuite.scala`)
of asserting real model predictions — but cross-checks against torch (CPU) since the
image has no network access for ONNX zoo downloads.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.core import Table
from synapseml_tpu.onnx import (
    ONNXModel,
    OnnxFunction,
    make_graph,
    make_model,
    node,
    parse_model,
    serialize_model,
    value_info,
)
from synapseml_tpu.onnx.wire import numpy_to_tensor, tensor_to_numpy


def build_fn(nodes, inputs, outputs, inits=None, opset=17, **kw):
    g = make_graph(nodes, "test", inputs, outputs, inits)
    return OnnxFunction(serialize_model(make_model(g, opset=opset)), **kw)


def test_wire_roundtrip():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(4, 3)).astype(np.float32)
    g = make_graph(
        [node("MatMul", ["x", "w"], ["y"]), node("Relu", ["y"], ["z"])],
        "rt",
        [value_info("x", np.float32, ["N", 4])],
        [value_info("z", np.float32, ["N", 3])],
        {"w": w},
    )
    m = make_model(g, opset=15)
    data = serialize_model(m)
    back = parse_model(data)
    assert back.opset_version == 15
    assert [n.op_type for n in back.graph.node] == ["MatMul", "Relu"]
    np.testing.assert_allclose(tensor_to_numpy(back.graph.initializer[0]), w)
    assert back.graph.input[0].shape == ["N", 4]


def test_tensor_dtypes_roundtrip():
    for dtype in [np.float32, np.int64, np.int32, np.uint8, np.bool_, np.float16]:
        arr = (np.arange(6).reshape(2, 3) % 2).astype(dtype)
        t = numpy_to_tensor("t", arr)
        back = tensor_to_numpy(t)
        np.testing.assert_array_equal(back, arr)


def test_matmul_relu_exec():
    w = np.array([[1.0, -1.0], [2.0, 0.5]], dtype=np.float32)
    fn = build_fn(
        [node("MatMul", ["x", "w"], ["y"]), node("Relu", ["y"], ["z"])],
        [value_info("x", np.float32, [None, 2])],
        [value_info("z", np.float32, [None, 2])],
        {"w": w},
    )
    x = np.array([[1.0, 2.0]], dtype=np.float32)
    out = fn({"x": x})["z"]
    np.testing.assert_allclose(np.asarray(out), np.maximum(x @ w, 0))


@pytest.mark.parametrize("stride,pad", [(1, 0), (2, 1), (1, 2)])
def test_conv_matches_torch(stride, pad):
    import torch

    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 3, 16, 16)).astype(np.float32)
    w = rng.normal(size=(5, 3, 3, 3)).astype(np.float32)
    b = rng.normal(size=(5,)).astype(np.float32)
    ref = torch.nn.functional.conv2d(
        torch.tensor(x), torch.tensor(w), torch.tensor(b), stride=stride, padding=pad
    ).numpy()
    fn = build_fn(
        [node("Conv", ["x", "w", "b"], ["y"], kernel_shape=[3, 3],
              strides=[stride, stride], pads=[pad, pad, pad, pad])],
        [value_info("x", np.float32, list(x.shape))],
        [value_info("y", np.float32, None)],
        {"w": w, "b": b},
    )
    out = np.asarray(fn({"x": x})["y"])
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_grouped_conv_matches_torch():
    import torch

    rng = np.random.default_rng(2)
    x = rng.normal(size=(1, 4, 8, 8)).astype(np.float32)
    w = rng.normal(size=(8, 2, 3, 3)).astype(np.float32)  # groups=2
    ref = torch.nn.functional.conv2d(torch.tensor(x), torch.tensor(w), groups=2, padding=1).numpy()
    fn = build_fn(
        [node("Conv", ["x", "w"], ["y"], kernel_shape=[3, 3], pads=[1, 1, 1, 1], group=2)],
        [value_info("x", np.float32, list(x.shape))],
        [value_info("y", np.float32, None)],
        {"w": w},
    )
    np.testing.assert_allclose(np.asarray(fn({"x": x})["y"]), ref, rtol=1e-4, atol=1e-4)


def test_maxpool_avgpool_match_torch():
    import torch

    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 3, 9, 9)).astype(np.float32)
    tx = torch.tensor(x)
    ref_max = torch.nn.functional.max_pool2d(tx, 3, stride=2, padding=1).numpy()
    ref_avg = torch.nn.functional.avg_pool2d(tx, 2, stride=2).numpy()
    fn = build_fn(
        [
            node("MaxPool", ["x"], ["m"], kernel_shape=[3, 3], strides=[2, 2], pads=[1, 1, 1, 1]),
            node("AveragePool", ["x"], ["a"], kernel_shape=[2, 2], strides=[2, 2]),
        ],
        [value_info("x", np.float32, list(x.shape))],
        [value_info("m", np.float32, None), value_info("a", np.float32, None)],
    )
    out = fn({"x": x})
    np.testing.assert_allclose(np.asarray(out["m"]), ref_max, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out["a"]), ref_avg, rtol=1e-5, atol=1e-5)


def test_batchnorm_gemm_match_torch():
    import torch

    rng = np.random.default_rng(4)
    x = rng.normal(size=(4, 6, 5, 5)).astype(np.float32)
    scale = rng.normal(size=6).astype(np.float32)
    bias = rng.normal(size=6).astype(np.float32)
    mean = rng.normal(size=6).astype(np.float32)
    var = rng.uniform(0.5, 2.0, size=6).astype(np.float32)
    ref = torch.nn.functional.batch_norm(
        torch.tensor(x), torch.tensor(mean), torch.tensor(var),
        torch.tensor(scale), torch.tensor(bias), eps=1e-5,
    ).numpy()
    fn = build_fn(
        [node("BatchNormalization", ["x", "s", "b", "m", "v"], ["y"], epsilon=1e-5)],
        [value_info("x", np.float32, list(x.shape))],
        [value_info("y", np.float32, None)],
        {"s": scale, "b": bias, "m": mean, "v": var},
    )
    np.testing.assert_allclose(np.asarray(fn({"x": x})["y"]), ref, rtol=1e-3, atol=1e-4)

    a = rng.normal(size=(3, 4)).astype(np.float32)
    w = rng.normal(size=(5, 4)).astype(np.float32)
    c = rng.normal(size=(5,)).astype(np.float32)
    fn2 = build_fn(
        [node("Gemm", ["a", "w", "c"], ["y"], transB=1, alpha=1.0, beta=1.0)],
        [value_info("a", np.float32, [3, 4])],
        [value_info("y", np.float32, None)],
        {"w": w, "c": c},
    )
    np.testing.assert_allclose(np.asarray(fn2({"a": a})["y"]), a @ w.T + c, rtol=1e-4, atol=1e-5)


def test_layernorm_softmax_match_torch():
    import torch

    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 7, 8)).astype(np.float32)
    g = rng.normal(size=8).astype(np.float32)
    b = rng.normal(size=8).astype(np.float32)
    ref = torch.nn.functional.layer_norm(
        torch.tensor(x), (8,), torch.tensor(g), torch.tensor(b), eps=1e-5
    ).numpy()
    fn = build_fn(
        [node("LayerNormalization", ["x", "g", "b"], ["y"], axis=-1, epsilon=1e-5),
         node("Softmax", ["y"], ["p"], axis=-1)],
        [value_info("x", np.float32, list(x.shape))],
        [value_info("y", np.float32, None), value_info("p", np.float32, None)],
        {"g": g, "b": b},
    )
    out = fn({"x": x})
    np.testing.assert_allclose(np.asarray(out["y"]), ref, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(out["p"]), torch.softmax(torch.tensor(ref), -1).numpy(), rtol=1e-3, atol=1e-5
    )


def test_dynamic_shape_chain_constant_folds():
    """BERT-style Shape->Gather->Concat->Reshape chain must compile (static under jit)."""
    fn = build_fn(
        [
            node("Shape", ["x"], ["shp"]),
            node("Gather", ["shp", "zero"], ["batch"], axis=0),
            node("Gather", ["shp", "one"], ["seq"], axis=0),
            node("Unsqueeze", ["batch", "ax0"], ["b1"]),
            node("Unsqueeze", ["seq", "ax0"], ["s1"]),
            node("Concat", ["b1", "s1", "negone"], ["newshape"], axis=0),
            node("Reshape", ["x", "newshape"], ["y"]),
        ],
        [value_info("x", np.float32, [None, None, 2, 3])],
        [value_info("y", np.float32, None)],
        {
            "zero": np.array(0, dtype=np.int64),
            "one": np.array(1, dtype=np.int64),
            "ax0": np.array([0], dtype=np.int64),
            "negone": np.array([-1], dtype=np.int64),
        },
    )
    x = np.arange(2 * 5 * 2 * 3, dtype=np.float32).reshape(2, 5, 2, 3)
    out = np.asarray(fn({"x": x})["y"])
    assert out.shape == (2, 5, 6)
    np.testing.assert_allclose(out, x.reshape(2, 5, 6))


def test_slice_split_transpose_ops():
    fn = build_fn(
        [
            node("Transpose", ["x"], ["t"], perm=[1, 0]),
            node("Slice", ["x", "starts", "ends", "axes"], ["s"]),
            node("Split", ["x"], ["a", "b"], axis=1, num_outputs=2),
        ],
        [value_info("x", np.float32, [4, 6])],
        [value_info("t", np.float32, None), value_info("s", np.float32, None),
         value_info("a", np.float32, None), value_info("b", np.float32, None)],
        {
            "starts": np.array([1], dtype=np.int64),
            "ends": np.array([3], dtype=np.int64),
            "axes": np.array([0], dtype=np.int64),
        },
        opset=13,
    )
    x = np.arange(24, dtype=np.float32).reshape(4, 6)
    out = fn({"x": x})
    np.testing.assert_allclose(np.asarray(out["t"]), x.T)
    np.testing.assert_allclose(np.asarray(out["s"]), x[1:3])
    np.testing.assert_allclose(np.asarray(out["a"]), x[:, :3])
    np.testing.assert_allclose(np.asarray(out["b"]), x[:, 3:])


def test_squeeze_axes_attr_pre13_and_input_post13():
    x = np.zeros((1, 3, 1), dtype=np.float32)
    fn_old = build_fn(
        [node("Squeeze", ["x"], ["y"], axes=[0])],
        [value_info("x", np.float32, [1, 3, 1])],
        [value_info("y", np.float32, None)],
        opset=11,
    )
    assert np.asarray(fn_old({"x": x})["y"]).shape == (3, 1)
    fn_new = build_fn(
        [node("Squeeze", ["x", "axes"], ["y"])],
        [value_info("x", np.float32, [1, 3, 1])],
        [value_info("y", np.float32, None)],
        {"axes": np.array([2], dtype=np.int64)},
        opset=13,
    )
    assert np.asarray(fn_new({"x": x})["y"]).shape == (1, 3)


def test_reduce_erf_where_cast():
    import scipy.special

    x = np.linspace(-2, 2, 12, dtype=np.float32).reshape(3, 4)
    fn = build_fn(
        [
            node("ReduceMean", ["x"], ["m"], axes=[1], keepdims=1),
            node("Erf", ["x"], ["e"]),
            node("Cast", ["x"], ["i"], to=7),
            node("Greater", ["x", "m"], ["g"]),
            node("Where", ["g", "x", "m"], ["w"]),
        ],
        [value_info("x", np.float32, [3, 4])],
        [value_info(n, np.float32, None) for n in ["m", "e", "i", "g", "w"]],
        opset=13,
    )
    out = fn({"x": x})
    np.testing.assert_allclose(np.asarray(out["m"]), x.mean(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["e"]), scipy.special.erf(x), rtol=1e-4)
    assert np.asarray(out["i"]).dtype == np.int64 or np.asarray(out["i"]).dtype == np.int32


def test_unsupported_op_reported():
    with pytest.raises(NotImplementedError, match="NotARealOp"):
        build_fn(
            [node("NotARealOp", ["x"], ["y"])],
            [value_info("x", np.float32, [1])],
            [value_info("y", np.float32, None)],
        )


def test_bfloat16_policy_small_cnn():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(2, 3, 8, 8)).astype(np.float32)
    w = rng.normal(size=(4, 3, 3, 3)).astype(np.float32) * 0.1
    nodes = [
        node("Conv", ["x", "w"], ["c"], kernel_shape=[3, 3], pads=[1, 1, 1, 1]),
        node("Relu", ["c"], ["r"]),
        node("GlobalAveragePool", ["r"], ["g"]),
        node("Flatten", ["g"], ["y"]),
    ]
    f32 = build_fn(nodes, [value_info("x", np.float32, list(x.shape))],
                   [value_info("y", np.float32, None)], {"w": w})
    bf16 = build_fn(nodes, [value_info("x", np.float32, list(x.shape))],
                    [value_info("y", np.float32, None)], {"w": w}, dtype_policy="bfloat16")
    a = np.asarray(f32({"x": x})["y"])
    b = np.asarray(bf16({"x": x})["y"])
    assert b.dtype == np.float32  # policy casts outputs back
    np.testing.assert_allclose(a, b, rtol=0.05, atol=0.02)


def test_onnx_model_transformer_end_to_end():
    """Pipeline-level: ONNXModel with feed/fetch/softmax/argmax over a Table."""
    rng = np.random.default_rng(8)
    w = rng.normal(size=(4, 3)).astype(np.float32)
    g = make_graph(
        [node("MatMul", ["features", "w"], ["logits"])],
        "clf",
        [value_info("features", np.float32, [None, 4])],
        [value_info("logits", np.float32, [None, 3])],
        {"w": w},
    )
    model_bytes = serialize_model(make_model(g))
    t = Table({"feat": rng.normal(size=(10, 4)).astype(np.float32)})
    m = ONNXModel(
        feed_dict={"features": "feat"},
        fetch_dict={"rawPrediction": "logits"},
        softmax_dict={"rawPrediction": "probability"},
        argmax_dict={"rawPrediction": "prediction"},
        batch_size=4,  # forces pad-to-bucket on the final batch of 2
    ).set_model(model_bytes)
    out = m.transform(t)
    logits = t["feat"] @ w
    np.testing.assert_allclose(out["rawPrediction"], logits, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out["probability"].sum(axis=1), np.ones(10), rtol=1e-5)
    np.testing.assert_array_equal(out["prediction"], logits.argmax(1))


def test_onnx_model_save_load(tmp_path):
    rng = np.random.default_rng(9)
    w = rng.normal(size=(2, 2)).astype(np.float32)
    g = make_graph(
        [node("MatMul", ["x", "w"], ["y"])], "m",
        [value_info("x", np.float32, [None, 2])], [value_info("y", np.float32, None)],
        {"w": w},
    )
    m = ONNXModel(feed_dict={"x": "c"}, fetch_dict={"out": "y"}).set_model(
        serialize_model(make_model(g))
    )
    t = Table({"c": rng.normal(size=(3, 2)).astype(np.float32)})
    expected = m.transform(t)["out"]
    p = str(tmp_path / "onnxstage")
    m.save(p)
    from synapseml_tpu.core import load_stage

    m2 = load_stage(p)
    np.testing.assert_allclose(m2.transform(t)["out"], expected, rtol=1e-6)


def test_flatten_softmax_onehot_edge_cases():
    """Regression: negative axes and out-of-range indices (ONNX spec corners)."""
    from synapseml_tpu.onnx.ops import OPS

    out = OPS["Flatten"]([jnp.zeros((2, 3, 4))], {"axis": -1},
                         {"op_type": "Flatten", "opset": 13})
    assert out.shape == (6, 4)
    out = OPS["Softmax"]([jnp.ones((2, 3, 4))], {"axis": -1},
                         {"op_type": "Softmax", "opset": 11})
    np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-6)
    # OneHot: -1 wraps to depth-1; 5 is out of [-3, 2] -> all-off row
    out = OPS["OneHot"]([np.array([5, -1, 2]), np.array(3), np.array([0.0, 1.0])],
                        {}, {"op_type": "OneHot", "opset": 13})
    np.testing.assert_allclose(np.asarray(out), [[0, 0, 0], [0, 0, 1], [0, 0, 1]])


def test_quantize_linear_golden():
    """ONNX spec golden values: saturation at both ends and round-half-
    to-even (3/2 -> 2, not 1)."""
    from synapseml_tpu.onnx.ops import OPS

    x = np.array([0.0, 2.0, 3.0, 1000.0, -254.0, -1000.0], np.float32)
    y = OPS["QuantizeLinear"](
        [jnp.asarray(x), np.float32(2.0), np.uint8(128)], {},
        {"op_type": "QuantizeLinear", "opset": 13})
    assert np.asarray(y).dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(y), [128, 129, 130, 255, 1, 0])
    # int8 output follows the zero_point dtype; saturates at [-128, 127]
    y = OPS["QuantizeLinear"](
        [jnp.asarray(x), np.float32(2.0), np.int8(0)], {},
        {"op_type": "QuantizeLinear", "opset": 13})
    assert np.asarray(y).dtype == np.int8
    np.testing.assert_array_equal(np.asarray(y), [0, 1, 2, 127, -127, -128])


def test_quantize_linear_per_axis():
    from synapseml_tpu.onnx.ops import OPS

    x = np.array([[-1.5, 0.5, 3.4], [2.0, -5.0, 6.0]], np.float32)
    y = OPS["QuantizeLinear"](
        [jnp.asarray(x), np.array([1.0, 2.0], np.float32),
         np.array([0, 10], np.int8)], {"axis": 0},
        {"op_type": "QuantizeLinear", "opset": 13})
    # row 0: round([-1.5, .5, 3.4]) + 0 (half-to-even: -1.5->-2, .5->0)
    # row 1: round([1, -2.5, 3]) + 10 (-2.5 -> -2)
    np.testing.assert_array_equal(np.asarray(y), [[-2, 0, 3], [11, 8, 13]])


def test_dequantize_linear_golden():
    from synapseml_tpu.onnx.ops import OPS

    x = np.array([0, 3, 128, 255], np.uint8)
    y = OPS["DequantizeLinear"](
        [jnp.asarray(x), np.float32(2.0), np.uint8(128)], {},
        {"op_type": "DequantizeLinear", "opset": 13})
    assert np.asarray(y).dtype == np.float32
    np.testing.assert_allclose(np.asarray(y), [-256.0, -250.0, 0.0, 254.0])
    # per-axis (axis=0): row scales [2, 4], zero points [0, 1]
    x2 = np.array([[0, 1, 2], [3, 4, 5]], np.int8)
    y2 = OPS["DequantizeLinear"](
        [jnp.asarray(x2), np.array([2.0, 4.0], np.float32),
         np.array([0, 1], np.int8)], {"axis": 0},
        {"op_type": "DequantizeLinear", "opset": 13})
    np.testing.assert_allclose(np.asarray(y2), [[0, 2, 4], [8, 12, 16]])


def test_dynamic_quantize_linear_golden():
    from synapseml_tpu.onnx.ops import OPS

    # range [-1, 3] widens to include 0 already: scale 4/255, zp
    # round(63.75) = 64; inputs chosen OFF the .5 rounding boundary so
    # the golden is stable across float orderings
    x = np.array([-1.0, 0.0, 1.0, 3.0], np.float32)
    y, scale, zp = OPS["DynamicQuantizeLinear"](
        [jnp.asarray(x)], {}, {"op_type": "DynamicQuantizeLinear",
                               "opset": 13})
    np.testing.assert_allclose(float(scale), 4.0 / 255.0, rtol=1e-6)
    assert int(zp) == 64 and np.asarray(zp).dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(y), [0, 64, 128, 255])
    # all-zero input: finite everywhere, scale 0, everything quantizes to 0
    y, scale, zp = OPS["DynamicQuantizeLinear"](
        [jnp.zeros(4, jnp.float32)], {},
        {"op_type": "DynamicQuantizeLinear", "opset": 13})
    assert float(scale) == 0.0 and int(zp) == 0
    np.testing.assert_array_equal(np.asarray(y), [0, 0, 0, 0])


def test_matmul_integer_golden():
    """ONNX spec example: uint8 operands, per-tensor zero points, int32
    accumulation (widening BEFORE the zp subtraction — naive uint8 math
    would wrap)."""
    from synapseml_tpu.onnx.ops import OPS

    a = np.array([[11, 7, 3], [10, 6, 2], [9, 5, 1], [8, 4, 0]], np.uint8)
    b = np.array([[1, 4], [2, 5], [3, 6]], np.uint8)
    y = OPS["MatMulInteger"](
        [jnp.asarray(a), jnp.asarray(b), np.uint8(12), np.uint8(0)], {},
        {"op_type": "MatMulInteger", "opset": 13})
    assert np.asarray(y).dtype == np.int32
    np.testing.assert_array_equal(
        np.asarray(y),
        [[-38, -83], [-44, -98], [-50, -113], [-56, -128]])
    # 1-D b_zero_point is per-COLUMN: shifting column 1 by 1 subtracts
    # sum(A - a_zp) per row from that column only
    y2 = OPS["MatMulInteger"](
        [jnp.asarray(a), jnp.asarray(b), np.uint8(12),
         np.array([0, 1], np.uint8)], {},
        {"op_type": "MatMulInteger", "opset": 13})
    row_sums = (a.astype(np.int32) - 12).sum(1)
    np.testing.assert_array_equal(
        np.asarray(y2)[:, 1], np.asarray(y)[:, 1] - row_sums)


def test_conv_integer_golden():
    """ONNX spec example: 3x3 uint8 image, x_zero_point 1, all-ones 2x2
    kernel -> plain 2x2 window sums of (x - 1), int32 out."""
    from synapseml_tpu.onnx.ops import OPS

    x = np.arange(2, 11, dtype=np.uint8).reshape(1, 1, 3, 3)
    w = np.ones((1, 1, 2, 2), np.uint8)
    y = OPS["ConvInteger"](
        [jnp.asarray(x), jnp.asarray(w), np.uint8(1)], {},
        {"op_type": "ConvInteger", "opset": 13})
    assert np.asarray(y).dtype == np.int32
    np.testing.assert_array_equal(
        np.asarray(y).reshape(2, 2), [[12, 16], [24, 28]])
    # with explicit padding the implicit border contributes zero in the
    # shifted domain, i.e. real x_zero_point pixels (onnxruntime semantics)
    yp = OPS["ConvInteger"](
        [jnp.asarray(x), jnp.asarray(w), np.uint8(1)],
        {"pads": [1, 1, 1, 1]},
        {"op_type": "ConvInteger", "opset": 13})
    assert np.asarray(yp).shape == (1, 1, 4, 4)
    np.testing.assert_array_equal(np.asarray(yp)[0, 0, 1:3, 1:3],
                                  [[12, 16], [24, 28]])
    assert int(np.asarray(yp)[0, 0, 0, 0]) == 1  # lone corner pixel: 2-1


def test_qlinear_matmul_golden():
    """ONNX spec example: full requantizing uint8 matmul (int32
    accumulate, rescale, round half to even, re-centre, saturate)."""
    from synapseml_tpu.onnx.ops import OPS

    a = np.array([[208, 236, 0, 238], [3, 214, 255, 29]], np.uint8)
    b = np.array([[152, 51, 244], [60, 26, 255], [0, 127, 246],
                  [127, 254, 247]], np.uint8)
    y = OPS["QLinearMatMul"](
        [jnp.asarray(a), np.float32(0.0066), np.uint8(113),
         jnp.asarray(b), np.float32(0.00705), np.uint8(114),
         np.float32(0.0107), np.uint8(118)], {},
        {"op_type": "QLinearMatMul", "opset": 13})
    assert np.asarray(y).dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(y),
                                  [[168, 115, 255], [1, 66, 151]])


def test_qlinear_conv_golden():
    """ONNX-spec QLinearConv shape (the 1x1-kernel spec example): uint8
    image and kernel with per-channel w_scale/w_zero_point arrays, int32
    accumulation over zero-centred operands, rescale by
    x_scale*w_scale/y_scale, round half to even, re-centre, saturate."""
    from synapseml_tpu.onnx.ops import OPS

    rng = np.random.default_rng(5)
    x = rng.integers(0, 256, size=(1, 1, 7, 7), dtype=np.uint8)
    x_scale, x_zp = np.float32(0.00369204697), np.uint8(132)
    w = np.array([0], np.uint8).reshape(1, 1, 1, 1)
    w_scale = np.array([0.00172794575], np.float32)
    w_zp = np.array([255], np.uint8)
    y_scale, y_zp = np.float32(0.00162681262), np.uint8(123)
    y = OPS["QLinearConv"](
        [jnp.asarray(x), x_scale, x_zp, jnp.asarray(w), w_scale, w_zp,
         y_scale, y_zp], {},
        {"op_type": "QLinearConv", "opset": 13})
    assert np.asarray(y).dtype == np.uint8
    acc = (x.astype(np.int32) - 132) * (0 - 255)
    ref = np.clip(np.round(
        acc.astype(np.float32)
        * np.float32(float(x_scale) * float(w_scale[0]) / float(y_scale)))
        + 123, 0, 255).astype(np.uint8)
    np.testing.assert_array_equal(np.asarray(y), ref)


def test_qlinear_conv_graph_bias_padding_per_channel():
    """QLinearConv through a real graph: 2 output channels with DISTINCT
    per-channel scales/zero_points, an int32 bias (spec: quantized at
    x_scale*w_scale, added into the accumulator) and explicit padding —
    exactly equals a naive integer reference requantized the same way."""
    rng = np.random.default_rng(6)
    x = rng.integers(0, 256, size=(1, 2, 5, 5), dtype=np.uint8)
    w = rng.integers(0, 256, size=(2, 2, 3, 3), dtype=np.uint8)
    bias = np.array([700, -1300], np.int32)
    x_scale, x_zp = np.float32(0.02), np.uint8(120)
    w_scale = np.array([0.015, 0.03], np.float32)
    w_zp = np.array([110, 140], np.uint8)
    y_scale, y_zp = np.float32(0.05), np.uint8(128)
    fn = build_fn(
        [node("QLinearConv",
              ["x", "xs", "xz", "w", "ws", "wz", "ys", "yz", "b"], ["y"],
              pads=[1, 1, 1, 1])],
        [value_info("x", np.uint8, [None, 2, 5, 5])],
        [value_info("y", np.uint8, None)],
        {"xs": x_scale, "xz": x_zp, "w": w, "ws": w_scale, "wz": w_zp,
         "ys": y_scale, "yz": y_zp, "b": bias})
    y = np.asarray(fn({"x": x})["y"])
    assert y.shape == (1, 2, 5, 5) and y.dtype == np.uint8
    # naive reference: zero-centred int32 conv with zero-padding in the
    # SHIFTED domain (pad pixels are real x_zero_point), then requantize
    xc = x.astype(np.int32) - int(x_zp)
    xp = np.zeros((1, 2, 7, 7), np.int32)
    xp[:, :, 1:6, 1:6] = xc
    ref = np.empty((1, 2, 5, 5), np.uint8)
    for o in range(2):
        wc = w[o].astype(np.int32) - int(w_zp[o])
        scale = np.float32(float(x_scale) * float(w_scale[o])
                           / float(y_scale))
        for i in range(5):
            for j in range(5):
                acc = int((xp[0, :, i:i + 3, j:j + 3] * wc).sum()) \
                    + int(bias[o])
                q = np.round(np.float32(acc) * scale) + int(y_zp)
                ref[0, o, i, j] = np.uint8(np.clip(q, 0, 255))
    np.testing.assert_array_equal(y, ref)


def test_matmul_integer_graph_matches_dequant_path():
    """MatMulInteger through a real graph == dequantize-then-float-matmul
    to within accumulated float error, and exactly equals the exact
    integer reference."""
    rng = np.random.default_rng(11)
    a = rng.integers(0, 255, size=(6, 16), dtype=np.uint8)
    w = rng.integers(0, 255, size=(16, 5), dtype=np.uint8)
    fn = build_fn(
        [node("MatMulInteger", ["a", "w", "az", "wz"], ["y"])],
        [value_info("a", np.uint8, [None, 16])],
        [value_info("y", np.int32, None)],
        {"w": w, "az": np.uint8(121), "wz": np.uint8(130)},
    )
    y = np.asarray(fn({"a": a})["y"])
    ref = (a.astype(np.int32) - 121) @ (w.astype(np.int32) - 130)
    np.testing.assert_array_equal(y, ref)


def test_quantize_dequantize_roundtrip_graph():
    """Q -> DQ through a real graph stays within one quantization step."""
    rng = np.random.default_rng(7)
    x = rng.uniform(-4, 4, size=(5, 8)).astype(np.float32)
    fn = build_fn(
        [node("QuantizeLinear", ["x", "s", "z"], ["q"]),
         node("DequantizeLinear", ["q", "s", "z"], ["y"])],
        [value_info("x", np.float32, [None, 8])],
        [value_info("y", np.float32, [None, 8])],
        {"s": np.float32(8.0 / 255.0), "z": np.uint8(128)},
    )
    y = fn({"x": x})["y"]
    np.testing.assert_allclose(np.asarray(y), x, atol=8.0 / 255.0 / 2 + 1e-6)


def test_onnx_model_empty_table():
    """Empty partitions are normal in a partitioned pipeline; must not crash."""
    rng = np.random.default_rng(0)
    w = rng.normal(size=(4, 3)).astype(np.float32)
    g = make_graph(
        [node("MatMul", ["x", "w"], ["y"])], "m",
        [value_info("x", np.float32, ["N", 4])], [value_info("y", np.float32, None)],
        {"w": w},
    )
    m = ONNXModel(feed_dict={"x": "c"}, fetch_dict={"out": "y"}).set_model(
        serialize_model(make_model(g))
    )
    out = m.transform(Table({"c": np.zeros((0, 4), np.float32)}))
    assert out["out"].shape == (0, 3)


# -- model-parallel (tensor-parallel) serving: runtime/layout.py --------------------

def _tp_mlp_bytes(rng, d=32, h=64, out=8):
    w1 = (rng.normal(size=(d, h)) / np.sqrt(d)).astype(np.float32)
    b1 = rng.normal(size=(h,)).astype(np.float32)
    w2 = (rng.normal(size=(h, out)) / np.sqrt(h)).astype(np.float32)
    g = make_graph(
        [node("MatMul", ["x", "w1"], ["h0"]),
         node("Add", ["h0", "b1"], ["h1"]),
         node("Relu", ["h1"], ["h2"]),
         node("MatMul", ["h2", "w2"], ["y"])],
        "tp_mlp",
        [value_info("x", np.float32, [None, d])],
        [value_info("y", np.float32, [None, out])],
        {"w1": w1, "b1": b1, "w2": w2})
    return serialize_model(make_model(g))


def test_tp_sharded_matmul_weights_match_single_device():
    """MatMul initializer weights column-shard over the layout 'model' axis
    (jit-inserted collectives); outputs must match the unsharded graph."""
    from synapseml_tpu.runtime.layout import SpecLayout

    rng = np.random.default_rng(7)
    mb = _tp_mlp_bytes(rng)
    x = rng.normal(size=(16, 32)).astype(np.float32)
    ref = np.asarray(OnnxFunction(mb)({"x": x})["y"])
    layout = SpecLayout.build(data=2, model=4)
    fn_tp = OnnxFunction(mb, layout=layout)
    # both MatMul weights sharded column-wise; the bias replicates
    assert set(fn_tp._const_specs) == {"w1", "w2"}
    from jax.sharding import PartitionSpec as P

    assert fn_tp._const_specs["w1"] == P(None, "model")
    out = np.asarray(fn_tp({"x": x})["y"])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_replicated_and_tp_instances_of_one_graph_never_share_a_program():
    """One graph live twice in one process, replicated and under a (1, 2)
    model-parallel layout: the same feeds and the same weight shapes, but
    two programs (``_program_digest`` sees the placement), each compiled
    once, and the same answers."""
    import jax

    from synapseml_tpu.observability.metrics import get_registry
    from synapseml_tpu.runtime.layout import SpecLayout

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices for the (1, 2) layout")

    def compiles(fn_name):
        family = get_registry().snapshot()["families"].get(
            "smt_compile_seconds") or {"series": []}
        return sum(int(s["count"]) for s in family["series"]
                   if s["labels"][0] == fn_name)

    rng = np.random.default_rng(29)
    # a hidden width of this test's own: no program of an earlier test's
    # graph, still alive and compiled, is found in its place
    mb = _tp_mlp_bytes(rng, h=48)
    x = rng.normal(size=(16, 32)).astype(np.float32)
    replicated = OnnxFunction(mb)
    sharded = OnnxFunction(mb, layout=SpecLayout.build(
        data=1, model=2, devices=jax.devices()[:2]))
    assert set(sharded._const_specs) == {"w1", "w2"}
    assert sharded._program is not replicated._program
    assert sharded._jit is not replicated._jit
    name = replicated._jit.name
    assert sharded._jit.name == name
    before = compiles(name)
    for _ in range(2):  # the second round compiles nothing
        ref = np.asarray(replicated({"x": x})["y"])
        out = np.asarray(sharded({"x": x})["y"])
    assert compiles(name) == before + 2
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_tp_sharding_degrades_to_single_chip():
    """(1, 1) layout: no weight sharded, outputs bit-identical."""
    import jax

    from synapseml_tpu.runtime.layout import SpecLayout

    rng = np.random.default_rng(8)
    mb = _tp_mlp_bytes(rng)
    x = rng.normal(size=(4, 32)).astype(np.float32)
    ref = np.asarray(OnnxFunction(mb)({"x": x})["y"])
    lay = SpecLayout.build(devices=jax.devices()[:1])
    fn = OnnxFunction(mb, layout=lay)
    assert fn._const_specs == {}
    np.testing.assert_array_equal(np.asarray(fn({"x": x})["y"]), ref)


def test_tp_sharding_respects_gemm_transb_and_indivisible_dims():
    """Gemm transB=1 weights shard dim 0 (the output-feature dim); a weight
    whose output dim does not divide the model axis replicates instead of
    erroring."""
    from jax.sharding import PartitionSpec as P

    from synapseml_tpu.runtime.layout import SpecLayout

    rng = np.random.default_rng(9)
    wt = (rng.normal(size=(6, 16)) / 4).astype(np.float32)  # (N=6, K=16)
    bias = np.zeros(6, np.float32)
    w_odd = rng.normal(size=(16, 5)).astype(np.float32)  # 5 cols: indivisible
    g = make_graph(
        [node("Gemm", ["x", "wt", "bias"], ["h"], transB=1),
         node("MatMul", ["x", "w_odd"], ["z"])],
        "gemm_tp",
        [value_info("x", np.float32, [None, 16])],
        [value_info("h", np.float32, [None, 6]),
         value_info("z", np.float32, [None, 5])],
        {"wt": wt, "bias": bias, "w_odd": w_odd})
    mb = serialize_model(make_model(g))
    x = rng.normal(size=(8, 16)).astype(np.float32)
    ref = OnnxFunction(mb)({"x": x})
    fn = OnnxFunction(mb, layout=SpecLayout.build(data=4, model=2))
    assert fn._const_specs == {"wt": P("model", None)}  # w_odd replicated
    out = fn({"x": x})
    for k in ("h", "z"):
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, atol=1e-6)


def test_tp_sharding_bf16_policy():
    """The bfloat16 MXU policy composes with tensor-parallel weights."""
    from synapseml_tpu.runtime.layout import SpecLayout

    rng = np.random.default_rng(10)
    mb = _tp_mlp_bytes(rng)
    x = rng.normal(size=(8, 32)).astype(np.float32)
    ref = np.asarray(OnnxFunction(mb, dtype_policy="bfloat16")({"x": x})["y"])
    fn = OnnxFunction(mb, dtype_policy="bfloat16",
                      layout=SpecLayout.build(data=2, model=4))
    out = np.asarray(fn({"x": x})["y"])
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


# -- beyond-HBM storage: the fsdp axis of runtime/layout.py -------------------

def test_fsdp_planner_stores_weights_and_matches_reference():
    """Under a 3-D (data, fsdp, model) layout the planner's third decision
    kicks in: matmul weights are use-sharded over 'model' AND stored
    row-sharded over 'fsdp' (1/(f*m) of the tensor per device at rest),
    all-gathered transiently at each consumer — outputs match the
    replicated reference."""
    import jax
    from jax.sharding import PartitionSpec as P

    from synapseml_tpu.runtime.layout import SpecLayout

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for the (1,2,2) layout")
    rng = np.random.default_rng(21)
    mb = _tp_mlp_bytes(rng)
    x = rng.normal(size=(16, 32)).astype(np.float32)
    ref = np.asarray(OnnxFunction(mb)({"x": x})["y"])
    layout = SpecLayout.build(data=1, model=2, fsdp=2,
                              devices=jax.devices()[:4])
    fn = OnnxFunction(mb, layout=layout)
    assert fn._const_specs["w1"] == P("fsdp", "model")
    assert fn._const_specs["w2"] == P("fsdp", "model")
    by_name = {r["tensor"]: r for r in fn.placement_report()}
    assert by_name["w1"]["decision"] == "fsdp"
    assert "all-gather" in by_name["w1"]["reason"]
    assert by_name["b1"]["decision"] == "replicated"
    # at rest each device holds exactly 1/(fsdp*model) of the weight
    w1 = fn.constants["w1"]
    assert w1.sharding.spec == P("fsdp", "model")
    assert max(s.data.nbytes for s in w1.addressable_shards) == \
        w1.nbytes // 4
    np.testing.assert_allclose(np.asarray(fn({"x": x})["y"]), ref,
                               rtol=1e-5, atol=1e-6)


def test_fsdp_only_layout_stores_without_model_axis():
    """model=1, fsdp=2: no tensor-parallel use sharding is possible, but
    storage sharding still pays — weights store row-sharded over fsdp and
    gather at the consumer."""
    import jax
    from jax.sharding import PartitionSpec as P

    from synapseml_tpu.runtime.layout import SpecLayout

    rng = np.random.default_rng(22)
    mb = _tp_mlp_bytes(rng)
    x = rng.normal(size=(8, 32)).astype(np.float32)
    ref = np.asarray(OnnxFunction(mb)({"x": x})["y"])
    layout = SpecLayout.build(data=1, model=1, fsdp=2,
                              devices=jax.devices()[:2])
    fn = OnnxFunction(mb, layout=layout)
    assert fn._const_specs["w1"] == P("fsdp", None)
    assert {r["tensor"] for r in fn.placement_report()
            if r["decision"] == "fsdp"} == {"w1", "w2"}
    np.testing.assert_allclose(np.asarray(fn({"x": x})["y"]), ref,
                               rtol=1e-5, atol=1e-6)


def _np_sig(v):
    return 1.0 / (1.0 + np.exp(-v))


def test_lstm_matches_numpy_reference():
    """Forward iofc LSTM with bias, initial states and peepholes against a
    step-by-step numpy reference of the ONNX gate equations."""
    from synapseml_tpu.onnx.ops import OPS

    rng = np.random.default_rng(3)
    s, b, i, h = 5, 2, 3, 4
    x = rng.normal(size=(s, b, i)).astype(np.float32)
    w = rng.normal(size=(1, 4 * h, i)).astype(np.float32)
    r = rng.normal(size=(1, 4 * h, h)).astype(np.float32)
    bias = rng.normal(size=(1, 8 * h)).astype(np.float32)
    h0 = rng.normal(size=(1, b, h)).astype(np.float32)
    c0 = rng.normal(size=(1, b, h)).astype(np.float32)
    p = rng.normal(size=(1, 3 * h)).astype(np.float32)

    y, y_h, y_c = OPS["LSTM"](
        [jnp.asarray(x), w, r, bias, None, h0, c0, p],
        {"hidden_size": h}, {"op_type": "LSTM", "opset": 17})
    assert np.asarray(y).shape == (s, 1, b, h)
    assert np.asarray(y_h).shape == (1, b, h)

    hc, cc = h0[0].astype(np.float64), c0[0].astype(np.float64)
    pi, po, pf = np.split(p[0].astype(np.float64), 3)
    cb = (bias[0, :4 * h] + bias[0, 4 * h:]).astype(np.float64)
    ys = []
    for t in range(s):
        zi, zo, zf, zc = np.split(x[t] @ w[0].T + hc @ r[0].T + cb, 4, axis=-1)
        gi, gf = _np_sig(zi + pi * cc), _np_sig(zf + pf * cc)
        cc = gf * cc + gi * np.tanh(zc)
        hc = _np_sig(zo + po * cc) * np.tanh(cc)
        ys.append(hc)
    np.testing.assert_allclose(np.asarray(y)[:, 0], np.stack(ys), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y_h)[0], hc, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y_c)[0], cc, rtol=2e-5, atol=2e-5)


def test_lstm_defaults_zero_state():
    """Omitted B/initial_h/initial_c behave as zeros."""
    from synapseml_tpu.onnx.ops import OPS

    rng = np.random.default_rng(4)
    s, b, i, h = 3, 1, 2, 2
    x = rng.normal(size=(s, b, i)).astype(np.float32)
    w = rng.normal(size=(1, 4 * h, i)).astype(np.float32)
    r = rng.normal(size=(1, 4 * h, h)).astype(np.float32)
    y1, h1, c1 = OPS["LSTM"]([jnp.asarray(x), w, r], {"hidden_size": h},
                             {"op_type": "LSTM", "opset": 17})
    y2, h2, c2 = OPS["LSTM"](
        [jnp.asarray(x), w, r, np.zeros((1, 8 * h), np.float32), None,
         np.zeros((1, b, h), np.float32), np.zeros((1, b, h), np.float32)],
        {"hidden_size": h}, {"op_type": "LSTM", "opset": 17})
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-6)


@pytest.mark.parametrize("lbr", [0, 1])
def test_gru_matches_numpy_reference(lbr):
    """Forward zrh GRU, both linear_before_reset modes, vs numpy."""
    from synapseml_tpu.onnx.ops import OPS

    rng = np.random.default_rng(7 + lbr)
    s, b, i, h = 4, 3, 2, 5
    x = rng.normal(size=(s, b, i)).astype(np.float32)
    w = rng.normal(size=(1, 3 * h, i)).astype(np.float32)
    r = rng.normal(size=(1, 3 * h, h)).astype(np.float32)
    bias = rng.normal(size=(1, 6 * h)).astype(np.float32)
    h0 = rng.normal(size=(1, b, h)).astype(np.float32)

    y, y_h = OPS["GRU"](
        [jnp.asarray(x), w, r, bias, None, h0],
        {"hidden_size": h, "linear_before_reset": lbr},
        {"op_type": "GRU", "opset": 17})
    assert np.asarray(y).shape == (s, 1, b, h)

    hc = h0[0].astype(np.float64)
    wb, rb = bias[0, :3 * h].astype(np.float64), bias[0, 3 * h:].astype(np.float64)
    wz, wr, wh = np.split(w[0].astype(np.float64), 3)
    rz, rr, rh = np.split(r[0].astype(np.float64), 3)
    wbz, wbr, wbh = np.split(wb, 3)
    rbz, rbr, rbh = np.split(rb, 3)
    ys = []
    for t in range(s):
        z = _np_sig(x[t] @ wz.T + hc @ rz.T + wbz + rbz)
        rg = _np_sig(x[t] @ wr.T + hc @ rr.T + wbr + rbr)
        if lbr:
            hh = np.tanh(x[t] @ wh.T + rg * (hc @ rh.T + rbh) + wbh)
        else:
            hh = np.tanh(x[t] @ wh.T + (rg * hc) @ rh.T + wbh + rbh)
        hc = (1.0 - z) * hh + z * hc
        ys.append(hc)
    np.testing.assert_allclose(np.asarray(y)[:, 0], np.stack(ys), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y_h)[0], hc, rtol=2e-5, atol=2e-5)


def test_lstm_graph_end_to_end():
    """LSTM inside a graph: multi-output wiring and downstream consumption."""
    rng = np.random.default_rng(11)
    s, b, i, h = 4, 2, 3, 3
    w = rng.normal(size=(1, 4 * h, i)).astype(np.float32)
    r = rng.normal(size=(1, 4 * h, h)).astype(np.float32)
    fn = build_fn(
        [node("LSTM", ["x", "w", "r"], ["y", "y_h", "y_c"], hidden_size=h),
         node("Relu", ["y_h"], ["z"])],
        [value_info("x", np.float32, [s, b, i])],
        [value_info("y", np.float32, None), value_info("z", np.float32, None)],
        {"w": w, "r": r},
    )
    x = rng.normal(size=(s, b, i)).astype(np.float32)
    out = fn({"x": x})
    direct = np.asarray(OPS_LSTM_REF(x, w, r))
    np.testing.assert_allclose(np.asarray(out["y"]), direct, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out["z"]), np.maximum(direct[-1], 0), rtol=1e-5, atol=1e-5)


def OPS_LSTM_REF(x, w, r):
    from synapseml_tpu.onnx.ops import OPS
    y, _, _ = OPS["LSTM"]([jnp.asarray(x), w, r], {"hidden_size": r.shape[-1]},
                          {"op_type": "LSTM", "opset": 17})
    return y


# -- dtype_policy="bfloat16": bfloat16 between nodes, float32 inside an op ----

def _parent_matmul(inputs, attrs, ctx):
    """``MatMul`` as it stood before it cast back: the accumulator leaves."""
    return jnp.matmul(inputs[0], inputs[1],
                      preferred_element_type=ctx.get("accum_dtype"))


def _handoff_bytes(mb, feed, **kw):
    """``smt_onnx_float32_handoff_bytes`` after tracing ``mb`` once (no
    compile), and the traced outputs' avals."""
    import jax

    from synapseml_tpu.observability.metrics import (MetricsRegistry,
                                                     set_registry)

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        fn = OnnxFunction(mb, **kw)
        outs = jax.eval_shape(fn._run_positional, feed)
    finally:
        set_registry(prev)
    fam = reg.snapshot()["families"].get("smt_onnx_float32_handoff_bytes")
    if fam is None:
        return None, outs
    (series,) = fam["series"]
    assert series["labels"] == [fn._jit.name]
    return series["value"], outs


def _policy_matmul_vs_gemm(policy, monkeypatch):
    from synapseml_tpu.onnx.ops import OPS

    rng = np.random.default_rng(3)
    x = rng.normal(size=(8, 16)).astype(np.float32)
    w = (rng.normal(size=(16, 6)) / 4).astype(np.float32)
    b = rng.normal(size=6).astype(np.float32)
    # the ops themselves: what the next node is handed
    dt = jnp.bfloat16 if policy == "bfloat16" else jnp.float32
    ctx = {"opset": 17, "accum_dtype": jnp.float32 if policy == "bfloat16" else None}
    xs, ws, bs = (jnp.asarray(v, dt) for v in (x, w, b))
    mm = OPS["Add"]([OPS["MatMul"]([xs, ws], {}, ctx), bs], {}, ctx)
    gemm = OPS["Gemm"]([xs, ws, bs], {}, ctx)
    assert mm.dtype == gemm.dtype == dt
    # and through a graph: float32 outputs either way
    fn = build_fn(
        [node("MatMul", ["x", "w"], ["y0"]), node("Add", ["y0", "b"], ["y"]),
         node("Gemm", ["x", "w", "b"], ["z"])],
        [value_info("x", np.float32, [None, 16])],
        [value_info("y", np.float32, [None, 6]), value_info("z", np.float32, [None, 6])],
        {"w": w, "b": b}, dtype_policy=policy)
    out = fn({"x": x})
    y, z = np.asarray(out["y"]), np.asarray(out["z"])
    assert y.dtype == z.dtype == np.float32
    if policy == "float32":
        np.testing.assert_array_equal(y, z)
        np.testing.assert_allclose(y, x @ w + b, rtol=1e-5, atol=1e-5)
    else:
        # MatMul rounds the product and then the sum, Gemm the sum alone:
        # one bfloat16 ulp (2**-8 relative) apart at most, plus the operands'
        np.testing.assert_allclose(y, z, rtol=2 ** -7, atol=2 ** -7)
        np.testing.assert_allclose(y, x @ w + b, rtol=3e-2, atol=3e-2)


def _float32_inside(op_type, monkeypatch):
    from synapseml_tpu.onnx.ops import OPS

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(3, 5, 32)) * 3, jnp.bfloat16)
    rest, attrs = [], {"axis": -1}
    if op_type == "LayerNormalization":
        rest = [jnp.asarray(rng.normal(size=32), jnp.bfloat16),
                jnp.asarray(rng.normal(size=32), jnp.bfloat16)]
        attrs["epsilon"] = 1e-12
    ctx = {"opset": 17}
    got = OPS[op_type]([x, *rest], attrs, ctx)
    want = OPS[op_type]([x.astype(jnp.float32), *rest], attrs, ctx)
    assert got.dtype == jnp.bfloat16 and want.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint16),
        np.asarray(want.astype(jnp.bfloat16)).view(np.uint16))
    if op_type == "Softmax":  # against jax's own, not the op under test
        import jax

        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint16),
            np.asarray(jax.nn.softmax(x.astype(jnp.float32), axis=-1)
                       .astype(jnp.bfloat16)).view(np.uint16))


def _float32_policy_program_is_the_parents(_, monkeypatch):
    import jax

    from synapseml_tpu.models.zoo import build_model_bytes
    from synapseml_tpu.onnx.ops import OPS

    mb = build_model_bytes("BERTTiny", seed=0)
    ids = np.random.default_rng(0).integers(0, 1000, (4, 16)).astype(np.int64)
    fn = OnnxFunction(mb)
    jaxpr = str(jax.make_jaxpr(fn._run_positional)(ids))
    out = {k: np.asarray(v) for k, v in fn({"input_ids": ids}).items()}
    monkeypatch.setitem(OPS, "MatMul", _parent_matmul)
    for k in ("Softmax", "LogSoftmax", "LayerNormalization"):
        monkeypatch.setitem(OPS, k, OPS[k].__wrapped__)
    parent = OnnxFunction(mb)
    parent_jaxpr = str(jax.make_jaxpr(parent._run_positional)(ids))
    parent_out = parent({"input_ids": ids})
    assert jaxpr == parent_jaxpr  # so no convert_element_type the parent lacked
    for k, v in out.items():
        np.testing.assert_array_equal(v, np.asarray(parent_out[k]))
    assert _handoff_bytes(mb, ids)[0] is None  # the gauge is the bfloat16 policy's


def _bfloat16_handoff(which, monkeypatch):
    from synapseml_tpu.models.zoo import build_model_bytes, vit
    from synapseml_tpu.onnx.ops import OPS

    if which == "bert_tiny":
        mb = build_model_bytes("BERTTiny", seed=0)
        feed = np.zeros((4, 16), np.int64)
    elif which == "vit_two_layers":
        mb = serialize_model(vit(image_size=32, layers=2, hidden=64, heads=2,
                                 num_classes=10))
        feed = np.zeros((2, 3, 32, 32), np.float32)
    else:  # a graph that asks for float32 itself keeps it, and the gauge says so
        w = np.ones((16, 6), np.float32)
        g = make_graph(
            [node("MatMul", ["x", "w"], ["y"]),
             node("Cast", ["y"], ["y32"], to=1),
             node("Softmax", ["y32"], ["p"], axis=-1)],
            "cast_before_softmax",
            [value_info("x", np.float32, [None, 16])],
            [value_info("p", np.float32, [None, 6])], {"w": w})
        mb = serialize_model(make_model(g, opset=17))
        feed = np.zeros((4, 16), np.float32)
    handed, outs = _handoff_bytes(mb, feed, dtype_policy="bfloat16")
    assert all(o.dtype == np.float32 for o in outs)
    if which == "explicit_cast":
        assert handed == 4 * 6 * 4  # y32 alone: p is an output, read by no node
        return
    assert handed == 0
    # the counter counts: with the parent's MatMul every product hands float32 on
    monkeypatch.setitem(OPS, "MatMul", _parent_matmul)
    assert _handoff_bytes(mb, feed, dtype_policy="bfloat16")[0] > 0


@pytest.mark.parametrize("case,arg", [
    (_policy_matmul_vs_gemm, "float32"),
    (_policy_matmul_vs_gemm, "bfloat16"),
    (_float32_inside, "Softmax"),
    (_float32_inside, "LogSoftmax"),
    (_float32_inside, "LayerNormalization"),
    (_float32_policy_program_is_the_parents, None),
    (_bfloat16_handoff, "bert_tiny"),
    (_bfloat16_handoff, "vit_two_layers"),
    (_bfloat16_handoff, "explicit_cast"),
], ids=lambda v: getattr(v, "__name__", v))
def test_bfloat16_policy_hands_on_bfloat16(case, arg, monkeypatch):
    """ISSUE 26: under ``dtype_policy="bfloat16"`` a tensor one node hands the
    next is bfloat16 and float32 lives inside an op; the float32 policy's
    program is what it was."""
    case(arg, monkeypatch)
