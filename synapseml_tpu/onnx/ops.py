"""ONNX operator implementations on JAX.

Each entry maps an ONNX op_type to ``fn(inputs, attrs, ctx) -> output | tuple``.
``inputs`` holds jnp arrays (traced under jit), numpy arrays (graph constants —
initializers, Constant nodes, and anything derived only from them or from *shapes*),
or None for omitted optional inputs. Numpy-ness is significant: ops that *need* static
values (Reshape target, Slice bounds, ...) require numpy inputs, which the executor
guarantees by constant-folding shape arithmetic during tracing (under ``jit`` shapes are
static, so ``Shape`` always yields numpy — this is how dynamic-shape chains in BERT-style
exports compile to static XLA programs; reference pins only dim 0 instead,
``ONNXModel.scala:357-362``).

Opset notes: handles both attribute-style (<13) and input-style (>=13) axes for
Squeeze/Unsqueeze/Reduce*, Clip min/max attrs (<11) vs inputs, Pad attrs (<11) vs
inputs, Slice attrs (<10) vs inputs.

TPU notes: convs/matmuls go through ``lax.conv_general_dilated``/``jnp.matmul`` and land
on the MXU; XLA picks layouts (NCHW semantics preserved from ONNX). bf16 execution is
applied at the executor level by dtype policy (it casts inputs and weights); an op's
part is to hand on the dtype of its operands: products return their float32 accumulator
rounded to it, Softmax/LogSoftmax/LayerNormalization reduce in float32 inside.
"""

from __future__ import annotations

import functools
import operator
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.lazyimport import lazy_import

# resolved on first attribute access inside an op body — importing the
# 129-op registry (or synapseml_tpu.onnx) stays jax-free (lint SMT001)
jax = lazy_import("jax")
jnp = lazy_import("jax.numpy")
lax = lazy_import("jax.lax")

OPS: Dict[str, Callable] = {}


def _lazy_fn(spec: str) -> Callable:
    """Resolve a dotted ``jnp.add`` / ``jax.nn.relu`` spec at *call* time
    (attribute access on the lazy proxies), so building the op tables
    below never imports jax."""
    root, _, rest = spec.partition(".")
    base = {"jax": jax, "jnp": jnp, "lax": lax}[root]

    def call(*args, **kw):
        fn = base
        for part in rest.split("."):
            fn = getattr(fn, part)
        return fn(*args, **kw)

    return call


def op(*names: str):
    def deco(fn):
        for n in names:
            OPS[n] = fn
        return fn

    return deco


def _static(v, what: str) -> np.ndarray:
    """Require a graph-constant (numpy) value; informative error otherwise."""
    if v is None:
        raise ValueError(f"{what}: missing required static input")
    if isinstance(v, np.ndarray) or np.isscalar(v):
        return np.asarray(v)
    raise ValueError(
        f"{what} must be a graph constant (initializer / shape-derived), got a traced "
        f"array; this graph has genuinely data-dependent shapes, which XLA cannot compile"
    )


def _ints(v, what: str) -> List[int]:
    return [int(x) for x in np.atleast_1d(_static(v, what))]


def _axis_list(attrs, inputs, idx, what, default=None):
    """axes from attrs (opset<13) or inputs[idx] (>=13)."""
    if attrs.get("axes") is not None:
        return [int(a) for a in attrs["axes"]]
    if len(inputs) > idx and inputs[idx] is not None:
        return _ints(inputs[idx], what)
    return default


# ---------------------------------------------------------------------------------
# elementwise math
# ---------------------------------------------------------------------------------

_BINOPS = {
    "Add": "jnp.add", "Sub": "jnp.subtract", "Mul": "jnp.multiply",
    "Div": "jnp.divide",
    "Pow": "jnp.power", "Mod": "jnp.mod",
    "PRelu": lambda x, s: jnp.where(x >= 0, x, x * s),
    "And": "jnp.logical_and", "Or": "jnp.logical_or", "Xor": "jnp.logical_xor",
    "BitwiseAnd": "jnp.bitwise_and", "BitwiseOr": "jnp.bitwise_or",
    "BitwiseXor": "jnp.bitwise_xor",
}
for _name, _fn in _BINOPS.items():
    _fn = _fn if callable(_fn) else _lazy_fn(_fn)
    OPS[_name] = (lambda f: lambda inputs, attrs, ctx: f(inputs[0], inputs[1]))(_fn)

_UNOPS = {
    "Sqrt": "jnp.sqrt", "Exp": "jnp.exp", "Log": "jnp.log", "Abs": "jnp.abs",
    "Neg": "jnp.negative",
    "Floor": "jnp.floor", "Ceil": "jnp.ceil", "Reciprocal": lambda x: 1.0 / x,
    "Sign": "jnp.sign", "Erf": "jax.scipy.special.erf",
    "Not": "jnp.logical_not",
    "Relu": "jax.nn.relu", "Sigmoid": "jax.nn.sigmoid", "Tanh": "jnp.tanh",
    "Softplus": "jax.nn.softplus", "Softsign": "jax.nn.soft_sign",
    "Identity": lambda x: x,
    "IsNaN": "jnp.isnan", "Sin": "jnp.sin", "Cos": "jnp.cos", "Tan": "jnp.tan",
    "Asin": "jnp.arcsin", "Acos": "jnp.arccos", "Atan": "jnp.arctan",
    "Sinh": "jnp.sinh", "Cosh": "jnp.cosh", "Asinh": "jnp.arcsinh",
    "Acosh": "jnp.arccosh",
    "Atanh": "jnp.arctanh", "BitwiseNot": "jnp.bitwise_not",
}
for _name, _fn in _UNOPS.items():
    _fn = _fn if callable(_fn) else _lazy_fn(_fn)
    OPS[_name] = (lambda f: lambda inputs, attrs, ctx: f(inputs[0]))(_fn)


@op("Round")
def _round(inputs, attrs, ctx):
    return jnp.round(inputs[0])  # banker's rounding matches ONNX spec


_COMPARE = {"Equal": _lazy_fn("jnp.equal"), "Greater": _lazy_fn("jnp.greater"),
            "GreaterOrEqual": _lazy_fn("jnp.greater_equal"),
            "Less": _lazy_fn("jnp.less"),
            "LessOrEqual": _lazy_fn("jnp.less_equal")}


@op("Equal", "Greater", "GreaterOrEqual", "Less", "LessOrEqual")
def _compare(inputs, attrs, ctx):
    return _COMPARE[ctx["op_type"]](inputs[0], inputs[1])


@op("Min", "Max", "Sum", "Mean")
def _variadic(inputs, attrs, ctx):
    vals = [v for v in inputs if v is not None]
    red = {"Min": jnp.minimum, "Max": jnp.maximum}.get(ctx["op_type"])
    if red is not None:
        return functools.reduce(red, vals)
    s = functools.reduce(jnp.add, vals)
    return s / len(vals) if ctx["op_type"] == "Mean" else s


@op("Clip")
def _clip(inputs, attrs, ctx):
    lo = attrs.get("min") if attrs.get("min") is not None else (inputs[1] if len(inputs) > 1 else None)
    hi = attrs.get("max") if attrs.get("max") is not None else (inputs[2] if len(inputs) > 2 else None)
    return jnp.clip(inputs[0], lo, hi)


@op("LeakyRelu")
def _leaky(inputs, attrs, ctx):
    return jax.nn.leaky_relu(inputs[0], attrs.get("alpha", 0.01))


@op("Elu")
def _elu(inputs, attrs, ctx):
    return jax.nn.elu(inputs[0], attrs.get("alpha", 1.0))


@op("Selu")
def _selu(inputs, attrs, ctx):
    a = attrs.get("alpha", 1.6732632423543772)
    g = attrs.get("gamma", 1.0507009873554805)
    x = inputs[0]
    return g * jnp.where(x > 0, x, a * (jnp.exp(x) - 1.0))


@op("Celu")
def _celu(inputs, attrs, ctx):
    return jax.nn.celu(inputs[0], attrs.get("alpha", 1.0))


@op("HardSigmoid")
def _hard_sigmoid(inputs, attrs, ctx):
    a, b = attrs.get("alpha", 0.2), attrs.get("beta", 0.5)
    return jnp.clip(a * inputs[0] + b, 0.0, 1.0)


@op("HardSwish")
def _hard_swish(inputs, attrs, ctx):
    x = inputs[0]
    return x * jnp.clip(x / 6.0 + 0.5, 0.0, 1.0)


@op("Mish")
def _mish(inputs, attrs, ctx):
    x = inputs[0]
    return x * jnp.tanh(jax.nn.softplus(x))


def _float32_inside(fn):
    """A bfloat16 first input is computed on as its float32 upcast and the
    result rounded once to bfloat16: reductions (softmax's sum, a norm's mean
    and variance) keep float32 inside the op while the tensor handed on stays
    narrow. GELU has no reduction and is here for its rounding steps: in
    bfloat16 every product of its formula rounds to eight bits, in float32
    only the result does. On the TPU the upcast fuses into the op; any other
    dtype passes through untouched."""

    @functools.wraps(fn)
    def wrapped(inputs, attrs, ctx):
        x = inputs[0]
        if getattr(x, "dtype", None) != jnp.bfloat16:
            return fn(inputs, attrs, ctx)
        out = fn([x.astype(jnp.float32), *inputs[1:]], attrs, ctx)
        return out.astype(jnp.bfloat16)

    return wrapped


# below this the exact GELU is under 1.1e-7 in size, and 1 + erf is what
# float32 leaves of a cancellation: zero is the closer answer
_GELU_ZERO_BELOW = -5.5


@_float32_inside
def _gelu_erf_float32(inputs, attrs, ctx):
    x = inputs[0]
    y = 0.5 * x * (1.0 + lax.erf(x * np.float32(np.sqrt(0.5))))
    return jnp.where(x < _GELU_ZERO_BELOW, 0.0, y)


@op("Gelu")
def _gelu(inputs, attrs, ctx):
    """Opset 20 ``Gelu``. ``approximate="tanh"`` and every input type but
    bfloat16 are ``jax.nn.gelu``, whose exact form is ``0.5 x erfc(-x/sqrt 2)``
    with BOTH of ``erfc``'s branches computed for every element: some 67
    vector operations, which buy relative accuracy in the far negative tail
    (3.8e-7 absolute on [-10, 10] in float32 against the form below's 1.1e-6),
    and float32, float64 and float16 outputs can hold that. A bfloat16 output
    cannot, so a bfloat16 input takes the one-branch ``0.5 x (1 + erf(x/sqrt
    2))`` on its float32 upcast, rounded once (``_float32_inside``): half the
    vector work in a feed-forward fusion's epilogue, and the correctly rounded
    bfloat16 of the exact GELU at all but 27 of the 65,280 finite inputs (the
    bfloat16 ``erfc`` form: all but 771). The form's hazard is the tail, where
    ``1 + erf`` is a float32 residue (6e-8 at -5.7) that a large ``|x|``
    multiplies up: below ``_GELU_ZERO_BELOW`` = -5.5, where ``|gelu|`` <
    1.1e-7, the answer is zero. The trace notes which form a node took
    (``smt_onnx_gelu_lowering_total{form}``)."""
    x = inputs[0]
    if attrs.get("approximate", "none") == "tanh":
        return jax.nn.gelu(x, approximate=True)
    if getattr(x, "dtype", None) == jnp.bfloat16:
        _note(ctx, "gelu", "erf_float32")
        return _gelu_erf_float32(inputs, attrs, ctx)
    _note(ctx, "gelu", "erfc")
    return jax.nn.gelu(x, approximate=False)


@op("Softmax")
@_float32_inside
def _softmax(inputs, attrs, ctx):
    axis = attrs.get("axis", -1 if ctx["opset"] >= 13 else 1)
    if ctx["opset"] >= 13:
        return jax.nn.softmax(inputs[0], axis=axis)
    # pre-13: flatten trailing dims from axis, softmax over the flattened tail
    x = inputs[0]
    shape = x.shape
    axis = axis % x.ndim  # spec coerces negative axis to axis + rank
    lead = int(np.prod(shape[:axis])) if axis > 0 else 1
    flat = x.reshape(lead, -1)
    return jax.nn.softmax(flat, axis=-1).reshape(shape)


@op("LogSoftmax")
@_float32_inside
def _log_softmax(inputs, attrs, ctx):
    axis = attrs.get("axis", -1 if ctx["opset"] >= 13 else 1)
    return jax.nn.log_softmax(inputs[0], axis=axis)


@op("Einsum")
def _einsum(inputs, attrs, ctx):
    return jnp.einsum(attrs["equation"], *[v for v in inputs if v is not None])


@op("CumSum")
def _cumsum(inputs, attrs, ctx):
    axis = int(_static(inputs[1], "CumSum.axis"))
    x = inputs[0]
    if attrs.get("reverse", 0):
        x = jnp.flip(x, axis)
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive", 0):
        out = jnp.roll(out, 1, axis)
        idx = [slice(None)] * out.ndim
        idx[axis] = 0
        out = out.at[tuple(idx)].set(0)
    if attrs.get("reverse", 0):
        out = jnp.flip(out, axis)
    return out


# ---------------------------------------------------------------------------------
# matmul / gemm
# ---------------------------------------------------------------------------------

@op("MatMul")
def _matmul(inputs, attrs, ctx):
    a, b = inputs[0], inputs[1]
    out = jnp.matmul(a, b, preferred_element_type=ctx.get("accum_dtype"))
    # the accumulator's dtype stays inside the op, as in Gemm and Conv: two
    # bfloat16 operands hand on bfloat16, a float32 operand keeps float32
    dtype = jnp.promote_types(a.dtype, b.dtype)
    return out.astype(dtype) if out.dtype != dtype else out


@op("Gemm")
def _gemm(inputs, attrs, ctx):
    a, b = inputs[0], inputs[1]
    # transA/transB fold into the contraction dims: no transposed copy is
    # ever materialized, so a device-resident (sharded/fsdp-stored) B
    # traces identically to a host-constant B
    ca = 0 if attrs.get("transA", 0) else 1
    cb = 1 if attrs.get("transB", 0) else 0
    out = attrs.get("alpha", 1.0) * lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())),
        preferred_element_type=ctx.get("accum_dtype"))
    if len(inputs) > 2 and inputs[2] is not None:
        out = out + attrs.get("beta", 1.0) * inputs[2]
    return out.astype(a.dtype) if out.dtype != a.dtype else out


# ---------------------------------------------------------------------------------
# convolution / pooling
# ---------------------------------------------------------------------------------

def _resolve_pads(attrs, spatial_rank: int, x_shape, k_shape, strides, dilations):
    """ONNX pads [x1b,x2b,...,x1e,x2e,...] or auto_pad."""
    auto = attrs.get("auto_pad", "NOTSET")
    if auto in ("NOTSET", ""):
        pads = attrs.get("pads") or [0] * (2 * spatial_rank)
        return [(int(pads[i]), int(pads[i + spatial_rank])) for i in range(spatial_rank)]
    if auto == "VALID":
        return [(0, 0)] * spatial_rank
    # SAME_UPPER / SAME_LOWER
    out = []
    for i in range(spatial_rank):
        in_dim = x_shape[2 + i]
        eff_k = (k_shape[i] - 1) * dilations[i] + 1
        out_dim = -(-in_dim // strides[i])
        total = max(0, (out_dim - 1) * strides[i] + eff_k - in_dim)
        lo = total // 2 if auto == "SAME_UPPER" else (total + 1) // 2
        out.append((lo, total - lo))
    return out


@op("Conv")
def _conv(inputs, attrs, ctx):
    x, w = inputs[0], inputs[1]
    b = inputs[2] if len(inputs) > 2 else None
    rank = x.ndim - 2
    strides = [int(s) for s in attrs.get("strides", [1] * rank)]
    dilations = [int(d) for d in attrs.get("dilations", [1] * rank)]
    groups = int(attrs.get("group", 1))
    kernel_spatial = w.shape[2:]
    pads = _resolve_pads(attrs, rank, x.shape, kernel_spatial, strides, dilations)
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NCHW"[: rank + 2], "OIHW"[: rank + 2], "NCHW"[: rank + 2])
                                    if rank <= 2 else
                                    ("NCDHW", "OIDHW", "NCDHW"))
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pads, rhs_dilation=dilations,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=ctx.get("accum_dtype"),
    )
    if out.dtype != x.dtype:
        out = out.astype(x.dtype)
    if b is not None:
        out = out + b.reshape((1, -1) + (1,) * rank)
    return out


@op("ConvTranspose")
def _conv_transpose(inputs, attrs, ctx):
    x, w = inputs[0], inputs[1]
    b = inputs[2] if len(inputs) > 2 else None
    rank = x.ndim - 2
    strides = [int(s) for s in attrs.get("strides", [1] * rank)]
    dilations = [int(d) for d in attrs.get("dilations", [1] * rank)]
    groups = int(attrs.get("group", 1))
    if groups != 1:
        raise NotImplementedError("grouped ConvTranspose not supported yet")
    kernel_spatial = w.shape[2:]
    pads = _resolve_pads(attrs, rank, x.shape, kernel_spatial, strides, dilations)
    out_pads = [int(p) for p in attrs.get("output_padding", [0] * rank)]
    # ONNX W layout for ConvTranspose is (C_in, C_out/groups, *k); transpose to OIHW.
    w_t = jnp.swapaxes(w, 0, 1)
    w_t = jnp.flip(w_t, axis=tuple(range(2, 2 + rank)))
    # conv_transpose via input dilation
    padding = []
    for i in range(rank):
        eff_k = (kernel_spatial[i] - 1) * dilations[i] + 1
        padding.append((eff_k - 1 - pads[i][0], eff_k - 1 - pads[i][1] + out_pads[i]))
    dn = lax.conv_dimension_numbers(x.shape, w_t.shape,
                                    ("NCHW"[: rank + 2], "OIHW"[: rank + 2], "NCHW"[: rank + 2]))
    out = lax.conv_general_dilated(
        x, w_t, window_strides=[1] * rank, padding=padding, lhs_dilation=strides,
        rhs_dilation=dilations, dimension_numbers=dn,
        preferred_element_type=ctx.get("accum_dtype"),
    )
    if out.dtype != x.dtype:
        out = out.astype(x.dtype)
    if b is not None:
        out = out + b.reshape((1, -1) + (1,) * rank)
    return out


def _pool(x, kernel, strides, pads, reducer, init, count_include_pad, ceil_mode=0):
    rank = len(kernel)
    if ceil_mode:
        # extend end-padding so ceil-division windows fit
        new_pads = []
        for i in range(rank):
            in_dim = x.shape[2 + i] + pads[i][0] + pads[i][1]
            rem = (in_dim - kernel[i]) % strides[i]
            extra = (strides[i] - rem) % strides[i] if rem else 0
            new_pads.append((pads[i][0], pads[i][1] + extra))
        pads = new_pads
    window = (1, 1) + tuple(kernel)
    strides_full = (1, 1) + tuple(strides)
    pads_full = ((0, 0), (0, 0)) + tuple(pads)
    out = lax.reduce_window(x, init, reducer, window, strides_full, pads_full)
    return out, pads


@op("MaxPool")
def _maxpool(inputs, attrs, ctx):
    x = inputs[0]
    kernel = [int(k) for k in attrs["kernel_shape"]]
    rank = len(kernel)
    strides = [int(s) for s in attrs.get("strides", [1] * rank)]
    dil = [int(d) for d in attrs.get("dilations", [1] * rank)]
    if any(d != 1 for d in dil):
        raise NotImplementedError("dilated MaxPool not supported")
    pads = _resolve_pads(attrs, rank, x.shape, kernel, strides, [1] * rank)
    neg_inf = jnp.array(-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                        else jnp.iinfo(x.dtype).min, dtype=x.dtype)
    out, _ = _pool(x, kernel, strides, pads, lax.max, neg_inf, False,
                   attrs.get("ceil_mode", 0))
    return out


@op("AveragePool")
def _avgpool(inputs, attrs, ctx):
    x = inputs[0]
    kernel = [int(k) for k in attrs["kernel_shape"]]
    rank = len(kernel)
    strides = [int(s) for s in attrs.get("strides", [1] * rank)]
    pads = _resolve_pads(attrs, rank, x.shape, kernel, strides, [1] * rank)
    include_pad = attrs.get("count_include_pad", 0)
    out, eff_pads = _pool(x, kernel, strides, pads, lax.add, jnp.array(0, x.dtype),
                          include_pad, attrs.get("ceil_mode", 0))
    if include_pad:
        return out / float(np.prod(kernel))
    ones = jnp.ones(x.shape[2:], dtype=x.dtype)[None, None]
    counts, _ = _pool(ones, kernel, strides, eff_pads, lax.add, jnp.array(0, x.dtype), True)
    return out / counts


@op("GlobalAveragePool")
def _gap(inputs, attrs, ctx):
    x = inputs[0]
    return jnp.mean(x, axis=tuple(range(2, x.ndim)), keepdims=True)


@op("GlobalMaxPool")
def _gmp(inputs, attrs, ctx):
    x = inputs[0]
    return jnp.max(x, axis=tuple(range(2, x.ndim)), keepdims=True)


@op("LRN")
def _lrn(inputs, attrs, ctx):
    x = inputs[0]
    size = int(attrs["size"])
    alpha, beta, bias = attrs.get("alpha", 1e-4), attrs.get("beta", 0.75), attrs.get("bias", 1.0)
    sq = x * x
    half = size // 2
    pads = ((0, 0), (half, size - 1 - half)) + ((0, 0),) * (x.ndim - 2)
    window = (1, size) + (1,) * (x.ndim - 2)
    summed = lax.reduce_window(sq, jnp.array(0, x.dtype), lax.add, window, (1,) * x.ndim, pads)
    return x / jnp.power(bias + (alpha / size) * summed, beta)


# ---------------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------------

@op("BatchNormalization")
def _batchnorm(inputs, attrs, ctx):
    x, scale, bias, mean, var = inputs[:5]
    eps = attrs.get("epsilon", 1e-5)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    inv = lax.rsqrt(var.astype(jnp.float32) + eps).astype(x.dtype)
    return (x - mean.reshape(shape)) * (scale * inv).reshape(shape) + bias.reshape(shape)


@op("InstanceNormalization")
def _instancenorm(inputs, attrs, ctx):
    x, scale, bias = inputs[:3]
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return (x - mean) * lax.rsqrt(var + eps) * scale.reshape(shape) + bias.reshape(shape)


@op("LayerNormalization")
@_float32_inside
def _layernorm(inputs, attrs, ctx):
    x = inputs[0]
    scale = inputs[1] if len(inputs) > 1 else None
    bias = inputs[2] if len(inputs) > 2 else None
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(axis % x.ndim, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias
    return out


@op("RMSNormalization")
@_float32_inside
def _rmsnorm(inputs, attrs, ctx):
    """Opset 23: ``x * rsqrt(mean(x², axes from axis on) + epsilon) * scale``;
    ``stash_type`` is float32 (the only value the op defines), which
    ``_float32_inside`` gives a bfloat16 input."""
    x, scale = inputs[0], inputs[1]
    if int(attrs.get("stash_type", 1)) != 1:
        raise NotImplementedError("RMSNormalization: stash_type must be 1")
    axes = tuple(range(attrs.get("axis", -1) % x.ndim, x.ndim))
    mean_sq = jnp.mean(jnp.square(x), axis=axes, keepdims=True)
    return x * lax.rsqrt(mean_sq + attrs.get("epsilon", 1e-5)) * scale


@op("GroupNormalization")
def _groupnorm(inputs, attrs, ctx):
    x, scale, bias = inputs[:3]
    g = int(attrs["num_groups"])
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[:2]
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return out * scale.reshape(shape) + bias.reshape(shape)


@op("Dropout")
def _dropout(inputs, attrs, ctx):
    # inference-mode: identity (+ all-true mask as optional second output)
    x = inputs[0]
    return (x, jnp.ones(x.shape, dtype=bool))


# ---------------------------------------------------------------------------------
# shape / data movement  (static-shape discipline: see module docstring)
# ---------------------------------------------------------------------------------

@op("Shape")
def _shape(inputs, attrs, ctx):
    shp = np.asarray(np.shape(inputs[0]), dtype=np.int64)
    start = attrs.get("start", 0)
    end = attrs.get("end")
    return shp[start:end]


@op("Size")
def _size(inputs, attrs, ctx):
    return np.asarray(int(np.prod(np.shape(inputs[0]))), dtype=np.int64)


@op("Reshape")
def _reshape(inputs, attrs, ctx):
    if attrs.get("shape") is not None:  # opset<5 attribute form
        target = [int(s) for s in attrs["shape"]]
    else:
        target = _ints(inputs[1], "Reshape.shape")
    x = inputs[0]
    if attrs.get("allowzero", 0) == 0:
        target = [x.shape[i] if s == 0 else s for i, s in enumerate(target)]
    return jnp.reshape(x, target)


@op("Flatten")
def _flatten(inputs, attrs, ctx):
    x = inputs[0]
    axis = attrs.get("axis", 1)
    if axis < 0:
        axis += x.ndim
    lead = int(np.prod(x.shape[:axis])) if axis else 1
    return jnp.reshape(x, (lead, -1))


@op("Transpose")
def _transpose(inputs, attrs, ctx):
    perm = attrs.get("perm")
    x = inputs[0]
    return jnp.transpose(x, perm if perm is not None else tuple(reversed(range(x.ndim))))


@op("Concat")
def _concat(inputs, attrs, ctx):
    vals = [v for v in inputs if v is not None]
    if all(isinstance(v, np.ndarray) for v in vals):
        return np.concatenate([np.atleast_1d(v) for v in vals], axis=attrs.get("axis", 0))
    return jnp.concatenate([jnp.atleast_1d(v) for v in vals], axis=attrs.get("axis", 0))


@op("Split")
def _split(inputs, attrs, ctx):
    x = inputs[0]
    axis = attrs.get("axis", 0)
    splits = attrs.get("split")
    if splits is None and len(inputs) > 1 and inputs[1] is not None:
        splits = _ints(inputs[1], "Split.split")
    n_out = ctx["n_outputs"]
    if splits is None:
        dim = x.shape[axis]
        base = -(-dim // n_out) if attrs.get("num_outputs") else dim // n_out
        splits = [base] * (n_out - 1) + [dim - base * (n_out - 1)]
    idx = np.cumsum(splits)[:-1]
    return tuple(jnp.split(x, idx, axis=axis))


@op("Slice")
def _slice(inputs, attrs, ctx):
    x = inputs[0]
    if attrs.get("starts") is not None:  # opset<10 attribute form
        starts, ends = list(attrs["starts"]), list(attrs["ends"])
        axes = list(attrs.get("axes", range(len(starts))))
        steps = [1] * len(starts)
    else:
        starts = _ints(inputs[1], "Slice.starts")
        ends = _ints(inputs[2], "Slice.ends")
        axes = _ints(inputs[3], "Slice.axes") if len(inputs) > 3 and inputs[3] is not None \
            else list(range(len(starts)))
        steps = _ints(inputs[4], "Slice.steps") if len(inputs) > 4 and inputs[4] is not None \
            else [1] * len(starts)
    idx = [slice(None)] * x.ndim
    for s, e, a, st in zip(starts, ends, axes, steps):
        a = a % x.ndim
        idx[a] = slice(s if s > -(1 << 62) else None,
                       e if -(1 << 62) < e < (1 << 62) else None, st)
    return x[tuple(idx)]


@op("Gather")
def _gather(inputs, attrs, ctx):
    x, idx = inputs[0], inputs[1]
    axis = attrs.get("axis", 0)
    if isinstance(x, np.ndarray) and isinstance(idx, np.ndarray):
        return np.take(x, idx.astype(np.int64), axis=axis)
    return jnp.take(x, jnp.asarray(idx), axis=axis)


@op("GatherElements")
def _gather_elements(inputs, attrs, ctx):
    x, idx = inputs[0], jnp.asarray(inputs[1])
    axis = attrs.get("axis", 0)
    if axis % x.ndim == x.ndim - 1 and x.shape[-1] <= 1024 \
            and idx.shape[-1] <= 16 and not isinstance(x, np.ndarray):
        # a few picks out of a short last axis (a router's scores at its
        # top-k): a compare and a sum fuse, a gather of scalars crawls on
        # the TPU (4 ms for 6 of 128 over 65,536 rows); the same numbers
        idx = jnp.where(idx < 0, idx + x.shape[-1], idx)
        hit = idx[..., :, None] == jnp.arange(x.shape[-1], dtype=idx.dtype)
        return jnp.sum(jnp.where(hit, x[..., None, :], 0), axis=-1,
                       dtype=x.dtype)
    return jnp.take_along_axis(x, idx, axis=axis)


@op("GatherND")
def _gather_nd(inputs, attrs, ctx):
    x, idx = inputs[0], inputs[1]
    batch_dims = attrs.get("batch_dims", 0)
    if batch_dims:
        raise NotImplementedError("GatherND batch_dims>0")
    idx = jnp.asarray(idx)
    return x[tuple(jnp.moveaxis(idx, -1, 0))]


@op("ScatterND")
def _scatter_nd(inputs, attrs, ctx):
    data, indices, updates = inputs[:3]
    indices = jnp.asarray(indices)
    out = jnp.asarray(data)
    red = attrs.get("reduction", "none")
    at = out.at[tuple(jnp.moveaxis(indices, -1, 0))]
    if red == "add":
        return at.add(updates)
    if red == "mul":
        return at.multiply(updates)
    return at.set(updates)


@op("Squeeze")
def _squeeze(inputs, attrs, ctx):
    x = inputs[0]
    axes = _axis_list(attrs, inputs, 1, "Squeeze.axes")
    if axes is None:
        axes = [i for i, d in enumerate(np.shape(x)) if d == 1]
    if isinstance(x, np.ndarray):
        return np.squeeze(x, axis=tuple(a % x.ndim for a in axes))
    return jnp.squeeze(x, axis=tuple(a % x.ndim for a in axes))


@op("Unsqueeze")
def _unsqueeze(inputs, attrs, ctx):
    x = inputs[0]
    axes = _axis_list(attrs, inputs, 1, "Unsqueeze.axes")
    out_rank = np.ndim(x) + len(axes)
    axes = sorted(a % out_rank for a in axes)
    if isinstance(x, np.ndarray):
        return np.expand_dims(x, tuple(axes))
    return jnp.expand_dims(x, tuple(axes))


@op("Expand")
def _expand(inputs, attrs, ctx):
    target = _ints(inputs[1], "Expand.shape")
    x = inputs[0]
    # ONNX Expand uses bidirectional broadcast; jnp.broadcast_to needs exact target.
    in_shape = list(np.shape(x))
    rank = max(len(in_shape), len(target))
    in_shape = [1] * (rank - len(in_shape)) + in_shape
    target = [1] * (rank - len(target)) + list(target)
    final = [max(a, b) for a, b in zip(in_shape, target)]
    return jnp.broadcast_to(x, final)


@op("Tile")
def _tile(inputs, attrs, ctx):
    reps = _ints(inputs[1], "Tile.repeats")
    return jnp.tile(inputs[0], reps)


@op("Pad")
def _pad(inputs, attrs, ctx):
    x = inputs[0]
    mode = attrs.get("mode", "constant")
    if attrs.get("pads") is not None:  # opset<11
        pads = [int(p) for p in attrs["pads"]]
        cval = attrs.get("value", 0.0)
    else:
        pads = _ints(inputs[1], "Pad.pads")
        cval = inputs[2] if len(inputs) > 2 and inputs[2] is not None else 0.0
    rank = x.ndim
    axes = _ints(inputs[3], "Pad.axes") if len(inputs) > 3 and inputs[3] is not None \
        else list(range(rank))
    width = [(0, 0)] * rank
    half = len(pads) // 2
    for i, a in enumerate(axes):
        width[a % rank] = (pads[i], pads[i + half])
    if mode == "constant":
        cval_scalar = cval if np.isscalar(cval) else jnp.reshape(cval, ())
        return jnp.pad(x, width, constant_values=cval_scalar)
    jmode = {"reflect": "reflect", "edge": "edge", "wrap": "wrap"}[mode]
    return jnp.pad(x, width, mode=jmode)


@op("Cast", "CastLike")
def _cast(inputs, attrs, ctx):
    from .wire import DataType

    if ctx["op_type"] == "CastLike":
        dtype = np.asarray(inputs[1]).dtype if isinstance(inputs[1], np.ndarray) else inputs[1].dtype
    else:
        dtype = DataType.to_numpy(int(attrs["to"]))
    return inputs[0].astype(dtype)


def _qbroadcast(x, scale, zp, axis: int):
    """Per-axis quantization params broadcast against ``x``: a 1-D
    scale/zero_point lies along ``axis`` (ONNX per-channel form); scalars
    broadcast as-is. Returns jnp views ready for arithmetic."""
    scale = jnp.asarray(scale)
    if zp is not None:
        zp = jnp.asarray(zp)
    nd = jnp.ndim(x)
    if scale.ndim == 1 and nd > 1:
        shape = [1] * nd
        shape[axis % nd] = -1
        scale = scale.reshape(shape)
        if zp is not None and zp.ndim == 1:
            zp = zp.reshape(shape)
    return scale, zp


@op("QuantizeLinear")
def _quantize_linear(inputs, attrs, ctx):
    # y = saturate(round(x / y_scale) + y_zero_point), round half to even
    # (jnp.round IS banker's rounding); output dtype follows the
    # zero_point (uint8 when omitted, per spec)
    x, scale = inputs[0], inputs[1]
    zp = inputs[2] if len(inputs) > 2 else None
    qdtype = (np.dtype(np.uint8) if zp is None
              else np.asarray(zp).dtype if isinstance(zp, np.ndarray)
              else np.dtype(zp.dtype))
    scale, zp = _qbroadcast(x, scale, zp, int(attrs.get("axis", 1)))
    y = jnp.round(x / scale)
    if zp is not None:
        y = y + zp.astype(y.dtype)
    info = np.iinfo(qdtype)
    return jnp.clip(y, info.min, info.max).astype(qdtype)


@op("DequantizeLinear")
def _dequantize_linear(inputs, attrs, ctx):
    # y = (x - x_zero_point) * x_scale, in the scale's float dtype
    x, scale = inputs[0], inputs[1]
    zp = inputs[2] if len(inputs) > 2 else None
    scale, zp = _qbroadcast(x, scale, zp, int(attrs.get("axis", 1)))
    xf = jnp.asarray(x).astype(scale.dtype)
    if zp is not None:
        xf = xf - zp.astype(scale.dtype)
    return xf * scale


@op("DynamicQuantizeLinear")
def _dynamic_quantize_linear(inputs, attrs, ctx):
    # uint8 affine quantization with the data's own range (the range is
    # widened to include 0 so zero_point is always representable);
    # returns (y, y_scale, y_zero_point) exactly per spec
    x = jnp.asarray(inputs[0])
    xmax = jnp.maximum(jnp.max(x), 0.0)
    xmin = jnp.minimum(jnp.min(x), 0.0)
    scale = ((xmax - xmin) / 255.0).astype(jnp.float32)
    # all-zero input: the spec's scale is 0 — quantize against 1.0 to
    # keep the kernel finite (y and zero_point are all zero either way)
    safe = jnp.where(scale == 0, jnp.float32(1.0), scale)
    zp = jnp.clip(jnp.round(-xmin / safe), 0, 255)
    y = jnp.clip(jnp.round(x / safe) + zp, 0, 255).astype(jnp.uint8)
    return y, scale, zp.astype(jnp.uint8)


def _zp_shift(q, zp, axis: int):
    """Zero-centre a quantized (u)int8 operand in int32: widening BEFORE
    the zero_point subtraction keeps the accumulation exact (uint8 - 255
    underflows in-dtype). A 1-D zero_point lies along ``axis``."""
    q = jnp.asarray(q).astype(jnp.int32)
    if zp is None:
        return q
    zp = jnp.asarray(zp).astype(jnp.int32)
    if zp.ndim == 1 and q.ndim > 1:
        shape = [1] * q.ndim
        shape[axis % q.ndim] = -1
        zp = zp.reshape(shape)
    return q - zp


@op("MatMulInteger")
def _matmul_integer(inputs, attrs, ctx):
    # int32 accumulation over zero-centred operands; per spec a 1-D
    # a_zero_point is per-row (M axis of A), a 1-D b_zero_point is
    # per-column (N axis of B). Output is always int32.
    a = _zp_shift(inputs[0], inputs[2] if len(inputs) > 2 else None, -2)
    b = _zp_shift(inputs[1], inputs[3] if len(inputs) > 3 else None, -1)
    return jnp.matmul(a, b, preferred_element_type=jnp.int32)


@op("ConvInteger")
def _conv_integer(inputs, attrs, ctx):
    # Conv over zero-centred int32 operands (implicit padding therefore
    # represents x_zero_point, i.e. real zero — onnxruntime semantics);
    # w_zero_point may be per-output-channel (axis 0 of OIHW)
    x = _zp_shift(inputs[0], inputs[2] if len(inputs) > 2 else None, 0)
    w = _zp_shift(inputs[1], inputs[3] if len(inputs) > 3 else None, 0)
    rank = x.ndim - 2
    strides = [int(s) for s in attrs.get("strides", [1] * rank)]
    dilations = [int(d) for d in attrs.get("dilations", [1] * rank)]
    groups = int(attrs.get("group", 1))
    pads = _resolve_pads(attrs, rank, x.shape, w.shape[2:], strides, dilations)
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NCHW"[: rank + 2], "OIHW"[: rank + 2], "NCHW"[: rank + 2])
                                    if rank <= 2 else
                                    ("NCDHW", "OIDHW", "NCDHW"))
    return lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pads, rhs_dilation=dilations,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=jnp.int32,
    )


@op("QLinearConv")
def _qlinear_conv(inputs, attrs, ctx):
    # full requantizing Conv: ConvInteger's zero-centred int32
    # accumulation, an optional int32 bias (per spec already quantized
    # with scale x_scale*w_scale, zero_point 0 — added into the
    # accumulator), then rescale by x_scale*w_scale/y_scale, round half
    # to even, re-centre on y_zero_point and saturate to its dtype.
    # w_scale/w_zero_point may be per-output-channel (OIHW axis 0).
    x, x_scale, x_zp, w, w_scale, w_zp, y_scale, y_zp = inputs[:8]
    bias = inputs[8] if len(inputs) > 8 and inputs[8] is not None else None
    acc = _conv_integer([x, w, x_zp, w_zp], attrs, ctx)
    nd = acc.ndim

    def _chan(s):  # per-channel params lie along the output-channel axis
        s = jnp.asarray(s).astype(jnp.float32)
        return s.reshape((1, -1) + (1,) * (nd - 2)) if s.ndim == 1 else s

    if bias is not None:
        acc = acc + jnp.asarray(bias).astype(jnp.int32).reshape(
            (1, -1) + (1,) * (nd - 2))
    scale = jnp.asarray(x_scale).astype(jnp.float32) * _chan(w_scale) \
        / jnp.asarray(y_scale).astype(jnp.float32)
    qdtype = (np.asarray(y_zp).dtype if isinstance(y_zp, np.ndarray)
              else np.dtype(y_zp.dtype))
    y = jnp.round(acc.astype(jnp.float32) * scale) + _chan(y_zp)
    info = np.iinfo(qdtype)
    return jnp.clip(y, info.min, info.max).astype(qdtype)


@op("QLinearMatMul")
def _qlinear_matmul(inputs, attrs, ctx):
    # full requantizing matmul: int32 accumulate, rescale by
    # a_scale*b_scale/y_scale, round half to even, re-centre on
    # y_zero_point and saturate to its dtype. 1-D scales/zero_points are
    # per-row for a and y, per-column for b (same layout rule as
    # MatMulInteger).
    a, a_scale, a_zp, b, b_scale, b_zp, y_scale, y_zp = inputs[:8]
    acc = jnp.matmul(_zp_shift(a, a_zp, -2), _zp_shift(b, b_zp, -1),
                     preferred_element_type=jnp.int32)

    def _row(s):  # per-row params broadcast down the output's M axis
        s = jnp.asarray(s).astype(jnp.float32)
        return s.reshape(-1, 1) if s.ndim == 1 else s

    scale = _row(a_scale) * jnp.asarray(b_scale).astype(jnp.float32) \
        / _row(y_scale)
    qdtype = (np.asarray(y_zp).dtype if isinstance(y_zp, np.ndarray)
              else np.dtype(y_zp.dtype))
    y = jnp.round(acc.astype(jnp.float32) * scale) + _row(y_zp)
    info = np.iinfo(qdtype)
    return jnp.clip(y, info.min, info.max).astype(qdtype)


@op("Where")
def _where(inputs, attrs, ctx):
    c, a, b = inputs[:3]
    if all(isinstance(v, np.ndarray) for v in (c, a, b)):
        return np.where(c, a, b)
    return jnp.where(c, a, b)


@op("OneHot")
def _onehot(inputs, attrs, ctx):
    indices, depth, values = inputs[:3]
    axis = attrs.get("axis", -1)
    d = int(_static(depth, "OneHot.depth"))
    off_val, on_val = values[0], values[1]
    idx = jnp.asarray(indices)
    # spec: negative indices in [-depth, -1] wrap; anything else is all-off
    valid = (idx >= -d) & (idx <= d - 1)
    idx = jnp.where(valid, jnp.where(idx < 0, idx + d, idx), -1)
    oh = jax.nn.one_hot(idx, d, axis=axis)  # one_hot(-1) -> all zeros
    return oh * (on_val - off_val) + off_val


@op("Range")
def _range(inputs, attrs, ctx):
    start, limit, delta = (_static(v, "Range") for v in inputs[:3])
    return np.arange(start.item(), limit.item(), delta.item(),
                     dtype=np.asarray(start).dtype)


@op("ConstantOfShape")
def _constant_of_shape(inputs, attrs, ctx):
    from .wire import tensor_to_numpy

    shape = _ints(inputs[0], "ConstantOfShape.shape")
    t = attrs.get("value")
    if t is None:
        return np.zeros(shape, dtype=np.float32)
    v = tensor_to_numpy(t, external_dir=ctx.get("external_dir"))
    return np.full(shape, v.reshape(-1)[0], dtype=v.dtype)


@op("Constant")
def _constant(inputs, attrs, ctx):
    from .wire import tensor_to_numpy

    if attrs.get("value") is not None:
        return tensor_to_numpy(attrs["value"],
                               external_dir=ctx.get("external_dir"))
    for k in ("value_float", "value_int"):
        if attrs.get(k) is not None:
            return np.asarray(attrs[k])
    for k in ("value_floats", "value_ints"):
        if attrs.get(k) is not None:
            return np.asarray(attrs[k])
    raise ValueError("Constant node with no value attribute")


@op("DepthToSpace")
def _depth_to_space(inputs, attrs, ctx):
    x = inputs[0]
    b = int(attrs["blocksize"])
    n, c, h, w = x.shape
    if attrs.get("mode", "DCR") == "DCR":
        t = x.reshape(n, b, b, c // (b * b), h, w).transpose(0, 3, 4, 1, 5, 2)
    else:
        t = x.reshape(n, c // (b * b), b, b, h, w).transpose(0, 1, 4, 2, 5, 3)
    return t.reshape(n, c // (b * b), h * b, w * b)


@op("SpaceToDepth")
def _space_to_depth(inputs, attrs, ctx):
    x = inputs[0]
    b = int(attrs["blocksize"])
    n, c, h, w = x.shape
    t = x.reshape(n, c, h // b, b, w // b, b).transpose(0, 3, 5, 1, 2, 4)
    return t.reshape(n, c * b * b, h // b, w // b)


@op("Resize")
def _resize(inputs, attrs, ctx):
    x = inputs[0]
    mode = attrs.get("mode", "nearest")
    sizes = None
    if len(inputs) > 3 and inputs[3] is not None:
        sizes = _ints(inputs[3], "Resize.sizes")
    elif len(inputs) > 2 and inputs[2] is not None:
        scales = np.asarray(_static(inputs[2], "Resize.scales"), dtype=np.float64)
        if scales.size:
            sizes = [int(np.floor(s * d)) for s, d in zip(scales, x.shape)]
    if sizes is None:
        raise ValueError("Resize needs scales or sizes")
    method = {"nearest": "nearest", "linear": "linear", "cubic": "cubic"}[mode]
    return jax.image.resize(x, sizes, method=method)


@op("ArgMax", "ArgMin")
def _argminmax(inputs, attrs, ctx):
    axis = attrs.get("axis", 0)
    keepdims = attrs.get("keepdims", 1)
    fn = jnp.argmax if ctx["op_type"] == "ArgMax" else jnp.argmin
    x = inputs[0]
    if attrs.get("select_last_index", 0):
        x = jnp.flip(x, axis)
        out = x.shape[axis] - 1 - fn(x, axis=axis)
    else:
        out = fn(x, axis=axis)
    out = out.astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)
    return jnp.expand_dims(out, axis) if keepdims else out


@op("TopK")
def _topk(inputs, attrs, ctx):
    x = inputs[0]
    k = _ints(inputs[1], "TopK.k")[0] if len(inputs) > 1 else int(attrs["k"])
    axis = attrs.get("axis", -1)
    largest = attrs.get("largest", 1)
    xm = jnp.moveaxis(x, axis, -1)
    vals, idx = lax.top_k(xm if largest else -xm, k)
    if not largest:
        vals = -vals
    return (jnp.moveaxis(vals, -1, axis), jnp.moveaxis(idx, -1, axis))


@op("Trilu")
def _trilu(inputs, attrs, ctx):
    x = inputs[0]
    k = int(_static(inputs[1], "Trilu.k")) if len(inputs) > 1 and inputs[1] is not None else 0
    return jnp.triu(x, k) if attrs.get("upper", 1) else jnp.tril(x, k)


@op("IsInf")
def _isinf(inputs, attrs, ctx):
    x = inputs[0]
    pos = attrs.get("detect_positive", 1)
    neg = attrs.get("detect_negative", 1)
    out = jnp.zeros(jnp.shape(x), dtype=bool)
    if pos:
        out = out | (x == jnp.inf)
    if neg:
        out = out | (x == -jnp.inf)
    return out


# ---------------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------------

def _reduce(fn_np, fn_jnp, axes_from_input_opset: int):
    def impl(inputs, attrs, ctx):
        x = inputs[0]
        if ctx["opset"] >= axes_from_input_opset:
            axes = _axis_list({"axes": attrs.get("axes")}, inputs, 1, "Reduce.axes")
        else:
            axes = attrs.get("axes")
        keepdims = bool(attrs.get("keepdims", 1))
        if axes is None:
            if attrs.get("noop_with_empty_axes", 0):
                return x
            ax = None
        else:
            ax = tuple(int(a) for a in np.atleast_1d(axes))
        if isinstance(x, np.ndarray):
            return fn_np(x, axis=ax, keepdims=keepdims)
        return fn_jnp(x, axis=ax, keepdims=keepdims)

    return impl


OPS["ReduceSum"] = _reduce(np.sum, _lazy_fn("jnp.sum"), 13)
OPS["ReduceMean"] = _reduce(np.mean, _lazy_fn("jnp.mean"), 18)
OPS["ReduceMax"] = _reduce(np.max, _lazy_fn("jnp.max"), 18)
OPS["ReduceMin"] = _reduce(np.min, _lazy_fn("jnp.min"), 18)
OPS["ReduceProd"] = _reduce(np.prod, _lazy_fn("jnp.prod"), 18)
OPS["ReduceL1"] = _reduce(lambda x, axis, keepdims: np.sum(np.abs(x), axis=axis, keepdims=keepdims),
                          lambda x, axis, keepdims: jnp.sum(jnp.abs(x), axis=axis, keepdims=keepdims), 18)
OPS["ReduceL2"] = _reduce(lambda x, axis, keepdims: np.sqrt(np.sum(x * x, axis=axis, keepdims=keepdims)),
                          lambda x, axis, keepdims: jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=keepdims)), 18)
OPS["ReduceSumSquare"] = _reduce(lambda x, axis, keepdims: np.sum(x * x, axis=axis, keepdims=keepdims),
                                 lambda x, axis, keepdims: jnp.sum(x * x, axis=axis, keepdims=keepdims), 18)
OPS["ReduceLogSum"] = _reduce(lambda x, axis, keepdims: np.log(np.sum(x, axis=axis, keepdims=keepdims)),
                              lambda x, axis, keepdims: jnp.log(jnp.sum(x, axis=axis, keepdims=keepdims)), 18)
OPS["ReduceLogSumExp"] = _reduce(
    lambda x, axis, keepdims: np.log(np.sum(np.exp(x), axis=axis, keepdims=keepdims)),
    lambda x, axis, keepdims: jax.scipy.special.logsumexp(x, axis=axis, keepdims=keepdims), 18)


@op("If")
def _if(inputs, attrs, ctx):
    cond = inputs[0]
    then_fn, else_fn = ctx["subgraph_runner"](attrs["then_branch"]), ctx["subgraph_runner"](attrs["else_branch"])
    if isinstance(cond, np.ndarray):  # constant condition: fold at trace time
        return then_fn() if bool(cond) else else_fn()
    raise NotImplementedError(
        "If with traced condition not supported (branches may differ in shape); "
        "most exported models have constant conditions after shape specialization"
    )


@op("Loop")
def _loop(inputs, attrs, ctx):
    """``Loop`` with a trip count known at trace time and loop-carried values
    of fixed shape and type: one ``lax.fori_loop`` whose state is the carried
    values, so a buffer the body updates at computed positions (a key-value
    cache) stays one buffer. The body reads the enclosing graph's names
    (weights stay the program's arguments). Anything else raises by name: a
    trip count or a condition computed at run time, scan outputs, a carried
    value whose shape or type the body changes."""
    trips, cond, carried = inputs[0], inputs[1], list(inputs[2:])
    body, name = attrs["body"], ctx.get("node_name") or "loop"
    if trips is None or not _is_trace_constant(trips):
        raise NotImplementedError(
            f"Loop {name}: the trip count M must be a constant of the graph")
    if cond is not None and not (_is_trace_constant(cond) and bool(cond)):
        raise NotImplementedError(
            f"Loop {name}: a condition that is not the constant true")
    if len(body.input) != 2 + len(carried):
        raise ValueError(
            f"Loop {name}: {len(carried)} carried values for a body of "
            f"{len(body.input)} inputs")
    if len(body.output) != 1 + len(carried) \
            or ctx["n_outputs"] != len(carried):
        raise NotImplementedError(
            f"Loop {name}: scan outputs ({len(body.output) - 1} body outputs "
            f"for {len(carried)} carried values)")
    trips = int(np.asarray(trips).reshape(()))
    run = ctx["subgraph_runner"](body)
    state = tuple(jnp.asarray(v) for v in carried)
    notes = ctx.get("notes")
    if notes is None:
        notes = {}
    outer = notes.get("loop_factor", 1)
    _note(ctx, "loop_trips", name, amount=outer * trips)
    if outer == 1:  # an inner loop's state is part of its outer loop's
        _note(ctx, "loop_state_bytes",
              amount=sum(v.size * v.dtype.itemsize for v in state))

    def one_trip(i, state):
        outs = run(i, np.bool_(True), *state)
        outs = outs if isinstance(outs, tuple) else (outs,)
        if not (_is_trace_constant(outs[0]) and bool(outs[0])):
            raise NotImplementedError(
                f"Loop {name}: the body computes its condition")
        for vi, was, now in zip(body.input[2:], state, outs[1:]):
            if jnp.shape(now) != was.shape \
                    or jnp.result_type(now) != was.dtype:
                raise ValueError(
                    f"Loop {name}: carried value {vi.name!r} enters as "
                    f"{was.dtype}{list(was.shape)} and leaves as "
                    f"{jnp.result_type(now)}{list(jnp.shape(now))}")
        return tuple(jnp.asarray(v) for v in outs[1:])

    notes["loop_factor"] = outer * trips
    try:
        state = lax.fori_loop(0, trips, one_trip, state)
    finally:
        notes["loop_factor"] = outer
    return state if len(state) != 1 else state[0]


def _is_trace_constant(v) -> bool:
    return isinstance(v, (np.ndarray, np.generic, bool, int))


# ---------------------------------------------------------------------------------
# recurrent (LSTM / GRU)
# ---------------------------------------------------------------------------------

def _rnn_act(name: str) -> Callable:
    try:
        return {"Sigmoid": _lazy_fn("jax.nn.sigmoid"),
                "Tanh": _lazy_fn("jnp.tanh"),
                "Relu": _lazy_fn("jax.nn.relu")}[name]
    except KeyError:
        raise NotImplementedError(f"RNN activation {name!r}") from None


def _rnn_common(op_type: str, inputs, attrs, n_gates: int):
    """Shared LSTM/GRU front end: forward single-direction slices,
    combined bias, initial hidden state, optional pre-activation clip."""
    if attrs.get("layout", 0) != 0:
        raise NotImplementedError(f"{op_type} layout=1")
    direction = attrs.get("direction", "forward")
    if direction != "forward":
        raise NotImplementedError(f"{op_type} direction={direction!r}")
    x, w, r = inputs[0], inputs[1], inputs[2]
    seq_lens = inputs[4] if len(inputs) > 4 else None
    if seq_lens is not None and not (
            isinstance(seq_lens, np.ndarray) and np.all(seq_lens == x.shape[0])):
        raise NotImplementedError(f"{op_type} with ragged sequence_lens")
    hidden = int(r.shape[-1])
    w2, r2 = jnp.asarray(w[0]), jnp.asarray(r[0])  # (n_gates*H, I), (n_gates*H, H)
    b = inputs[3] if len(inputs) > 3 else None
    if b is not None:
        wb, rb = jnp.split(jnp.asarray(b[0]), 2)
    else:
        wb = rb = jnp.zeros((n_gates * hidden,), x.dtype)
    init_h = inputs[5] if len(inputs) > 5 else None
    h0 = (jnp.zeros((x.shape[1], hidden), x.dtype) if init_h is None
          else jnp.asarray(init_h[0]))
    clip = attrs.get("clip")
    squash = ((lambda v: jnp.clip(v, -clip, clip)) if clip is not None
              else (lambda v: v))
    return x, w2, r2, wb, rb, h0, hidden, squash


@op("LSTM")
def _lstm(inputs, attrs, ctx):
    """Single-layer forward LSTM via ``lax.scan``; gate order iofc, optional
    peepholes, outputs ``Y (S,1,B,H)``, ``Y_h (1,B,H)``, ``Y_c (1,B,H)``."""
    x, w2, r2, wb, rb, h0, hidden, squash = _rnn_common("LSTM", inputs, attrs, 4)
    acts = attrs.get("activations") or ["Sigmoid", "Tanh", "Tanh"]
    f, g, h_act = (_rnn_act(a) for a in acts[:3])
    init_c = inputs[6] if len(inputs) > 6 else None
    c0 = (jnp.zeros_like(h0) if init_c is None else jnp.asarray(init_c[0]))
    p = inputs[7] if len(inputs) > 7 else None
    if p is not None:
        pi, po, pf = jnp.split(jnp.asarray(p[0]), 3)
    else:
        pi = po = pf = jnp.zeros((hidden,), x.dtype)
    # the input projection has no step dependence: one batched matmul
    # outside the scan, only the H-recurrence stays sequential
    gx = jnp.matmul(x, w2.T) + wb + rb  # (S, B, 4H)

    def step(carry, xt):
        h, c = carry
        zi, zo, zf, zc = jnp.split(xt + jnp.matmul(h, r2.T), 4, axis=-1)
        i = f(squash(zi + pi * c))
        ft = f(squash(zf + pf * c))
        c_new = ft * c + i * g(squash(zc))
        o = f(squash(zo + po * c_new))
        return (o * h_act(c_new), c_new), o * h_act(c_new)

    (h_t, c_t), ys = lax.scan(step, (h0, c0), gx)
    return ys[:, None], h_t[None], c_t[None]


@op("GRU")
def _gru(inputs, attrs, ctx):
    """Single-layer forward GRU via ``lax.scan``; gate order zrh, both
    ``linear_before_reset`` modes, outputs ``Y (S,1,B,H)``, ``Y_h (1,B,H)``."""
    x, w2, r2, wb, rb, h0, hidden, squash = _rnn_common("GRU", inputs, attrs, 3)
    acts = attrs.get("activations") or ["Sigmoid", "Tanh"]
    f, g = _rnn_act(acts[0]), _rnn_act(acts[1])
    lbr = int(attrs.get("linear_before_reset", 0))
    rz, rr, rh = jnp.split(r2, 3)
    rbz, rbr, rbh = jnp.split(rb, 3)
    gx = jnp.matmul(x, w2.T) + wb  # (S, B, 3H)

    def step(h, xt):
        xz, xr, xh = jnp.split(xt, 3, axis=-1)
        z = f(squash(xz + jnp.matmul(h, rz.T) + rbz))
        r = f(squash(xr + jnp.matmul(h, rr.T) + rbr))
        if lbr:  # reset gate applied to the already-projected hidden state
            hh = g(squash(xh + r * (jnp.matmul(h, rh.T) + rbh)))
        else:
            hh = g(squash(xh + jnp.matmul(r * h, rh.T) + rbh))
        h_new = (1.0 - z) * hh + z * h
        return h_new, h_new

    h_t, ys = lax.scan(step, h0, gx)
    return ys[:, None], h_t[None]


# ---------------------------------------------------------------------------------
# attention and sparse experts
# ---------------------------------------------------------------------------------

def _kernels_on() -> bool:
    """Whether the Pallas TPU kernels (flash attention, the grouped matrix
    product) are what a program traced now will run on."""
    return jax.default_backend() == "tpu"


# What a traced program's notes publish (``OnnxFunction._record_notes``):
# by family, the metric a note feeds, declared in the registry it is given.
# Every family is labelled by the program (``fn``) first, then by the values
# ``_note`` names; a counter adds a trace's notes, a gauge is set to them.
NOTE_FAMILIES: Dict[str, Callable] = {
    "attention_lowering": lambda reg: reg.counter(
        "smt_onnx_attention_lowering_total",
        "Attention nodes of a traced program by lowering: flash (the "
        "Pallas kernel, scores never written), dense (materialised "
        "scores where the kernel could have served: not a TPU, or "
        "lengths that do not tile), cached (a mask over key positions "
        "that only the run knows, one head size of a multiple of 128: "
        "the Pallas kernel for a few queries against a cache, which "
        "reads it once as it lies and keeps the scores in VMEM) or "
        "masked (any other mask only the run knows, or no TPU: the "
        "grouped dense form is the lowering)",
        ("fn", "kind")),
    "gelu": lambda reg: reg.counter(
        "smt_onnx_gelu_lowering_total",
        "exact Gelu nodes of a traced program by form: erf_float32 (a "
        "bfloat16 input: one-branch erf on the float32 upcast, rounded "
        "once) or erfc (any wider input: jax.nn.gelu's two-branch form)",
        ("fn", "form")),
    "expert_combine": lambda reg: reg.counter(
        "smt_onnx_expert_combine_total",
        "ExpertFFN nodes of a traced program by how their product rows "
        "reach their tokens: held_first (a token's held picks first, "
        "the first few gathered for every token, the few beyond those "
        "added row by row)",
        ("fn", "form")),
    "expert_form": lambda reg: reg.counter(
        "smt_onnx_expert_form_total",
        "ExpertFFN nodes of a traced program by activation: relu2 (one "
        "up-projection) or swiglu (a gate and an up-projection)",
        ("fn", "form")),
    "expert_tile": lambda reg: reg.counter(
        "smt_onnx_expert_tile_total",
        "ExpertFFN nodes of a traced program by the row tile of their "
        "grouped products, which follows the pairs an expert is expected "
        "to get (512 at 512 pairs an expert or more)",
        ("fn", "rows")),
    "loop_trips": lambda reg: reg.gauge(
        "smt_onnx_loop_trips",
        "times a call of the newest traced program runs the body of "
        "each Loop node, outer loops multiplied in",
        ("fn", "loop"), merge="max"),
    "attention_widths": lambda reg: reg.counter(
        "smt_onnx_attention_widths_total",
        "Attention nodes of a traced program by the width of a head's "
        "queries and keys, of its values, and the key-value heads: which "
        "form of attention ran (latent attention expanded has values "
        "narrower than its keys; absorbed, one key-value head of latents)",
        ("fn", "qk", "v", "kv_heads")),
    "attention_flash_form": lambda reg: reg.counter(
        "smt_onnx_attention_flash_form_total",
        "Attention nodes of a traced program that run the flash kernel, "
        "by where it reads its operands: in_place ([batch, seq, heads x "
        "size] as the node got them, a head picked by the block index "
        "map) or heads_first (copies transposed to [batch x heads, seq, "
        "size] in HBM, and the result back: value heads that are no "
        "whole 128-lane blocks)",
        ("fn", "form")),
    "selective_scan": lambda reg: reg.counter(
        "smt_onnx_selective_scan_lowering_total",
        "SelectiveScan nodes of a traced program by lowering: kernel "
        "(the Pallas kernel: the state stays in VMEM across positions), "
        "step (one position, a generating loop's body: plain jax.numpy "
        "over the state) or scan (lax.scan over positions, the state "
        "crossing HBM every position: not a TPU, or shapes that do not "
        "tile)",
        ("fn", "form")),
    "gated_delta": lambda reg: reg.counter(
        "smt_onnx_gated_delta_lowering_total",
        "GatedDeltaRule nodes of a traced program by lowering: "
        "chunked_kernel (more than one position: the WY form as a "
        "Pallas kernel, a row's state and a chunk's products in VMEM), "
        "chunked (more than one position: the WY form as XLA's matrix "
        "products over chunks: not a TPU, or shapes the kernel does not "
        "take), kernel (one position: the Pallas kernel reads and "
        "writes the state once, in place) or step (one position, plain "
        "jax.numpy: not a TPU, or shapes the kernel does not take)",
        ("fn", "form")),
    "loop_state_bytes": lambda reg: reg.gauge(
        "smt_onnx_loop_state_bytes",
        "bytes the outermost Loop nodes of the newest traced "
        "program carry from trip to trip (a key-value cache)",
        ("fn",), merge="max"),
    "recurrent_state_bytes": lambda reg: reg.gauge(
        "smt_onnx_recurrent_state_bytes",
        "bytes of state the single-position SelectiveScan and "
        "GatedDeltaRule nodes of the newest traced program take in "
        "(and hand on as many): what a generating pass streams "
        "beside the weights",
        ("fn",), merge="max"),
    "expert_pairs": lambda reg: reg.gauge(
        "smt_onnx_expert_pairs",
        "(token, pick) pairs one call of the newest traced program "
        "presents to its ExpertFFN nodes: what their grouped "
        "products are sized for",
        ("fn",), merge="max"),
    "experts_held": lambda reg: reg.gauge(
        "smt_onnx_experts_held",
        "experts held by the ExpertFFN nodes of the newest traced "
        "program, summed over nodes",
        ("fn",), merge="max"),
    "expert_chunk_rows": lambda reg: reg.gauge(
        "smt_onnx_expert_chunk_rows",
        "sorted (token, pick) pairs the ExpertFFN nodes of the newest "
        "traced program gather and multiply at a time: the smallest "
        "chunk of any node",
        ("fn",), merge="max"),
    "sparse_attention": lambda reg: reg.counter(
        "smt_onnx_sparse_attention_lowering_total",
        "SparseAttention nodes of a traced program by lowering: "
        "prompt_kernel (queries against their own keys: the indexer's "
        "Pallas kernel picks each query's keys by bisection, a blockwise "
        "Pallas kernel attends to them; no [S, S] array), decode_kernel "
        "(one query a row against the caches: the indexer's kernel, the "
        "chosen positions in order, a Pallas kernel that DMAs those rows "
        "alone) or plain (jax.numpy with lax.top_k: not a TPU, or shapes "
        "the kernels do not take)",
        ("fn", "form")),
    "sparse_attention_keys": lambda reg: reg.counter(
        "smt_onnx_sparse_attention_keys_total",
        "(query, key) pairs the SparseAttention nodes of a traced program "
        "score with the indexer (scored: the causal pairs of a prompt "
        "pass, every cached position of a decode pass) and attend to "
        "(selected: at most topk a query), a call of each node",
        ("fn", "what")),
}
# how two notes of one trace combine where they do not add up
_NOTE_FOLDS: Dict[str, Callable[[int, int], int]] = {
    "loop_trips": lambda was, now: now, "expert_chunk_rows": min}


def _note(ctx, family: str, *labels, amount: int = 1) -> None:
    """Note ``amount`` under ``family``'s ``labels`` in what the executor
    says of the program it is tracing (``NOTE_FAMILIES``)."""
    notes = ctx.get("notes")
    if notes is not None:
        noted = notes.setdefault(family, {})
        fold = _NOTE_FOLDS.get(family, operator.add)
        noted[labels] = fold(noted[labels], amount) if labels in noted \
            else amount


@op("RotaryEmbedding")
def _rotary_embedding(inputs, attrs, ctx):
    """Opset 23 ``RotaryEmbedding``: ``X`` as ``[batch, seq, heads * size]``
    (with ``num_heads``) or ``[batch, heads, seq, size]``; ``cos_cache`` and
    ``sin_cache`` ``[positions, rot / 2]`` read at ``position_ids [batch,
    seq]``, or ``[batch, seq, rot / 2]`` without them; ``interleaved`` 0
    rotates halves, 1 rotates neighbours; ``rotary_embedding_dim`` rotates
    the first ``rot`` of a head's size and passes the rest. Float32 inside,
    the result in ``X``'s type."""
    x, cos, sin = inputs[:3]
    position_ids = inputs[3] if len(inputs) > 3 else None
    rank = x.ndim
    if rank == 3:
        heads = int(attrs.get("num_heads", 0))
        if heads <= 0:
            raise ValueError("RotaryEmbedding: a rank-3 input needs num_heads")
        x4 = x.reshape(*x.shape[:2], heads, -1)
    elif rank == 4:  # [batch, heads, seq, size] -> [batch, seq, heads, size]
        x4 = jnp.transpose(x, (0, 2, 1, 3))
    else:
        raise ValueError(f"RotaryEmbedding: rank {rank} input")
    rot = int(attrs.get("rotary_embedding_dim", 0)) or x4.shape[-1]
    if position_ids is not None:
        if isinstance(position_ids, np.ndarray) \
                and (position_ids == position_ids[:1]).all():
            # every row at the same positions: one row of angles, broadcast
            position_ids = position_ids[:1]
        gather = np.take if all(isinstance(v, np.ndarray) for v in
                                (cos, sin, position_ids)) else jnp.take
        cos, sin = (gather(c, position_ids, axis=0) for c in (cos, sin))
    if cos.shape[-1] * 2 != rot:
        raise ValueError(f"RotaryEmbedding: caches of {cos.shape[-1]} "
                         f"angles for a rotation over {rot}")
    cos, sin = (jnp.asarray(c, jnp.float32)[:, :, None, :] for c in (cos, sin))
    turned = x4[..., :rot].astype(jnp.float32)
    if attrs.get("interleaved", 0):
        x1, x2 = turned[..., 0::2], turned[..., 1::2]
    else:
        x1, x2 = jnp.split(turned, 2, axis=-1)
    real, imag = cos * x1 - sin * x2, sin * x1 + cos * x2
    if attrs.get("interleaved", 0):
        turned = jnp.stack([real, imag], axis=-1).reshape(turned.shape)
    else:
        turned = jnp.concatenate([real, imag], axis=-1)
    out = jnp.concatenate([turned.astype(x.dtype), x4[..., rot:]], axis=-1) \
        if rot != x4.shape[-1] else turned.astype(x.dtype)
    return out.reshape(x.shape) if rank == 3 \
        else jnp.transpose(out, (0, 2, 1, 3))


@op("TensorScatter")
def _tensor_scatter(inputs, attrs, ctx):
    """Opset 24 ``TensorScatter`` (``mode`` linear): ``update`` written into
    ``past_cache`` along ``axis`` from ``write_indices[batch]`` on, the way a
    key-value cache takes new positions. On a loop-carried cache the write is
    in place."""
    cache, update = jnp.asarray(inputs[0]), jnp.asarray(inputs[1])
    write = inputs[2] if len(inputs) > 2 else None
    if attrs.get("mode", "linear") != "linear":
        raise NotImplementedError(
            f"TensorScatter: mode {attrs.get('mode')!r}; only linear")
    axis = int(attrs.get("axis", -2)) % cache.ndim
    if axis == 0:
        raise ValueError("TensorScatter: axis 0 is the batch axis")
    update = update.astype(cache.dtype)
    write = jnp.zeros(cache.shape[0], jnp.int32) if write is None \
        else jnp.asarray(write, jnp.int32)
    # rows that all start at one position (a batch generating in step) take
    # one slice of the buffer; rows that differ, a slice each. Where the
    # starts are a constant or a broadcast scalar the compiler folds the
    # choice away
    return lax.cond(
        jnp.all(write == write[0]),
        lambda: lax.dynamic_update_slice_in_dim(cache, update, write[0],
                                                axis),
        lambda: jax.vmap(
            lambda c, u, at: lax.dynamic_update_slice_in_dim(c, u, at,
                                                             axis - 1)
        )(cache, update, write))


def _causal_block(mask: np.ndarray, s_q: int, s_k: int):
    """What a constant boolean mask says, if it is one the kernel has: 0 for
    "every key visible", ``B`` for causal at a granularity of ``B`` positions
    (``B`` a power of two; key ``j`` visible to query ``i`` where ``j <= (i +
    s_k - s_q) | (B - 1)``; 1 is the plain causal mask), None for any other."""
    if mask.shape[-2:] != (s_q, s_k) or mask.size != s_q * s_k:
        return None
    mask = mask.reshape(s_q, s_k)
    if mask.all():
        return 0
    off = s_k - s_q
    block = int(mask[0].sum()) - off
    if block < 1 or block & (block - 1) or off % block or s_q % block:
        return None
    q_pos = np.arange(s_q)[:, None] + off
    same = np.array_equal(mask, np.arange(s_k)[None, :] <= (q_pos | (block - 1)))
    return block if same else None


@op("Attention")
def _attention(inputs, attrs, ctx):
    """Opset 23 ``Attention`` as far as a stateless scorer and a generating
    loop need it: Q, K, V as ``[batch, seq, heads * size]`` (with
    ``q_num_heads`` / ``kv_num_heads``) or ``[batch, heads, seq, size]``,
    grouped-query (the key-value heads divide the query heads), ``is_causal``,
    ``scale``, and a BOOLEAN ``attn_mask`` broadcastable to ``[batch, heads,
    q, kv]``. V may have a width of its own: the result is ``[batch, seq,
    heads * v]`` (or ``[batch, heads, seq, v]``), so latent attention is
    served in both of its forms: queries and keys of 192 against values of
    128 a head, and every query head against ONE head of cached latents
    whose values are a slice of the keys. No float mask, past, softcap,
    second output or ``softmax_precision``: they raise.

    The scores are never written where ``parallel.flash.flash_attention``
    runs its kernel (a TPU, sequence lengths that tile) and the mask is one
    the kernel has: none, causal, or a constant that reads as causal at a
    granularity of a block of positions. Where the kernel could have served
    and did not, the dense form runs and the program's ``attention_dense``
    note counts it. Any other mask is one only the run knows (a few queries
    against a cache filled so far). Where it spans key positions alone
    (``[1, kv]``, ``[batch, 1, 1, kv]`` and what broadcasts from them), the
    kernels are on, queries, keys and values have ONE head size that is a
    multiple of 128 and a key-value head serves at most 128 query rows (``q``
    x the query heads that share it: one query on a head of its own too),
    ``parallel.flash.cached_attention`` reads the cache once and as it lies
    and keeps the scores in VMEM: ``attention_cached``. Every other case (a
    mask a head or a query has to itself, latent attention's one head of
    576-wide keys over 512-wide values, ``is_causal`` beside a mask, a CPU)
    takes the grouped dense form, ``attention_masked``."""
    from ..parallel import flash

    q, k, v = inputs[:3]
    mask = inputs[3] if len(inputs) > 3 else None
    given = [n for n, x in zip(("past_key", "past_value"), inputs[4:])
             if x is not None]
    given += [n for n in ("qk_matmul_output_mode", "softcap",
                          "softmax_precision") if attrs.get(n)]
    if mask is not None and mask.dtype != np.bool_:
        given.append(f"attn_mask of type {mask.dtype}")
    if given or ctx["n_outputs"] > 1:
        raise NotImplementedError(
            f"Attention: unsupported {given or 'outputs beyond Y'}; this "
            f"lowering takes Q, K, V, a boolean attn_mask, is_causal, scale, "
            f"q_num_heads and kv_num_heads")
    rank = q.ndim
    if rank == 3:
        n_q, n_kv = int(attrs["q_num_heads"]), int(attrs["kv_num_heads"])
        q, k, v = (x.reshape(*x.shape[:2], n, -1)
                   for x, n in ((q, n_q), (k, n_kv), (v, n_kv)))
    else:  # [batch, heads, seq, size] -> the kernel's [batch, seq, heads, size]
        q, k, v = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    b, s_q, h, d = q.shape
    s_k, h_kv, d_v = k.shape[1], k.shape[2], v.shape[3]
    scale = attrs.get("scale")  # None: 1 / sqrt(d), on the float32 scores
    _note(ctx, "attention_widths", d, d_v, h_kv)
    causal, block = bool(attrs.get("is_causal", 0)), 1
    if isinstance(mask, np.ndarray) and not causal:
        # a constant mask may be one of the kernel's own
        found = _causal_block(mask, s_q, s_k)
        if found is not None:
            mask, causal, block = None, found > 0, max(found, 1)
    if mask is not None:
        if not causal and _kernels_on() and flash.cached_attention_takes(
                q.shape, k.shape, v.shape, mask.shape, q.dtype):
            _note(ctx, "attention_lowering", "cached")
            out = flash.cached_attention(q, k, v, mask, scale=scale)
        else:
            _note(ctx, "attention_lowering", "masked")
            out = flash.masked_attention(q, k, v, mask, causal=causal,
                                         scale=scale)
    elif _kernels_on() and flash.auto_blocks_tile(b * h, s_q, s_k, block):
        _note(ctx, "attention_lowering", "flash")
        # where the kernel reads its operands: as they lie here, or from
        # copies laid out heads first (widths that are no blocks of lanes)
        _note(ctx, "attention_flash_form", (
            "in_place" if flash.reads_in_place(d, d_v, q.dtype.itemsize)
            else "heads_first"))
        out = flash.flash_attention(q, k, v, causal=causal,
                                    causal_block=block, scale=scale)
    else:
        _note(ctx, "attention_lowering", "dense")
        if h != h_kv:
            k, v = (jnp.repeat(x, h // h_kv, axis=2) for x in (k, v))
        out = flash.dense_attention(q, k, v, causal=causal,
                                    causal_block=block, scale=scale)
    if rank == 3:
        return out.reshape(b, s_q, h * d_v)
    return jnp.transpose(out, (0, 2, 1, 3))


# rows of (token, pick) pairs a grid step of the grouped kernel takes where an
# expert is expected to get that many or more
_GMM_ROWS = 512
# row tiles of sorted pairs ExpertFFN gathers and multiplies at a time, at most
_CHUNK_TILES = 48
# product rows ExpertFFN adds to their tokens at a time, where a token has
# more held picks than are gathered for every token (a go pays for the rows
# it is padded with)
_REST_ROWS = 1024


def _expert_tiling(n_pairs: int, num_experts: int) -> "tuple[int, int]":
    """``(row tile, chunk length)`` of ``ExpertFFN``'s grouped products, from
    the pairs an expert is expected to get under an even router, ``n_pairs
    // num_experts``: all a node knows of its load when it is traced.

    The kernel visits a row tile once for every group that touches it and
    computes the whole tile each visit, so a tile far over an expert's pairs
    multiplies rows of other experts that the store then masks: at 32 pairs
    an expert a 512-row tile does 17 times the arithmetic wanted. Under 128
    rows a visit costs the same whatever the tile (the MXU loads the
    expert's weight tiles for a few rows), so a smaller tile only adds
    visits. One layer of ``sdar_30b_a3b`` on a v5e, ms an ``ExpertFFN`` by
    row tile 512 / 256 / 128 / 64 / 32 (``tools/expert_tile_forms.py``'s
    ``<rows>:load:whole``; PERF.md section 6, PR 33): 3.81 / 2.39 / 2.23 /
    2.46 / 2.61 at 32 pairs an expert, 4.31 / 2.83 / 3.04 / 3.09 / 3.45 at
    64, 8.68 / 6.56 / 6.85 / 7.20 / 8.19 at 256. So 128 rows is the least,
    256 from 64 expected pairs on, and from 512 on 512: the tiles and the
    lowered text every program had before the rule."""
    expected = n_pairs // num_experts
    tile = 128 if expected < 64 else 256 if expected < _GMM_ROWS else _GMM_ROWS
    return tile, _chunk_rows(n_pairs, tile)


def _chunk_rows(n_pairs: int, tile: int) -> int:
    """Sorted pairs a chunk holds: ``_CHUNK_TILES`` row tiles (24,576 pairs at
    the 512-row tile) or, where there are fewer pairs, all of them rounded
    up to a tile: the row gather, the activation and the buffer's update
    cover a chunk whole, filled or not."""
    return min(_CHUNK_TILES * tile, -(-n_pairs // tile) * tile)


def _gmm_tiling(rows: int, k: int, n: int, itemsize: int):
    """The grouped kernel's ``(tm, tk, tn)`` for row tiles of ``rows``. At
    512 rows: 7.3 MB of the 16 MB of scoped VMEM at the caps (two buffers an
    operand tile, the float32 accumulator); a rhs tile serves 512 rows, twice
    the v5e's FLOPs a byte. Under that a visit is a few rows against a whole
    expert's weights, and VMEM goes to the weights: the whole of ``k`` is one
    tile where two buffers of it fit in half the scoped VMEM, so a weight
    tile is fetched once a visit and kept between consecutive visits of one
    expert."""
    tk, tn = _tile(k, 512), _tile(n, 1024)
    if rows < _GMM_ROWS and 2 * k * tn * itemsize <= 8 << 20:
        tk = k
    return rows, tk, tn


def _grouped_product(lhs, rhs, sizes, rows):
    """``lhs[rows of group g] @ rhs[g]`` for consecutive groups of ``sizes``
    rows, float32 accumulation, in ``lhs``'s type. Rows past the last group
    come back unspecified. On a TPU the megablox kernel, which visits only
    the row tiles (of ``rows`` rows) a group touches; elsewhere
    ``lax.ragged_dot``."""
    rhs = rhs.astype(lhs.dtype)
    if not _kernels_on():
        return lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = lhs.shape
    pad = -m % rows
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
              tiling=_gmm_tiling(rows, k, rhs.shape[2], lhs.dtype.itemsize))
    return out[:m] if pad else out


def _tile(size: int, cap: int) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``size``; else
    ``cap`` (the kernel masks the last, partial tile) or a smaller ``size``."""
    for tile in range(cap, 0, -128):
        if size % tile == 0:
            return tile
    return min(cap, size)


@op("ExpertFFN")
def _expert_ffn(inputs, attrs, ctx):
    """``synapseml_tpu::ExpertFFN(x, topk_index, topk_weight, U, D[, G])``:
    the part of a sparse-expert layer that THIS program's experts give.

    ``U [held, h, f]`` and ``D [held, f, h]`` are experts ``first_expert ..
    first_expert + held - 1`` of a router over ``num_experts``; each token
    carries ``k`` picks (``topk_index [..., k]``) and their weights. The
    result is ``sum over a token's picks of a held expert e of weight *
    act_e(x) D_e``; ``activation`` "relu2": ``act_e(x) = relu(x U_e)²``;
    "swiglu", with the gate weight ``G [held, h, f]``: ``silu(x G_e) * (x
    U_e)``, the product taken in float32 and rounded once. A pick of an
    expert held elsewhere adds nothing. No token is dropped and there is no
    capacity: the (token, pick) pairs are sorted by held expert and go
    through grouped products, in chunks, as far as the held experts' pairs
    reach."""
    x, index, weight, up, down = inputs[:5]
    gate = inputs[5] if len(inputs) > 5 else None
    activation = attrs.get("activation", "relu2")
    if activation not in ("relu2", "swiglu"):
        raise NotImplementedError(
            f"ExpertFFN: activation {activation!r}; relu2 or swiglu")
    if (gate is not None) != (activation == "swiglu"):
        raise ValueError(
            f"ExpertFFN: activation {activation!r} "
            f"{'needs' if gate is None else 'takes no'} gate weight (input 6)")
    first, held = int(attrs["first_expert"]), up.shape[0]
    if first < 0 or first + held > int(attrs["num_experts"]):
        raise ValueError(
            f"ExpertFFN: experts {first}..{first + held - 1} are not among "
            f"the router's {attrs['num_experts']}")
    h, k = x.shape[-1], index.shape[-1]
    tokens = x.reshape(-1, h)
    n_tokens = tokens.shape[0]
    # pairs in pick-major order (pair p is pick p // n_tokens of token
    # p % n_tokens): the sum over a token's picks is then over a leading
    # axis, which no tiled layout has to be rearranged for
    local = jnp.asarray(index).reshape(-1, k).T.reshape(-1).astype(
        jnp.int32) - first
    n_pairs = local.shape[0]
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held)  # absent experts' pairs sort last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(group[:, None] == jnp.arange(held, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    # a node in a Loop body presents its pairs once a trip
    _note(ctx, "expert_pairs", amount=n_pairs * (
        ctx.get("notes") or {}).get("loop_factor", 1))
    _note(ctx, "experts_held", amount=held)
    _note(ctx, "expert_combine", "held_first")
    _note(ctx, "expert_form", activation)
    tile, chunk = _expert_tiling(n_pairs, int(attrs["num_experts"]))
    _note(ctx, "expert_tile", tile)
    _note(ctx, "expert_chunk_rows", amount=chunk)

    # the sorted pairs a chunk at a time, for as many chunks as hold a held
    # expert's pair: the work follows the load (a quarter of the pairs where
    # a quarter of the experts is held), and every pair has its place. The
    # buffer starts as it is found: what no chunk wrote, and what the kernel
    # left in the rows of a chunk past its last group, is selected away
    # below and never reaches a sum
    n_chunks = -(-n_pairs // chunk)
    order_padded = jnp.pad(order, (0, n_chunks * chunk - n_pairs))

    def one_chunk(i, results):
        lo = i * chunk
        pairs = lax.dynamic_slice(order_padded, (lo,), (chunk,))
        inside = (jnp.clip(ends, lo, lo + chunk)
                  - jnp.clip(ends - sizes, lo, lo + chunk))
        rows = tokens[pairs % n_tokens]
        hidden = _grouped_product(rows, up, inside, tile)
        if gate is None:
            hidden = jnp.square(jax.nn.relu(hidden))
        else:
            gated = _grouped_product(rows, gate, inside, tile).astype(
                jnp.float32)
            hidden = (jax.nn.silu(gated) * hidden.astype(jnp.float32)
                      ).astype(x.dtype)
        out = _grouped_product(hidden, down, inside, tile)
        return lax.dynamic_update_slice(results, out, (lo, 0))

    results = lax.fori_loop(
        0, (ends[-1] + chunk - 1) // chunk, one_chunk,
        lax.empty((n_chunks * chunk, h), x.dtype))
    return _held_picks_sum(
        results, ~here.reshape(k, n_tokens),
        # the place of pair p among the sorted is where p sorts among the
        # places
        jnp.argsort(order).astype(jnp.int32).reshape(k, n_tokens),
        weight.reshape(-1, k).T.astype(jnp.float32),
        min(k, -(-k * held // int(attrs["num_experts"])) + 1)
    ).astype(x.dtype).reshape(x.shape)


def _held_picks_sum(results, absent, place, weight, every):
    """``sum over a token's picks p that are not absent of weight[p, t] *
    results[place[p, t]]``, float32 ``[n_tokens, h]``; ``absent``, ``place``
    and ``weight`` are ``[k, n_tokens]``.

    A row gather costs by the row it moves, wanted or not (43 ns of
    ``[2688]`` bfloat16 on a v5e), and a row scatter six times that
    (``tools/row_move_rates.py``), so neither all ``k`` picks of every token
    are gathered (where a quarter of the experts is held, three in four are
    absent) nor the held ones scattered. A token's picks are put held ones
    first; the first ``every`` of them (one more than a router that spreads
    its picks evenly fills) are gathered for every token. A token's held
    picks beyond those are added to its sum row by row, in a loop whose
    length the load sets: one row in forty where the router is even. A row
    added so costs what seven gathered ones do, so a router that sends this
    program over twice its even share of the picks is served slower than by
    a gather of all ``k`` (``tools/expert_combine_forms.py``)."""
    k, n_tokens = place.shape
    absent, place, weight = lax.sort((absent, place, weight), dimension=0,
                                     num_keys=1, is_stable=True)
    rows = jnp.where(absent[:every, :, None], 0, results[place[:every]])
    total = (rows.astype(jnp.float32) * weight[:every, :, None]).sum(axis=0)
    if every == k:
        return total
    rest = ~absent[every:].reshape(-1)
    n_rest = jnp.sum(rest, dtype=jnp.int32)
    first = jnp.pad(jnp.argsort(~rest, stable=True),
                    (0, -rest.shape[0] % _REST_ROWS))
    rest_place = place[every:].reshape(-1)
    rest_weight = weight[every:].reshape(-1)

    def some_rows(i, total):
        at = lax.dynamic_slice(first, (i * _REST_ROWS,), (_REST_ROWS,))
        held_pick = i * _REST_ROWS + jnp.arange(_REST_ROWS) < n_rest
        rows = jnp.where(held_pick[:, None], results[rest_place[at]], 0)
        return total.at[jnp.where(held_pick, at % n_tokens, n_tokens)].add(
            rows.astype(jnp.float32) * rest_weight[at][:, None], mode="drop")

    return lax.fori_loop(0, (n_rest + _REST_ROWS - 1) // _REST_ROWS,
                         some_rows, total)


@op("SelectiveScan")
def _selective_scan(inputs, attrs, ctx):
    """``synapseml_tpu::SelectiveScan(u, delta, A, B, C, D, z, delta_bias[,
    state_in]) -> (out, state_out)``: Mamba-1's recurrence with the signature
    of the published ``selective_scan_fn``, positions before channels.

    ``u``, ``delta``, ``z`` ``[rows, S, d]``; ``A [d, n]``; ``B``, ``C``
    ``[rows, S, n]``; ``D``, ``delta_bias`` ``[d]``. With ``delta_softplus``
    (default 1) ``delta <- softplus(delta + delta_bias)``; then for every
    position in order ``s <- exp(delta A) s + delta B u``, ``y = C s + D u``,
    ``out = y silu(z)``: float32 throughout, ``out`` rounded once to ``u``'s
    type (``parallel/selective_scan.py`` has the equations). The state is
    ``[rows, n, d]`` float32, channels minor; ``state_in`` absent means zero,
    ``state_out`` is the state after the last position: ONE operator is a
    prompt pass's scan and a generating loop's single step.

    Three lowerings, from shapes and the backend alone: ``kernel`` (the
    Pallas kernel, the state in VMEM across positions: the kernels on, more
    than one position, whole groups of 8 positions and blocks of 128
    channels), ``step`` (one position: plain ``jax.numpy`` over the state),
    ``scan`` (``lax.scan`` over positions: everything else). The program's
    notes count each, and the bytes of state the ``step`` nodes take in."""
    from ..parallel import selective_scan as scan

    u, delta, a, b, c, skip, z, bias = inputs[:8]
    state_in = inputs[8] if len(inputs) > 8 else None
    rows, s, d = u.shape
    n = a.shape[-1]
    if tuple(a.shape) != (d, n) or tuple(delta.shape) != tuple(u.shape) \
            or tuple(b.shape) != (rows, s, n) or tuple(c.shape) != b.shape \
            or (state_in is not None
                and tuple(state_in.shape) != (rows, n, d)):
        raise ValueError(
            f"SelectiveScan: u {list(u.shape)}, delta {list(delta.shape)}, "
            f"A {list(a.shape)}, B {list(b.shape)}, C {list(c.shape)}, "
            f"state_in {state_in is not None and list(state_in.shape)}: "
            f"[rows, S, d] twice, [d, n], [rows, S, n] twice, [rows, n, d]")
    if s == 1:
        form = "step"
        if state_in is not None:
            _note(ctx, "recurrent_state_bytes", amount=rows * n * d * 4)
    elif _kernels_on() and scan.kernel_takes(s, d):
        form = "kernel"
    else:
        form = "scan"
    _note(ctx, "selective_scan", form)
    out, state = getattr(scan, form + "_form")(
        u, delta, a, b, c, skip, z, bias, state_in,
        delta_softplus=bool(attrs.get("delta_softplus", 1)))
    return (out, state) if ctx["n_outputs"] > 1 else out


@op("GatedDeltaRule")
def _gated_delta_rule(inputs, attrs, ctx):
    """``synapseml_tpu::GatedDeltaRule(q, k, v, g, beta[, state_in]) -> (out,
    state_out)``: Gated DeltaNet's recurrence as the published
    ``chunk_gated_delta_rule`` computes it with ``use_qk_l2norm_in_kernel``
    and its default scale, positions before heads.

    ``q``, ``k`` ``[rows, S, H, dk]``; ``v`` ``[rows, S, H, dv]``; ``g`` (the
    log of a position's decay) and ``beta`` ``[rows, S, H]``. ``q`` and
    ``k`` are divided by their norms a head and ``q`` by ``sqrt(dk)``; then
    for every position in order ``S <- exp(g) S``, ``u = beta (v - S^T k)``,
    ``S <- S + k u^T``, ``out = S^T q``: float32 throughout, ``out`` rounded
    once to ``v``'s type (``parallel/gated_delta.py`` has the equations). The
    state is ``[rows, dk, H x dv]`` float32, a head's ``[dk, dv]`` in its own
    lanes; ``state_in`` absent means zero, ``state_out`` is the state after
    the last position: ONE operator is a prompt pass's rule and a generating
    loop's single step.

    Four lowerings, from shapes and the backend alone: ``chunked_kernel``
    (more than one position, the kernels on and the heads' lanes in whole
    128-lane groups: the WY form over chunks of 64 as ONE Pallas kernel that
    keeps a row's state and a chunk's float32 products in VMEM),
    ``chunked`` (more than one position otherwise: the same form as XLA's
    matrix products), ``kernel`` (one position, the kernels on, rows in
    groups of 8 and the heads' lanes in whole 128-lane groups: ONE Pallas
    kernel that reads and writes the state once, in place), ``step`` (one
    position otherwise: plain ``jax.numpy``). The program's notes count each, and the bytes of
    state the single positions take in."""
    from ..parallel import gated_delta as rule

    q, k, v, g, beta = inputs[:5]
    state_in = inputs[5] if len(inputs) > 5 else None
    rows, s, h, dk = q.shape
    dv = v.shape[-1]
    if tuple(k.shape) != tuple(q.shape) or tuple(v.shape[:3]) != (rows, s, h) \
            or tuple(g.shape) != (rows, s, h) or tuple(beta.shape) != g.shape \
            or (state_in is not None
                and tuple(state_in.shape) != (rows, dk, h * dv)):
        raise ValueError(
            f"GatedDeltaRule: q {list(q.shape)}, k {list(k.shape)}, v "
            f"{list(v.shape)}, g {list(g.shape)}, beta {list(beta.shape)}, "
            f"state_in {state_in is not None and list(state_in.shape)}: "
            f"[rows, S, H, dk] twice, [rows, S, H, dv], [rows, S, H] twice, "
            f"[rows, dk, H x dv]")
    if s > 1:
        takes = _kernels_on() and rule.chunked_kernel_takes(rows, s, h, dk,
                                                            dv)
        form = "chunked_kernel" if takes else "chunked"
    else:
        if state_in is not None:
            _note(ctx, "recurrent_state_bytes",
                  amount=rows * dk * h * dv * 4)
        takes = _kernels_on() and rule.kernel_takes(rows, h, dk, dv)
        form = "kernel" if takes else "step"
    _note(ctx, "gated_delta", form)
    out, state = getattr(rule, form + "_form")(q, k, v, g, beta, state_in)
    return (out, state) if ctx["n_outputs"] > 1 else out


@op("SparseAttention")
def _sparse_attention(inputs, attrs, ctx):
    """``synapseml_tpu::SparseAttention(q, k, v, q_index, k_index,
    index_weights[, query_start]) -> out``: learned sparse attention, the
    selection of DeepSeek Sparse Attention (``parallel/sparse_attention.py``
    has the equations). A learned indexer scores every key a query sees,
    ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; the ``topk`` keys of
    largest ``I`` are kept, ties to the lower position (every visible key
    where fewer are visible), and softmax attention of every query head runs
    over those alone, one set for every head.

    ``q [N, s, H x d]``; ``k``, ``v`` ``[N, L, H_kv, d]`` or ``[N, L, H_kv
    x d]``; ``q_index [N, s, H_I x dI]``; ``k_index [N, L, dI]`` (one index
    key head); ``index_weights [N, s, H_I]``; ``query_start [N]`` the
    position of a row's first query (absent: 0, the keys being the queries'
    own). Query ``i`` sees keys ``0 .. query_start + i``. Attributes
    ``q_num_heads``, ``kv_num_heads``, ``topk`` and ``scale`` (default
    ``1 / sqrt(d)``). ``I``, the scores and the softmax are float32, the
    probabilities rounded to ``v``'s type before the second product; the
    result is in ``v``'s type.

    Three lowerings, from shapes and the backend alone: ``prompt_kernel``
    (no ``query_start``, ``s == L``, a TPU, whole blocks of queries and
    keys, one head size of a multiple of 128: the indexer's Pallas kernel
    picks each query's set by bisection, without a sort, and a blockwise
    Pallas kernel attends to it, skipping the tiles of keys no query of a
    block chose from; no ``[S, S]`` array is written),
    ``decode_kernel`` (one query a row against caches of whole blocks of
    128 positions laid ``[N, L, H_kv, d]``, a TPU: the indexer's kernel,
    the chosen positions in order, then a Pallas kernel that DMAs exactly
    those rows of the caches) and ``plain`` (``jax.numpy``, the set by
    ``lax.top_k``: everything else). The program's notes count each, and
    the pairs scored and attended to."""
    from ..parallel import sparse_attention as sparse

    q, k, v, q_index, k_index, weights = inputs[:6]
    start = inputs[6] if len(inputs) > 6 else None
    heads, kv_heads = int(attrs["q_num_heads"]), int(attrs["kv_num_heads"])
    topk, scale = int(attrs["topk"]), attrs.get("scale")
    n, s, wide = q.shape
    length, index_heads = k.shape[1], weights.shape[-1]
    if q.ndim != 3 or k.ndim not in (3, 4) or tuple(v.shape) != tuple(
            k.shape) or int(np.prod(k.shape[2:])) * heads != kv_heads * wide \
            or heads % kv_heads or tuple(weights.shape[:2]) != (n, s) \
            or tuple(q_index.shape[:2]) != (n, s) \
            or q_index.shape[2] % index_heads \
            or tuple(k_index.shape) != (n, length, q_index.shape[2]
                                        // index_heads) \
            or (start is None and s != length) or topk < 1:
        raise ValueError(
            f"SparseAttention: q {list(q.shape)}, k {list(k.shape)}, v "
            f"{list(v.shape)}, q_index {list(q_index.shape)}, k_index "
            f"{list(k_index.shape)}, index_weights {list(weights.shape)}, "
            f"query_start {start is not None}, topk {topk}: [N, s, H x d], "
            f"[N, L, H_kv(, | x) d] twice, [N, s, H_I x dI], [N, L, dI], "
            f"[N, s, H_I]; without query_start the keys are the queries' own")
    kw = dict(heads=heads, kv_heads=kv_heads, topk=topk, scale=scale)
    if start is None and _kernels_on() and sparse.prompt_takes(
            q.shape, k.shape, k_index.shape, heads=heads, kv_heads=kv_heads):
        form = "prompt_kernel"
        out = sparse.prompt_form(q, k, v, q_index, k_index, weights, **kw)
    elif start is not None and _kernels_on() and sparse.decode_takes(
            q.shape, k.shape, k_index.shape, heads=heads, kv_heads=kv_heads,
            topk=topk):
        form = "decode_kernel"
        out = sparse.decode_form(q, k, v, q_index, k_index, weights,
                                 jnp.asarray(start, jnp.int32), **kw)
    else:
        form = "plain"
        out = sparse.plain_form(
            q, k, v, q_index, k_index, weights,
            None if start is None else jnp.asarray(start, jnp.int32), **kw)
    _note(ctx, "sparse_attention", form)
    if start is None:  # causal pairs; every query keeps min(t + 1, topk)
        scored = s * (s + 1) // 2
        kept = min(s, topk)
        selected = kept * (kept + 1) // 2 + (s - kept) * topk
    else:
        scored, selected = s * length, s * min(topk, length)
    _note(ctx, "sparse_attention_keys", "scored", amount=n * scored)
    _note(ctx, "sparse_attention_keys", "selected", amount=n * selected)
    return out
