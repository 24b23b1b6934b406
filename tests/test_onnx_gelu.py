"""The ``Gelu`` node's two forms (ISSUE 28): a bfloat16 input takes the
one-branch ``erf`` form on its float32 upcast, every other input type and
``approximate="tanh"`` take ``jax.nn.gelu`` as before.

The bfloat16 form is held to the exact GELU (float64, ``scipy.special.erfc``)
at every one of the 65,280 finite bfloat16 values in one vectorised call, and
its lowered HLO is counted, so a jax upgrade that turns it back into the
two-branch expansion fails here and not in the next benchmark run.
"""

import collections
import re

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from scipy.special import erfc

from synapseml_tpu.onnx.ops import OPS

BF16 = ml_dtypes.bfloat16


def _gelu_node(x, **attrs):
    return OPS["Gelu"]([x], attrs, {"op_type": "Gelu", "opset": 20})


def _gelu64(x):
    x = np.asarray(x, np.float64)
    return 0.5 * x * erfc(-x / np.sqrt(2.0))


def _ulp_bf16(v):
    """Spacing of bfloat16 values at ``v`` (normal range: 8 bits of mantissa)."""
    exponent = np.floor(np.log2(np.maximum(np.abs(v), 2.0 ** -126)))
    return 2.0 ** (exponent - 7)


@pytest.fixture(scope="module")
def every_finite():
    """(inputs, outputs of the node, exact GELU) over all finite bfloat16."""
    every = np.arange(1 << 16, dtype=np.uint16).view(BF16)
    x = every[np.isfinite(every.astype(np.float32))]
    assert x.size == 65280
    out = np.asarray(jax.jit(_gelu_node)(jnp.asarray(x)))
    assert out.dtype == BF16
    return x, out, _gelu64(x)


def _all_finite(x, out, exact):
    return bool(np.isfinite(out.astype(np.float32)).all())


def _within_half_an_ulp(x, out, exact):
    # what rounding the exact value once would give, and 1e-6 for float32's
    # own error before that rounding. The bfloat16 erfc form misses this by
    # a whole ulp at some 650 inputs, the tanh form at some 900
    err = np.abs(out.astype(np.float64) - exact)
    return int((err > 0.5 * _ulp_bf16(exact) + 1e-6).sum()) == 0


def _correctly_rounded_share(x, out, exact):
    # 99.87 % on the CPU; the bfloat16 erfc form reads 96.4
    sized = np.abs(exact) >= 1e-6
    same = out[sized].view(np.uint16) == exact[sized].astype(BF16).view(np.uint16)
    return sized.sum() > 21000 and same.mean() >= 0.995


@pytest.mark.parametrize("holds", [
    _all_finite, _within_half_an_ulp, _correctly_rounded_share], ids=lambda f: f.__name__.strip("_"))
def test_bfloat16_gelu_over_every_finite_input(every_finite, holds):
    assert holds(*every_finite)


@pytest.mark.parametrize("x,expected", [
    (np.inf, np.inf), (np.nan, np.nan), (-np.inf, 0.0), (0.0, 0.0),
    (-0.0, 0.0)], ids=str)
def test_bfloat16_gelu_at_the_special_values(x, expected):
    out = float(_gelu_node(jnp.asarray([x], BF16))[0])
    assert np.isnan(out) if np.isnan(expected) else out == expected


@pytest.mark.parametrize("x", [
    -5.5, -6.0, -13.0, -100.0, -1e4, -1e30, float(ml_dtypes.finfo(BF16).min)],
    ids=str)
def test_bfloat16_gelu_far_tail_is_zero(x):
    """``1 + erf`` there is a float32 residue that ``x`` multiplies up
    (-3e31 at the most negative input without the guard)."""
    out = float(_gelu_node(jnp.asarray([x], BF16))[0])
    assert abs(out) <= 1e-6


@pytest.mark.parametrize("dtype,approximate", [
    (np.float32, "none"), (np.float64, "none"), (np.float16, "none"),
    (np.float32, "tanh"), (np.float64, "tanh"), (np.float16, "tanh"),
    (BF16, "tanh")], ids=lambda v: v if isinstance(v, str) else np.dtype(v).name)
def test_other_types_and_tanh_are_jax_nn_gelu_bit_for_bit(dtype, approximate):
    rng = np.random.default_rng(28)
    x = np.concatenate([rng.normal(0.0, 3.0, 4096), np.linspace(-12, 12, 997),
                        [0.0, -0.0, 1e-30, -40.0, 40.0]]).astype(dtype)
    attrs = {} if approximate == "none" else {"approximate": approximate}
    with jax.enable_x64(dtype is np.float64):
        got = np.asarray(jax.jit(lambda v: _gelu_node(v, **attrs))(x))
        want = np.asarray(jax.jit(
            lambda v: jax.nn.gelu(v, approximate=approximate == "tanh"))(x))
    assert got.dtype == want.dtype == np.dtype(dtype)
    assert got.tobytes() == want.tobytes()


def _elementwise_ops(fn, dtype):
    text = jax.jit(fn).lower(jax.ShapeDtypeStruct((8, 128), dtype)).as_text(
        dialect="hlo")
    ops = collections.Counter(re.findall(r"= \S+ ([a-z\-]+)\(", text))
    for structural in ("parameter", "constant", "broadcast", "convert",
                       "tuple", "call", "reshape"):
        ops.pop(structural, None)
    return ops


def test_bfloat16_gelu_lowers_to_one_branch():
    """No exponential and no chain of selects between branches; under 45
    elementwise ops with ``erf`` counted as one (9 with ``lax.erf``). The
    float32 path keeps ``erfc``'s two-branch expansion (67 ops)."""
    ops = _elementwise_ops(_gelu_node, jnp.bfloat16)
    assert ops["exponential"] == 0 and ops["select"] <= 1, ops
    assert sum(ops.values()) < 45, ops
    wide = _elementwise_ops(_gelu_node, jnp.float32)
    assert wide["exponential"] == 1 and wide["select"] >= 3, wide
    assert sum(wide.values()) > sum(ops.values())
