"""The hybrid Mamba-1 / attention ``jamba`` graph (a prompt pass, then an ONNX
``Loop`` of one position a row that carries each Mamba layer's state and
convolution rows beside the attention layer's key-value cache) at its tiny
preset on the CPU: ``transform`` against the benchmark's plain reference,
teacher-forced; decoding through the carried state against one full forward;
``synapseml_tpu::SelectiveScan``'s three lowerings against each other and
against a position-by-position numpy loop; what the trace says of them; the
flash kernel at 20 query heads on one key-value head."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from synapseml_tpu.models import zoo  # noqa: E402
from synapseml_tpu.onnx.importer import OnnxFunction  # noqa: E402
from tests.test_joyai_flash import _numbers  # noqa: E402
from tests.test_sdar_moe import (_fresh_programs, _gauge, _model,  # noqa: E402
                                 _relative)

TINY = zoo.JAMBA_TINY
GENERATE, PROMPT = TINY["generate"], 16
INNER = TINY["expand"] * TINY["hidden"]
with open(os.path.join(ROOT, "benchmark", "configs", "jamba_tiny.json")) as _f:
    CONFIG = json.load(_f)
COLUMNS = ("tokens", "chosen_logprob", "pooled")


def _reference(model_bytes):
    from benchmark.reference import jamba
    from benchmark.reference.onnx_initializers import read_initializers

    return jamba.Reference(CONFIG, read_initializers(model_bytes))


def _prompts(rows, seed=0, length=PROMPT):
    return np.random.default_rng(seed).integers(0, TINY["vocab"],
                                                (rows, length))


def _transform(model_bytes, prompts, policy):
    import jax

    from synapseml_tpu.core import Table
    from synapseml_tpu.onnx import ONNXModel

    model = ONNXModel(
        model_bytes=model_bytes, feed_dict={"input_ids": "input_ids"},
        fetch_dict={c: c for c in COLUMNS}, batch_size=len(prompts),
        dtype_policy=policy)
    with jax.default_matmul_precision("highest"):
        out = model.transform(Table({"input_ids": prompts}))
    return {c: np.asarray(out[c]) for c in COLUMNS}


# float32 policy: the program (a prompt pass through ``scan_form`` and dense
# attention, then the loop: the convolution over kept rows, one step from the
# carried state, a cache) and the reference (one full forward, the recurrence
# in the published [rows, d, n] layout) are the same arithmetic in another
# order: 3e-7 is read, 1e-5 allowed, and every id is the reference's own
# argmax. bfloat16 policy: there is no router, so no pick flips; what is
# read is the hand-offs' rounding through four layers at hidden 64
# (``pooled`` 0.012-0.022 over seeds, a log-probability within 0.004 of its
# size): three times the largest reading is allowed.
@pytest.mark.parametrize("policy,limit", [
    ("float32", {"logprob": 1e-5, "gap": 1e-6, "pooled": 1e-5}),
    ("bfloat16", {"logprob": 0.02, "gap": 0.3, "pooled": 0.07})])
def test_transform_agrees_with_the_reference_teacher_forced(policy, limit,
                                                            monkeypatch):
    import jax

    _fresh_programs(monkeypatch)
    model_bytes = zoo.build_model_bytes("JambaTiny", seed=3)
    prompts = _prompts(4, seed=1)
    got = _transform(model_bytes, prompts, policy)
    assert got["tokens"].shape == (4, GENERATE)
    assert got["tokens"].dtype.kind == "i"
    assert got["pooled"].shape == (4, TINY["hidden"])
    with jax.default_matmul_precision("highest"):
        replayed = _reference(model_bytes).replay(prompts, got["tokens"],
                                                  block_rows=2)
    numbers = _numbers(got, replayed)
    assert numbers["chosen_logprob.rel_rms"] < limit["logprob"]
    assert numbers["argmax_gap"] <= limit["gap"]
    assert _relative(got["pooled"], replayed["pooled"]) < limit["pooled"]
    if policy == "float32":  # the reference's own greedy choice, every pass
        np.testing.assert_array_equal(got["tokens"],
                                      replayed["logits"].argmax(-1))


@pytest.mark.parametrize("conv_kernel", [4, 2, 3])
def test_decoding_from_the_carried_state_agrees_with_one_full_forward(
        conv_kernel, monkeypatch):
    """Prompt pass + loop = one forward, inside the program: the ids a call
    decodes one at a time from its carried states, convolution rows and
    cache are the ids the PROMPT pass (every position at once) gives for
    the same prefix. Windows of 2, 3 and 4 positions: one kept row, and
    more than one."""
    _fresh_programs(monkeypatch)
    prompts = _prompts(3, seed=2)
    whole = _transform(zoo.build_model_bytes(
        "JambaTiny", seed=4, conv_kernel=conv_kernel), prompts, "float32")
    # the same weights generating 2 ids: id 0 is the prompt pass's
    short = zoo.build_model_bytes("JambaTiny", seed=4, generate=2,
                                  conv_kernel=conv_kernel)
    for t in (1, 4, GENERATE - 1):
        prefix = np.concatenate([prompts, whole["tokens"][:, :t]], axis=1)
        again = _transform(short, prefix, "float32")
        np.testing.assert_array_equal(again["tokens"][:, 0],
                                      whole["tokens"][:, t])
        np.testing.assert_allclose(again["chosen_logprob"][:, 0],
                                   whole["chosen_logprob"][:, t],
                                   rtol=1e-5, atol=1e-5)


def test_the_graph_is_standard_operators_and_one_selective_scan_a_pass():
    from synapseml_tpu.models.jamba import jamba
    from synapseml_tpu.onnx.importer import OPS

    model = jamba(**{**TINY, "seed": 0})
    (loop,) = [n for n in model.graph.node if n.op_type == "Loop"]
    body = loop.attrs()["body"]
    mamba_layers = TINY["layers"] - 1
    for nodes, pass_ in ((model.graph.node, "p"), (body.node, "d")):
        custom = [n for n in nodes if n.domain == "synapseml_tpu"]
        assert [n.op_type for n in custom] == ["SelectiveScan"] * mamba_layers
        assert [n.name for n in custom] == [f"{pass_}_l{i}_ssm"
                                            for i in (0, 1, 3)]
        assert {n.op_type for n in nodes} - {"SelectiveScan"} <= set(OPS)
    # the prompt pass's node starts from no state, the body's from the
    # carried one
    assert len(model.graph.node[[n.name for n in model.graph.node].index(
        "p_l0_ssm")].input) == 8
    assert len(body.node[[n.name for n in body.node].index(
        "d_l0_ssm")].input) == 9
    # 4 values of the generation, then a layer's two in layer order
    assert len(loop.input) == 2 + 4 + 2 * TINY["layers"]
    with pytest.raises(ValueError, match="generate"):
        jamba(**{**TINY, "generate": 1})


@pytest.mark.parametrize("conv_kernel", [2, 4])
def test_the_loop_carries_convolution_rows_positions_major(conv_kernel,
                                                           monkeypatch):
    """Each Mamba layer's kept rows go into and come out of the loop as
    ``[conv - 1, N, d]``, the window is joined and sliced on its leading
    axis, and the decode body holds no ``Conv``, no ``Transpose`` round a
    convolution and no ``Concat`` or ``Slice`` on axis 1."""
    from synapseml_tpu.models.jamba import jamba
    from synapseml_tpu.onnx.importer import OPS

    model = jamba(**{**TINY, "conv_kernel": conv_kernel, "seed": 0})
    (loop,) = [n for n in model.graph.node if n.op_type == "Loop"]
    body = loop.attrs()["body"]
    made_by = {o: n for n in body.node for o in n.output}
    assert "Conv" not in {n.op_type for n in body.node}
    for n in body.node:
        if n.op_type == "Concat":
            assert n.attrs()["axis"] == 0, n.name
        if n.op_type == "Slice":  # the kept rows; the rest split on -1
            assert n.input[3] == "axes_0", n.name
        if n.op_type == "Transpose":  # the new row [N, 1, d] -> [1, N, d]
            assert n.name.endswith("_x_new") and list(
                n.attrs()["perm"]) == [1, 0, 2], n.name
    outputs = [o.name for o in body.output]
    for i in (0, 1, 3):
        window = made_by[f"d_l{i}_window"]
        assert list(window.input) == [f"d_carried{i}_1", f"d_l{i}_x_new"]
        assert made_by[f"d_l{i}_conv_rows"].input[0] == f"d_l{i}_window"
        assert outputs[5 + 2 * i + 1] == f"d_l{i}_conv_rows"
    # the shapes the program traces, by node
    _fresh_programs(monkeypatch)
    shapes = {}
    for op in ("Transpose", "Concat", "Slice", "CastLike"):
        def recorded(inputs, attrs, ctx, _run=OPS[op]):
            out = _run(inputs, attrs, ctx)
            shapes[ctx["node_name"]] = tuple(out.shape)
            return out
        monkeypatch.setitem(OPS, op, recorded)
    rows = 2
    OnnxFunction(model)({"input_ids": _prompts(rows)})
    kept = (conv_kernel - 1, rows, INNER)
    for i in (0, 1, 3):
        assert shapes[f"p_l{i}_conv_rows"] == kept
        assert shapes[f"d_l{i}_window"] == (conv_kernel, rows, INNER)
        assert shapes[f"d_l{i}_conv_rows"] == kept
        assert shapes[f"d_l{i}_conv_out"] == (rows, 1, INNER)


# ---- the operator and its three lowerings

def _scan_case(rng, rows, s, d, n, dtype=np.float32):
    def draw(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    a = -np.tile(np.arange(1, n + 1, dtype=np.float32), (d, 1)) \
        * rng.uniform(0.5, 1.5, (d, 1)).astype(np.float32)
    return dict(u=draw(rows, s, d).astype(dtype),
                delta=draw(rows, s, d).astype(dtype), A=a,
                B=draw(rows, s, n), C=draw(rows, s, n),
                D=rng.uniform(0.5, 1.5, d).astype(np.float32),
                z=draw(rows, s, d).astype(dtype),
                delta_bias=rng.uniform(-4.0, -2.0, d).astype(np.float32))


def _loop_by_hand(u, delta, A, B, C, D, z, delta_bias, state=None):
    """The equations position by position in float64, the state in the
    published ``[rows, d, n]`` layout; -> ``out``, the state ``[rows, n,
    d]``."""
    rows, s, d = u.shape
    u, delta, z = (v.astype(np.float64) for v in (u, delta, z))
    state = np.zeros((rows, d, A.shape[1])) if state is None \
        else np.swapaxes(state, 1, 2).astype(np.float64)
    out = np.zeros((rows, s, d))
    for t in range(s):
        step = np.logaddexp(delta[:, t] + delta_bias, 0.0)
        state = np.exp(step[:, :, None] * A) * state \
            + (step * u[:, t])[:, :, None] * B[:, t, None, :]
        y = (state * C[:, t, None, :]).sum(-1) + D * u[:, t]
        out[:, t] = y * z[:, t] / (1.0 + np.exp(-z[:, t]))
    return out, np.swapaxes(state, 1, 2)


# float32 operands: each form is the loop's arithmetic in float32 (a sum of
# 16 products a position, a recurrence of up to 48 positions): 4e-6 is read
# of numbers of size 1 to 10, 5e-5 allowed. bfloat16 operands: the result is
# rounded once to bfloat16 (half a unit in the last place: 2 ** -9 of its
# size, 0.03 at the largest results of some 10) and the state stays float32.
@pytest.mark.parametrize("entering", [False, True])
@pytest.mark.parametrize("form,blocks", [
    ("scan", {}), ("kernel", dict(channels=128, positions=16)),
    ("kernel", dict(channels=256, positions=8)), ("kernel", {})])
def test_selective_scan_forms_agree_with_a_loop_by_hand(form, blocks,
                                                        entering):
    """``S`` = 48 spans three and six blocks of positions under the caps
    given (and one under the default), ``d`` = 256 two blocks of channels
    and one; with and without a state that is not zero."""
    from synapseml_tpu.parallel import selective_scan as scan

    rng = np.random.default_rng(5)
    case = _scan_case(rng, 2, 48, 256, 16)
    state = rng.standard_normal((2, 16, 256)).astype(np.float32) \
        if entering else None
    want, want_state = _loop_by_hand(**case, state=state)
    if form == "scan":
        got, got_state = scan.scan_form(*case.values(), state)
    else:
        if blocks:
            assert scan._blocks(48, 256, 4, **blocks) == (
                blocks["channels"], blocks["positions"])
        got, got_state = scan.kernel_form(*case.values(), state,
                                          interpret=True, **blocks)
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(got_state), want_state, rtol=5e-5,
                               atol=5e-5)
    assert got_state.dtype == np.float32 and got_state.shape == (2, 16, 256)


def test_the_kernel_and_the_scan_round_bfloat16_operands_alike():
    import jax.numpy as jnp

    from synapseml_tpu.parallel import selective_scan as scan

    rng = np.random.default_rng(6)
    case = _scan_case(rng, 2, 32, 128, 16)
    narrow = {k: jnp.asarray(v, jnp.bfloat16) if k in ("u", "delta", "z")
              else v for k, v in case.items()}
    state = rng.standard_normal((2, 16, 128)).astype(np.float32)
    want, want_state = _loop_by_hand(
        **{k: np.asarray(jnp.asarray(v, jnp.float32))
           for k, v in narrow.items()}, state=state)
    for got, got_state in (
            scan.scan_form(*narrow.values(), state),
            scan.kernel_form(*narrow.values(), state, interpret=True,
                             positions=8)):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want,
                                   rtol=2 ** -8, atol=1e-3)
        np.testing.assert_allclose(np.asarray(got_state), want_state,
                                   rtol=5e-5, atol=5e-5)


def test_one_step_from_a_state_is_the_next_position_of_a_longer_scan():
    """``S`` = 1 from the state ``S`` positions left = position ``S + 1`` of
    a scan over ``S + 1``: what makes prompt-then-decode one forward."""
    from synapseml_tpu.parallel import selective_scan as scan

    rng = np.random.default_rng(7)
    case = _scan_case(rng, 3, 25, 128, 16)
    shared = {k: case[k] for k in ("A", "D", "delta_bias")}

    def positions(lo, hi):
        return {k: (v if k in shared else v[:, lo:hi])
                for k, v in case.items()}

    whole, whole_state = scan.scan_form(*case.values())
    _, state = scan.kernel_form(*positions(0, 24).values(), interpret=True)
    step, step_state = scan.step_form(*positions(24, 25).values(), state)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(whole[:, 24]), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(step_state),
                               np.asarray(whole_state), rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="one"):
        scan.step_form(*positions(0, 2).values())
    with pytest.raises(ValueError, match="groups of 8"):
        scan.kernel_form(*positions(0, 25).values(), interpret=True)


def _scan_node_model(rows, s, d, n, entering):
    from synapseml_tpu.onnx import builder as ob

    names = ["u", "delta", "A", "B", "C", "D", "z", "delta_bias"] \
        + ["state_in"] * entering
    shapes = dict(u=(rows, s, d), delta=(rows, s, d), A=(d, n),
                  B=(rows, s, n), C=(rows, s, n), D=(d,), z=(rows, s, d),
                  delta_bias=(d,), state_in=(rows, n, d))
    return _model(
        [ob.node("SelectiveScan", names, ["out", "state_out"], name="ssm",
                 domain="synapseml_tpu", delta_softplus=1)],
        {k: np.zeros(shapes[k], np.float32) for k in names},
        ["out", "state_out"], domain="synapseml_tpu")


@pytest.mark.parametrize("s,kernels,form", [
    (16, True, "kernel"), (16, False, "scan"), (12, True, "scan"),
    (1, True, "step"), (1, False, "step")])
def test_the_operator_chooses_its_lowering_from_shapes_and_backend(
        s, kernels, form, monkeypatch):
    """Through ``OnnxFunction``: the lowering, the note that counts it, the
    bytes of state a single step takes in, and the same answer whichever
    ran (the kernel through the interpreter)."""
    import functools

    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import selective_scan as scan

    _fresh_programs(monkeypatch)
    monkeypatch.setattr(ops, "_kernels_on", lambda: kernels)
    monkeypatch.setattr(scan, "kernel_form", functools.partial(
        scan.kernel_form, interpret=True))
    rng = np.random.default_rng(8)
    rows, d, n = 2, 128, 16
    case = _scan_case(rng, rows, s, d, n)
    case["state_in"] = rng.standard_normal((rows, n, d)).astype(np.float32)
    fn = OnnxFunction(_scan_node_model(rows, s, d, n, True))
    before = _gauge("smt_onnx_selective_scan_lowering_total", fn=fn._fn_name)
    got = fn(case)
    gained = {k: v - before.get(k, 0) for k, v in _gauge(
        "smt_onnx_selective_scan_lowering_total", fn=fn._fn_name).items()}
    # (the cases share a program's name, so its other counts stay as found)
    assert {k: v for k, v in gained.items() if v} == {(fn._fn_name, form): 1}
    if s == 1:
        assert _gauge("smt_onnx_recurrent_state_bytes", fn=fn._fn_name) == {
            (fn._fn_name,): rows * n * d * 4}
    want, want_state = _loop_by_hand(**{k: v for k, v in case.items()
                                        if k != "state_in"},
                                     state=case["state_in"])
    np.testing.assert_allclose(np.asarray(got["out"]), want, rtol=5e-5,
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(got["state_out"]), want_state,
                               rtol=5e-5, atol=5e-5)


def test_the_operator_refuses_operands_that_do_not_fit():
    case = _scan_case(np.random.default_rng(9), 2, 8, 128, 16)
    case["B"] = case["B"][:, :4]
    fn = OnnxFunction(_scan_node_model(2, 8, 128, 16, False))
    with pytest.raises(ValueError, match="SelectiveScan"):
        fn(case)


def test_the_trace_says_how_the_scans_ran_and_what_the_loop_carries(
        monkeypatch):
    _fresh_programs(monkeypatch)
    fn = OnnxFunction(zoo.build_model_bytes("JambaTiny", seed=7),
                      dtype_policy="bfloat16")
    name, layers = fn._fn_name, TINY["layers"]
    mamba_layers = layers - 1
    families = ("smt_onnx_selective_scan_lowering_total",
                "smt_onnx_attention_lowering_total",
                "smt_onnx_attention_widths_total")
    before = {f: _gauge(f, fn=name) for f in families}
    rows = 2
    fn({"input_ids": _prompts(rows, seed=4)})

    def since(family):  # counters add up over a process's traces
        return {k: v - before[family].get(k, 0)
                for k, v in _gauge(family, fn=name).items()}

    # on the CPU the prompt pass's scans are lax.scan (on the chip: kernel)
    assert since("smt_onnx_selective_scan_lowering_total") == {
        (name, "scan"): mamba_layers, (name, "step"): mamba_layers}
    assert since("smt_onnx_attention_lowering_total") == {
        (name, "dense"): 1, (name, "masked"): 1}
    assert since("smt_onnx_attention_widths_total") == {
        (name, str(TINY["head_dim"]), str(TINY["head_dim"]), "1"): 2}
    assert _gauge("smt_onnx_loop_trips", fn=name) == {
        (name, "decode"): GENERATE - 1}
    # what the decode passes' single steps take in: a float32 [n, d] a row
    # and Mamba layer
    state = mamba_layers * rows * TINY["state"] * INNER * 4
    assert _gauge("smt_onnx_recurrent_state_bytes", fn=name) == {
        (name,): state}
    # the loop carries both kinds: the states and three convolution rows of
    # the policy's type a Mamba layer ([3, N, d], positions major), keys and
    # values of the attention layer, and what it fills (the last id, tokens,
    # chosen_logprob, the pooled sum)
    conv_rows = mamba_layers * 3 * rows * INNER * 2
    cache = 2 * rows * (PROMPT + GENERATE) * TINY["head_dim"] * 2
    outputs = rows * (1 + GENERATE) * 4 + rows * (GENERATE
                                                  + TINY["hidden"]) * 4
    assert _gauge("smt_onnx_loop_state_bytes", fn=name) == {
        (name,): state + conv_rows + cache + outputs}


# ---- the flash kernel at this family's heads

@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_at_20_heads_on_one_key_value_head(causal):
    """20 query heads of 128 share ONE key-value head, read where the
    operands lie, through the Pallas interpreter against dense attention;
    the heads a grid step takes divide 20."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    group = flash._heads_a_step(20, 1, 128, 128, 128, 128, 2)
    assert group == 20  # every query head shares the step's keys and values
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((2, 256, 20, 128), dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((2, 256, 1, 128), dtype=np.float32))
    v = jnp.asarray(rng.standard_normal((2, 256, 1, 128), dtype=np.float32))
    with jax.default_matmul_precision("highest"):
        got = flash.flash_attention(q, k, v, causal=causal, block_q=128,
                                    block_k=128, interpret=True)
        want = flash.dense_attention(q, jnp.repeat(k, 20, axis=2),
                                     jnp.repeat(v, 20, axis=2), causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_the_selective_scan_tool_rehearses_on_the_cpu(capsys):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import selective_scan_forms

    assert selective_scan_forms.main(["--rehearse-on-cpu"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()][1:]
    assert [(line["load"], line["form"]) for line in lines] == [
        (load, form) for load in ("cell", "long", "step")
        for form in selective_scan_forms.TOY_FORMS[load].split(",")]
    assert all(line["finite"] and line["same_bits_twice"]
               and "ms" not in line and "error" not in line for line in lines)
    scans = [line for line in lines if line["load"] != "step"]
    # bfloat16 results: half a unit in the last place of numbers up to 16
    assert all(line["max_abs_from_loop"] <= 0.0625
               and line["state_max_abs_from_loop"] < 1e-4 for line in scans)
    assert [line["lowering"] for line in lines
            if line["form"] == "shipped"] == [
        {"kernel": 1}, {"kernel": 1}, {"step": 2}]
