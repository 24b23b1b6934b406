"""The hybrid Gated DeltaNet / attention ``olmo_hybrid`` graph (a prompt pass,
then an ONNX ``Loop`` of one position a row that carries each delta rule
layer's matrix state and convolution rows beside the attention layer's
key-value cache) at its tiny preset on the CPU: ``transform`` against the
benchmark's plain reference, teacher-forced; decoding through the carried
state against one full forward; ``synapseml_tpu::GatedDeltaRule``'s three
lowerings against each other and against a position-by-position numpy
loop; what the trace says of them."""

import functools
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

TINY = zoo.OLMO_HYBRID_TINY
GENERATE, PROMPT = TINY["generate"], 16
HEADS, DK, DV = TINY["linear_heads"], TINY["key_dim"], TINY["value_dim"]
CHANNELS = HEADS * (2 * DK + DV)
DELTA_LAYERS = (0, 2, 3)  # attention at layer 1 (period 4, offset 1)
with open(os.path.join(ROOT, "benchmark", "configs",
                       "olmo_hybrid_tiny.json")) as _f:
    CONFIG = json.load(_f)
COLUMNS = ("tokens", "chosen_logprob", "pooled")


def _reference(model_bytes):
    from benchmark.reference import olmo_hybrid
    from benchmark.reference.onnx_initializers import read_initializers

    return olmo_hybrid.Reference(CONFIG, read_initializers(model_bytes))


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


# float32 policy: the program (the prompt pass through the chunked form and
# dense attention, then the loop: the convolution over kept rows, one step
# from the carried state, a cache) and the reference (one full forward, the
# rule position by position in the published [rows, H, dk, dv] layout) are
# the same arithmetic in another order: 1e-6 is read, 1e-5 allowed, and
# every id is the reference's own argmax. bfloat16 policy: no router, so no
# pick flips; what is read is the hand-offs' rounding through four layers at
# hidden 64 (pooled 0.03-0.07 over seeds, a log-probability within 0.02 of
# its size): twice the largest reading is allowed.
@pytest.mark.parametrize("policy,limit", [
    ("float32", {"logprob": 1e-5, "gap": 1e-6, "pooled": 1e-5}),
    ("bfloat16", {"logprob": 0.05, "gap": 0.4, "pooled": 0.15})])
def test_transform_agrees_with_the_reference_teacher_forced(policy, limit,
                                                            monkeypatch):
    import jax

    _fresh_programs(monkeypatch)
    model_bytes = zoo.build_model_bytes("OlmoHybridTiny", seed=3)
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


@pytest.mark.parametrize("conv_kernel,prompt", [(4, PROMPT), (2, PROMPT),
                                                (4, 70)])
def test_decoding_from_the_carried_state_agrees_with_one_full_forward(
        conv_kernel, prompt, monkeypatch):
    """Prompt pass + loop = one forward, inside the program: the ids a call
    decodes one at a time from its carried states, convolution rows and
    cache are the ids the PROMPT pass (every position at once, in chunks)
    gives for the same prefix. A window of 2 positions and of 4; a prompt of
    70 positions is a whole chunk of 64 and a part of one."""
    _fresh_programs(monkeypatch)
    prompts = _prompts(3, seed=2, length=prompt)
    whole = _transform(zoo.build_model_bytes(
        "OlmoHybridTiny", seed=4, conv_kernel=conv_kernel), prompts,
        "float32")
    # the same weights generating 2 ids: id 0 is the prompt pass's
    short = zoo.build_model_bytes("OlmoHybridTiny", seed=4, generate=2,
                                  conv_kernel=conv_kernel)
    for t in (1, GENERATE - 1):
        prefix = np.concatenate([prompts, whole["tokens"][:, :t]], axis=1)
        again = _transform(short, prefix, "float32")
        np.testing.assert_array_equal(again["tokens"][:, 0],
                                      whole["tokens"][:, t])
        np.testing.assert_allclose(again["chosen_logprob"][:, 0],
                                   whole["chosen_logprob"][:, t],
                                   rtol=1e-5, atol=1e-5)


def test_the_graph_is_standard_operators_and_one_delta_rule_a_layer():
    from synapseml_tpu.models.olmo_hybrid import olmo_hybrid
    from synapseml_tpu.onnx.importer import OPS

    model = olmo_hybrid(**{**TINY, "seed": 0})
    (loop,) = [n for n in model.graph.node if n.op_type == "Loop"]
    body = loop.attrs()["body"]
    for nodes, pass_ in ((model.graph.node, "p"), (body.node, "d")):
        custom = [n for n in nodes if n.domain == "synapseml_tpu"]
        assert [n.op_type for n in custom] == ["GatedDeltaRule"] * 3
        assert [n.name for n in custom] == [f"{pass_}_l{i}_gdn"
                                            for i in DELTA_LAYERS]
        assert {n.op_type for n in nodes} - {"GatedDeltaRule"} <= set(OPS)
        # no Conv in the body: the window's single step
        assert ("Conv" in {n.op_type for n in nodes}) == (pass_ == "p")
    # the prompt pass's node starts from no state, the body's from the
    # carried one
    names = [n.name for n in model.graph.node]
    assert len(model.graph.node[names.index("p_l0_gdn")].input) == 5
    assert len(body.node[[n.name for n in body.node].index(
        "d_l0_gdn")].input) == 6
    # 4 values of the generation, then a layer's two in layer order
    assert len(loop.input) == 2 + 4 + 2 * TINY["layers"]
    # the untied head
    assert {"tok_emb", "lm_head"} <= {t.name for t in model.graph.initializer}
    with pytest.raises(ValueError, match="generate"):
        olmo_hybrid(**{**TINY, "generate": 1})


# ---- the operator and its three lowerings

def _case(rng, rows, s, h=HEADS, dk=DK, dv=DV, entering=True):
    case = {"q": rng.standard_normal((rows, s, h, dk)),
            "k": rng.standard_normal((rows, s, h, dk)),
            "v": rng.standard_normal((rows, s, h, dv)),
            # decays from 1 to e^-3 a position, write strengths in (0, 2)
            "g": -rng.uniform(0, 3, (rows, s, h)),
            "beta": rng.uniform(0, 2, (rows, s, h))}
    if entering:
        case["state_in"] = 0.3 * rng.standard_normal((rows, dk, h * dv))
    return {k: v.astype(np.float32) for k, v in case.items()}


def _loop_by_hand(q, k, v, g, beta, state_in=None):
    """The published naive recurrence in float64, the state ``[rows, H, dk,
    dv]``; -> ``(out, state_out [rows, dk, H dv])``."""
    rows, s, h, dk = q.shape
    dv = v.shape[-1]
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(dk)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    state = np.zeros((rows, h, dk, dv)) if state_in is None else \
        state_in.astype(np.float64).reshape(rows, dk, h, dv).transpose(
            0, 2, 1, 3)
    out = np.zeros((rows, s, h, dv))
    for t in range(s):
        state = state * np.exp(g[:, t])[..., None, None]
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("rhk,rhkv->rhv", k[:, t], state))
        state = state + k[:, t][..., None] * u[:, :, None, :]
        out[:, t] = np.einsum("rhk,rhkv->rhv", q[:, t], state)
    return out, state.transpose(0, 2, 1, 3).reshape(rows, dk, h * dv)


@pytest.mark.parametrize("entering", [False, True])
@pytest.mark.parametrize("form,s,options", [
    ("chunked", 37, {}), ("chunked", 37, {"chunk": 8}),
    ("chunked", 16, {"chunk": 16}), ("chunked", 1, {}),
    ("step", 1, {}), ("kernel", 1, {"interpret": True}),
    ("chunked_kernel", 37, {"interpret": True}),
    ("chunked_kernel", 37, {"chunk": 16, "interpret": True}),
    ("chunked_kernel", 32, {"chunk": 16, "interpret": True})])
def test_the_forms_agree_with_a_loop_by_hand(form, s, options, entering):
    """Every form, from a state and from none: whole chunks, a part of one,
    one position; the kernels through the interpreter."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import gated_delta as rule

    case = _case(np.random.default_rng(3), 8, s, entering=entering)
    got, state = getattr(rule, form + "_form")(
        *(jnp.asarray(case[k]) for k in ("q", "k", "v", "g", "beta")),
        case.get("state_in"), **options)
    want, want_state = _loop_by_hand(**case)
    assert got.shape == (8, s, HEADS, DV) and state.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), want_state, rtol=2e-5,
                               atol=2e-5)


def test_the_kernel_takes_two_heads_of_192_a_group_of_lanes():
    """``dv`` 192 (the cell's): a group of 384 lanes holds two heads, and the
    kernel spreads each head's key over its own 192."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import gated_delta as rule

    assert rule._group(192) == 384 and rule._group(32) == 128
    assert rule.kernel_takes(128, 30, 96, 192)
    assert not rule.kernel_takes(12, 30, 96, 192)   # rows off the group of 8
    assert not rule.kernel_takes(8, 3, 96, 192)     # 576 lanes: no whole group
    case = _case(np.random.default_rng(5), 8, 1, h=4, dk=16, dv=192)
    got, state = rule.kernel_form(
        *(jnp.asarray(case[k]) for k in ("q", "k", "v", "g", "beta")),
        case["state_in"], interpret=True)
    want, want_state = _loop_by_hand(**case)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), want_state, rtol=2e-5,
                               atol=2e-5)


def test_the_chunked_kernel_takes_two_heads_of_192_a_group_of_lanes():
    """``dv`` 192 (the cell's): a group of 384 lanes holds two heads, whose
    inverses share the forward substitution's lanes; a length off the chunk
    is padded."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import gated_delta as rule

    assert rule.chunked_kernel_takes(128, 256, 30, 96, 192)
    assert rule.chunked_kernel_takes(3, 5, 30, 96, 192)  # any rows, length
    assert not rule.chunked_kernel_takes(8, 1, 30, 96, 192)   # one position
    assert not rule.chunked_kernel_takes(8, 64, 3, 96, 192)   # 576 lanes
    assert not rule.chunked_kernel_takes(8, 64, 4, 12, 192)   # dk off 8
    assert not rule.chunked_kernel_takes(8, 64, 256, 96, 192)  # VMEM
    case = _case(np.random.default_rng(4), 3, 21, h=4, dk=16, dv=192)
    got, state = rule.chunked_kernel_form(
        *(jnp.asarray(case[k]) for k in ("q", "k", "v", "g", "beta")),
        case["state_in"], chunk=8, interpret=True)
    want, want_state = _loop_by_hand(**case)
    assert got.shape == (3, 21, 4, 192)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), want_state, rtol=2e-5,
                               atol=2e-5)


def test_the_chunked_kernels_state_then_one_step_is_one_more_position():
    """The chunked kernel's leaving state, then the decode kernel's single
    step, is the chunked kernel over one more position: the prompt pass's
    state is laid out as the decode loop takes it."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import gated_delta as rule

    case = _case(np.random.default_rng(7), 8, 25, entering=False)
    ops_ = [jnp.asarray(case[k]) for k in ("q", "k", "v", "g", "beta")]
    whole, whole_state = rule.chunked_kernel_form(*ops_, chunk=8,
                                                  interpret=True)
    _, state = rule.chunked_kernel_form(*(x[:, :-1] for x in ops_), chunk=8,
                                        interpret=True)
    last, last_state = rule.kernel_form(*(x[:, -1:] for x in ops_), state,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, -1:]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(last_state),
                               np.asarray(whole_state), rtol=2e-5, atol=2e-5)


def test_one_step_from_a_state_is_the_next_position_of_a_longer_run():
    """The chunked form's leaving state, then one step, is the chunked form
    over one more position: a prompt pass hands a decode pass all it
    needs. bfloat16 operands round alike in both."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import gated_delta as rule

    case = _case(np.random.default_rng(6), 8, 21, entering=False)
    ops_ = [jnp.asarray(case[k]) for k in ("q", "k", "v", "g", "beta")]
    ops_[:3] = [x.astype(jnp.bfloat16) for x in ops_[:3]]
    whole, whole_state = rule.chunked_form(*ops_, chunk=8)
    _, state = rule.chunked_form(*(x[:, :-1] for x in ops_), chunk=8)
    last, last_state = rule.step_form(*(x[:, -1:] for x in ops_), state)
    assert last.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(last, np.float32),
                               np.asarray(whole[:, -1:], np.float32),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(last_state),
                               np.asarray(whole_state), rtol=1e-5, atol=1e-5)


def _rule_node_model(rows, s, entering, h=HEADS):
    from synapseml_tpu.onnx import builder as ob

    names = ["q", "k", "v", "g", "beta"] + ["state_in"] * entering
    shapes = dict(q=(rows, s, h, DK), k=(rows, s, h, DK),
                  v=(rows, s, h, DV), g=(rows, s, h),
                  beta=(rows, s, h), state_in=(rows, DK, h * DV))
    return _model(
        [ob.node("GatedDeltaRule", names, ["out", "state_out"], name="gdn",
                 domain="synapseml_tpu")],
        {k: np.zeros(shapes[k], np.float32) for k in names},
        ["out", "state_out"], domain="synapseml_tpu")


@pytest.mark.parametrize("rows,s,kernels,form,h", [
    (8, 16, True, "chunked_kernel", HEADS), (8, 16, False, "chunked", HEADS),
    (8, 16, True, "chunked", 3),   # 96 lanes: no whole group of heads
    (8, 1, True, "kernel", HEADS), (8, 1, False, "step", HEADS),
    (4, 1, True, "step", HEADS)])
def test_the_operator_chooses_its_lowering_from_shapes_and_backend(
        rows, s, kernels, form, h, monkeypatch):
    """Through ``OnnxFunction``: the lowering, the note that counts it, the
    bytes of state a single position takes in, and the same answer
    whichever ran (the kernels through the interpreter)."""
    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import gated_delta as rule

    _fresh_programs(monkeypatch)
    monkeypatch.setattr(ops, "_kernels_on", lambda: kernels)
    for name in ("kernel_form", "chunked_kernel_form"):
        monkeypatch.setattr(rule, name, functools.partial(
            getattr(rule, name), interpret=True))
    case = _case(np.random.default_rng(8), rows, s, h=h)
    fn = OnnxFunction(_rule_node_model(rows, s, True, h))
    family = "smt_onnx_gated_delta_lowering_total"
    before = _gauge(family, fn=fn._fn_name)
    got = fn(case)
    gained = {k: v - before.get(k, 0)
              for k, v in _gauge(family, fn=fn._fn_name).items()}
    # (the cases share a program's name, so its other counts stay as found)
    assert {k: v for k, v in gained.items() if v} == {(fn._fn_name, form): 1}
    if s == 1:
        assert _gauge("smt_onnx_recurrent_state_bytes", fn=fn._fn_name) == {
            (fn._fn_name,): rows * DK * HEADS * DV * 4}
    want, want_state = _loop_by_hand(**case)
    np.testing.assert_allclose(np.asarray(got["out"]), want, rtol=5e-5,
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(got["state_out"]), want_state,
                               rtol=5e-5, atol=5e-5)


def test_the_operator_refuses_operands_that_do_not_fit():
    case = _case(np.random.default_rng(9), 8, 4, entering=False)
    case["beta"] = case["beta"][:, :2]
    fn = OnnxFunction(_rule_node_model(8, 4, False))
    with pytest.raises(ValueError, match="GatedDeltaRule"):
        fn(case)


def test_the_trace_says_how_the_rules_ran_and_what_the_loop_carries(
        monkeypatch):
    _fresh_programs(monkeypatch)
    fn = OnnxFunction(zoo.build_model_bytes("OlmoHybridTiny", seed=7),
                      dtype_policy="bfloat16")
    name = fn._fn_name
    families = ("smt_onnx_gated_delta_lowering_total",
                "smt_onnx_attention_lowering_total",
                "smt_onnx_attention_widths_total")
    before = {f: _gauge(f, fn=name) for f in families}
    rows = 2
    fn({"input_ids": _prompts(rows, seed=4)})

    def since(family):  # counters add up over a process's traces
        return {k: v - before[family].get(k, 0)
                for k, v in _gauge(family, fn=name).items()}

    # on the CPU a decode pass's rule is the plain step (on the chip, with
    # rows in groups of 8: the kernel)
    assert since("smt_onnx_gated_delta_lowering_total") == {
        (name, "chunked"): 3, (name, "step"): 3}
    assert since("smt_onnx_attention_lowering_total") == {
        (name, "dense"): 1, (name, "masked"): 1}
    width = str(TINY["head_dim"])
    assert since("smt_onnx_attention_widths_total") == {
        (name, width, width, str(TINY["heads"])): 2}
    assert _gauge("smt_onnx_loop_trips", fn=name) == {
        (name, "decode"): GENERATE - 1}
    # what the decode passes' single steps take in: a float32 [dk, H dv] a
    # row and delta rule layer
    state = 3 * rows * DK * HEADS * DV * 4
    assert _gauge("smt_onnx_recurrent_state_bytes", fn=name) == {
        (name,): state}
    # the loop carries both kinds: the states and three convolution rows of
    # the policy's type a delta rule layer ([3, N, channels]), keys and
    # values of the attention layer, and what it fills (the last id,
    # tokens, chosen_logprob, the pooled sum)
    conv_rows = 3 * 3 * rows * CHANNELS * 2
    cache = 2 * rows * (PROMPT + GENERATE) * TINY["heads"] \
        * TINY["head_dim"] * 2
    outputs = rows * (1 + GENERATE) * 4 + rows * (GENERATE
                                                  + TINY["hidden"]) * 4
    assert _gauge("smt_onnx_loop_state_bytes", fn=name) == {
        (name,): state + conv_rows + cache + outputs}


def test_the_forms_tool_rehearses_on_the_cpu(capsys):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import gated_delta_forms

    assert gated_delta_forms.main(["--rehearse-on-cpu", "--rows", "8",
                                   "--passes", "2", "--layers", "2",
                                   "--prompt", "16"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [(line["load"], line["form"]) for line in lines] == [
        ("decode", "step"), ("decode", "kernel"), ("prompt", "chunked"),
        ("prompt", "chunked_kernel")]
    # no time from a CPU; the kernels' answers are the plain forms'
    assert not [k for line in lines for k in line if "ms" in k or "gb" in k]
    assert lines[1]["max_diff_from_step"] < 1e-5
    assert lines[3]["max_diff_out_from_chunked"] < 2e-5
    assert lines[3]["max_diff_state_from_chunked"] < 2e-5


@pytest.mark.parametrize("config,kwargs", [
    ("olmo_hybrid_7b.json", {}), ("olmo_hybrid_tiny.json", TINY)])
def test_the_builder_places_attention_where_the_configuration_says(config,
                                                                   kwargs):
    """The builder's period and offset (the published pattern by default)
    give the layer types the configuration lists, which the reference
    reads."""
    import inspect

    from synapseml_tpu.models.olmo_hybrid import olmo_hybrid

    with open(os.path.join(ROOT, "benchmark", "configs", config)) as f:
        types = json.load(f)["layer_types"]
    defaults = {k: p.default for k, p in
                inspect.signature(olmo_hybrid).parameters.items()}
    period = kwargs.get("attn_period", defaults["attn_period"])
    offset = kwargs.get("attn_offset", defaults["attn_offset"])
    assert types == ["full_attention" if i % period == offset
                     else "linear_attention" for i in range(len(types))]
