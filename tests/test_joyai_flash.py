"""The latent-attention ``joyai_llm_flash`` graph (a prompt pass in the
expanded form, then an ONNX ``Loop`` of one token a row against a cache of
latents in the absorbed form; a leading dense layer, sigmoid-routed gated
experts with a shared one; the prediction module on request) at its tiny
preset on the CPU: ``transform`` against the benchmark's plain reference,
teacher-forced; decoding through the latents against one full forward; the
shares of an expert layer against the uncut layer; ``Attention`` and the
flash kernel with a value width of their own against forms written out by
hand (``test_sdar_moe.py``'s, where it has them)."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from synapseml_tpu.models import zoo  # noqa: E402
from synapseml_tpu.onnx import builder as ob  # noqa: E402
from synapseml_tpu.onnx.importer import OnnxFunction  # noqa: E402
from tests.test_sdar_moe import (_attention_by_hand, _fresh_programs,  # noqa: E402
                                 _gauge, _model, _relative)

TINY = zoo.JOYAI_FLASH_TINY
GENERATE, PROMPT = TINY["generate"], 16
LATENT = TINY["kv_lora_rank"] + TINY["rope"]
with open(os.path.join(ROOT, "benchmark", "configs",
                       "joyai_flash_tiny.json")) as _f:
    CONFIG = json.load(_f)
COLUMNS = ("tokens", "chosen_logprob", "pooled")
DRAFTS = ("draft_tokens", "draft_logprob")


def _reference(model_bytes, **kwargs):
    from benchmark.reference import joyai_llm_flash
    from benchmark.reference.onnx_initializers import read_initializers

    config = dict(CONFIG, builder_kwargs=dict(CONFIG["builder_kwargs"],
                                              **kwargs))
    return joyai_llm_flash.Reference(config, read_initializers(model_bytes))


def _prompts(rows, seed=0, length=PROMPT):
    return np.random.default_rng(seed).integers(0, TINY["vocab"],
                                                (rows, length))


def _transform(model_bytes, prompts, policy, columns=COLUMNS):
    import jax

    from synapseml_tpu.core import Table
    from synapseml_tpu.onnx import ONNXModel

    model = ONNXModel(
        model_bytes=model_bytes, feed_dict={"input_ids": "input_ids"},
        fetch_dict={c: c for c in columns}, batch_size=len(prompts),
        dtype_policy=policy)
    with jax.default_matmul_precision("highest"):
        out = model.transform(Table({"input_ids": prompts}))
    return {c: np.asarray(out[c]) for c in columns}


def _numbers(got, replayed, prefix=""):
    from benchmark.checks import replayed_decoding as check

    return check.replay_numbers(
        replayed[prefix + "logits"], got[prefix + "tokens"],
        got[(prefix or "chosen_") + "logprob"], prefix)


# float32 policy: the program (the expanded form through Attention, then the
# cache, the loop and the absorbed products; grouped expert products) and the
# reference (one full expanded forward, a loop over experts) are the same
# arithmetic in another order: 2e-7 is read, 1e-5 allowed, and every id is
# the reference's own argmax (a gap of 0: the logits of every pass agree in
# their largest). bfloat16 policy, at this size: a router of 8 experts,
# top-2, scaled by 2.5 flips a pick on a rounding, and a flip moves a
# position's state by tenths (0.008-0.05 read a row over seeds; the
# rehearsal cell's limits are as wide for the same reason), so the limits
# only say "the same model".
@pytest.mark.parametrize("policy,limit", [
    ("float32", {"logprob": 1e-5, "gap": 1e-6, "pooled": 1e-5}),
    ("bfloat16", {"logprob": 0.06, "gap": 2.0, "pooled": 0.15})])
def test_transform_agrees_with_the_reference_teacher_forced(policy, limit,
                                                            monkeypatch):
    import jax

    _fresh_programs(monkeypatch)
    model_bytes = zoo.build_model_bytes("JoyAIFlashTiny", seed=3)
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


def test_decoding_through_the_latents_agrees_with_one_full_forward(
        monkeypatch):
    """Absorbed = expanded, inside the program: the ids a call decodes one
    at a time against its cache are the ids the PROMPT pass (the expanded
    form over every position) gives for the same prefix."""
    _fresh_programs(monkeypatch)
    prompts = _prompts(3, seed=2)
    whole = _transform(zoo.build_model_bytes("JoyAIFlashTiny", seed=4),
                       prompts, "float32")
    # the same weights generating 2 ids: id 0 is the prompt pass's
    short = zoo.build_model_bytes("JoyAIFlashTiny", seed=4, generate=2)
    for t in (1, 4, GENERATE - 1):
        prefix = np.concatenate([prompts, whole["tokens"][:, :t]], axis=1)
        again = _transform(short, prefix, "float32")
        np.testing.assert_array_equal(again["tokens"][:, 0],
                                      whole["tokens"][:, t])
        np.testing.assert_allclose(again["chosen_logprob"][:, 0],
                                   whole["chosen_logprob"][:, t],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("policy,limit", [("float32", 1e-5),
                                          ("bfloat16", 0.06)])
def test_the_prediction_module_agrees_with_the_reference(policy, limit,
                                                         monkeypatch):
    import jax

    _fresh_programs(monkeypatch)
    model_bytes = zoo.build_model_bytes("JoyAIFlashTiny", seed=5, mtp=1)
    prompts = _prompts(3, seed=6)
    got = _transform(model_bytes, prompts, policy, COLUMNS + DRAFTS)
    with jax.default_matmul_precision("highest"):
        replayed = _reference(model_bytes, mtp=1).replay(
            prompts, got["tokens"], block_rows=3)
    assert _numbers(got, replayed)["chosen_logprob.rel_rms"] < limit
    drafts = _numbers(got, replayed, "draft_")
    assert drafts["draft_logprob.rel_rms"] < limit
    if policy == "float32":
        assert drafts["draft_argmax_gap"] <= 1e-6
        # the main model's weights and ids do not depend on the module
        plain = _transform(zoo.build_model_bytes("JoyAIFlashTiny", seed=5),
                           prompts, policy)
        np.testing.assert_array_equal(plain["tokens"], got["tokens"])


# ---------------------------------------------------------------- the shares


def _share_model(first, held):
    """One expert layer of the builder's own pieces over ``u``: the router
    over every expert, ``held`` routed experts from ``first`` on, and the
    shared expert as an output of its own."""
    from synapseml_tpu.models import joyai_flash as jf
    from synapseml_tpu.models.decoder import EXPERT_DOMAIN, Weights, \
        gated_ffn, router

    z = jf._Sizes(hidden=32, experts=8, experts_held=8, expert_width=24,
                  shared_width=24)
    w = Weights(21)
    jf._expert_weights(w, z, "l1")
    nodes = []
    top_i, top_w = router(nodes, w, "s", "u", z.hidden, z.experts, 3, 2.5,
                          weights="l1")
    w.fill_all()
    for name in ("experts_up", "experts_down", "experts_gate"):
        w.store["l1_" + name] = w.store["l1_" + name][first:first + held]
    nodes.append(ob.node(
        "ExpertFFN", ["u", top_i, top_w, "l1_experts_up", "l1_experts_down",
                      "l1_experts_gate"], ["routed"], name="s_moe_experts",
        domain=EXPERT_DOMAIN, first_expert=first, num_experts=z.experts,
        activation="swiglu"))
    shared = gated_ffn(nodes.append, "s_moe_shared", "l1_shared", "u")
    graph = ob.make_graph(
        nodes, "share", [ob.value_info("u", np.float32, [3, 10, 32])],
        [ob.value_info("routed", np.float32, None),
         ob.value_info(shared, np.float32, None)], w.store)
    return ob.make_model(graph, opset=24, domains={EXPERT_DOMAIN: 1}), shared


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference(
        monkeypatch):
    """The guide's share test: each of eight chips holds ONE of the eight
    experts under the whole router; their routed parts, with the shared
    expert (which every chip computes) counted once, add up to what the
    plain reference gives for the uncut layer."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import joyai_llm_flash as ref
    from benchmark.reference.onnx_initializers import read_initializers
    from synapseml_tpu.onnx.wire import serialize_model

    _fresh_programs(monkeypatch)
    u = np.random.default_rng(8).standard_normal((3, 10, 32),
                                                 dtype=np.float32)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for first in range(8):
            model, shared = _share_model(first, 1)
            out = OnnxFunction(serialize_model(model))({"u": u})
            total = total + np.asarray(out["routed"])
        total = total + np.asarray(out[shared])  # once
        whole, _ = _share_model(0, 8)
        w = {k[3:]: jnp.asarray(np.asarray(v, np.float32)) for k, v in
             read_initializers(serialize_model(whole)).items()
             if k.startswith("l1_")}
        want = ref.experts(jnp.asarray(u), w, top_k=3, scaling=2.5,
                           first_expert=0, precision="float32")
    np.testing.assert_allclose(total, np.asarray(want), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------- operators


def _latent_case(rng, b, s_q, s_k, heads, kv, d, dv, case):
    """Feeds, attributes and the by-hand answer of one ``Attention`` whose
    values are ``dv`` wide: ``causal``, ``masked`` (a boolean mask fed at run
    time) or ``latent_slice`` (ONE key-value head whose values are the
    keys' first ``dv`` columns, an explicit scale: the absorbed form)."""
    q = rng.standard_normal((b, s_q, heads, d), dtype=np.float32)
    k = rng.standard_normal((b, s_k, kv, d), dtype=np.float32)
    v = k[..., :dv] if case == "latent_slice" else \
        rng.standard_normal((b, s_k, kv, dv), dtype=np.float32)
    attrs, mask, scale = {}, None, None
    visible = np.ones((s_q, s_k), bool)
    if case == "causal":
        attrs["is_causal"] = 1
        visible = np.arange(s_k)[None, :] <= np.arange(s_q)[:, None] \
            + (s_k - s_q)
    else:
        mask = rng.random((s_q, s_k)) < 0.6
        mask[:, 0] = True  # no row without a visible key
        visible = mask
    if case == "latent_slice":
        scale = attrs["scale"] = float((d + 5) ** -0.5)
    want = _attention_by_hand(q, k, v,
                              np.broadcast_to(visible, (b, heads, s_q, s_k)),
                              scale)
    return q, k, v, mask, attrs, want


@pytest.mark.parametrize("layout", ["batch_seq_hidden", "batch_heads_seq"])
@pytest.mark.parametrize("case,kv,lowering", [
    ("causal", 4, "dense"), ("causal", 2, "dense"), ("masked", 2, "masked"),
    ("latent_slice", 1, "masked")])
def test_attention_takes_a_value_width_of_its_own(layout, case, kv, lowering,
                                                  monkeypatch):
    import jax

    _fresh_programs(monkeypatch)
    b, s_q, s_k, heads, d, dv = 2, (1 if case == "latent_slice" else 12), \
        12, 4, 24, 16
    q, k, v, mask, attrs, want = _latent_case(
        np.random.default_rng(10), b, s_q, s_k, heads, kv, d, dv, case)
    if layout == "batch_seq_hidden":
        feeds = {n: x.reshape(*x.shape[:2], -1)
                 for n, x in zip("qkv", (q, k, v))}
        attrs.update(q_num_heads=heads, kv_num_heads=kv)
    else:
        feeds = {n: x.transpose(0, 2, 1, 3) for n, x in zip("qkv", (q, k, v))}
    if mask is not None:
        feeds["mask"] = mask
    model = _model([ob.node("Attention", list(feeds), ["y"], name="att",
                            **attrs)], feeds, ["y"])
    fn = OnnxFunction(model)
    before = {f: _gauge(f, fn=fn._fn_name) for f in (
        "smt_onnx_attention_lowering_total", "smt_onnx_attention_widths_total")}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fn(feeds)["y"])
    if layout == "batch_seq_hidden":
        assert got.shape == (b, s_q, heads * dv)
        got = got.reshape(b, s_q, heads, dv)
    else:
        assert got.shape == (b, heads, s_q, dv)
        got = got.transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def gained(family):
        return {key: n - before[family].get(key, 0)
                for key, n in _gauge(family, fn=fn._fn_name).items()
                if n != before[family].get(key, 0)}

    assert gained("smt_onnx_attention_lowering_total") == {
        (fn._fn_name, lowering): 1}
    assert gained("smt_onnx_attention_widths_total") == {
        (fn._fn_name, str(d), str(dv), str(kv)): 1}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads,kv", [(4, 4), (4, 2), (32, 32)])
def test_flash_kernel_at_192_and_128_in_interpret_mode(causal, heads, kv):
    """The kernel's own arithmetic with queries and keys of 192 and values of
    128 a head (the published widths of the expanded form), through the
    Pallas interpreter, against dense attention: on the operands where they
    lie, read with the positions minor (4 / 4, the cell's 32 / 32, grouped
    key-value heads); an explicit scale too."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    assert flash.reads_in_place(192, 128, 4) and flash.reads_in_place(192, 128, 2)
    rng = np.random.default_rng(12)
    rows = 2 if heads == 4 else 1
    q = jnp.asarray(rng.standard_normal((rows, 256, heads, 192),
                                        dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((rows, 256, kv, 192),
                                        dtype=np.float32))
    v = jnp.asarray(rng.standard_normal((rows, 256, kv, 128),
                                        dtype=np.float32))
    scale = None if kv == heads else 0.05
    with jax.default_matmul_precision("highest"):
        got = flash.flash_attention(q, k, v, causal=causal, block_q=128,
                                    block_k=128, interpret=True, scale=scale)
        want = flash.dense_attention(
            q, jnp.repeat(k, heads // kv, axis=2),
            jnp.repeat(v, heads // kv, axis=2), causal=causal, scale=scale)
    assert got.shape == (rows, 256, heads, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    with pytest.raises(ValueError, match="shape mismatch"):
        flash.flash_attention(q, k, v[:, :128], interpret=True)


@pytest.mark.parametrize("diag_rows", [128, 64, 32, 16])
@pytest.mark.parametrize("d,heads,kv,group,causal_block,s_q", [
    (192, 4, 4, 2, 1, 256),    # positions minor, two heads a step
    (192, 4, 2, 1, 1, 256),    # positions minor, grouped heads
    (128, 8, 2, 1, 1, 256),    # grouped heads: the index map divides
    (128, 8, 2, 4, 4, 128),    # four heads share a step's keys and values;
                               # block-causal, the diagonal's offset a tile
])
def test_a_tile_the_diagonal_crosses_goes_in_sub_tiles(
        diag_rows, d, heads, kv, group, causal_block, s_q):
    """The kernel on ``[B, S, H x D]`` operands with a tile the diagonal
    crosses cut into sub-tiles of every height (128 is the whole tile under
    the mask): the same answer as dense attention, and as the whole tile."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    rng = np.random.default_rng(37)
    s_k = 256
    q, k, v = (jnp.asarray(rng.standard_normal(shape, dtype=np.float32))
               for shape in ((2, s_q, heads, d), (2, s_k, kv, d),
                             (2, s_k, kv, 128)))

    minor = d % 128 != 0
    q3, k3 = q.reshape(2, s_q, -1), k.reshape(2, s_k, -1)
    if minor:
        q3, k3 = jnp.swapaxes(q3, 1, 2), jnp.swapaxes(k3, 1, 2)

    def kernel(rows):
        return flash._flash_call(
            q3, k3, v.reshape(2, s_k, -1), heads=heads, kv_heads=kv,
            batch_rep=1, group=group, causal=True, block_q=128, block_k=128,
            diag_rows=rows, interpret=True, causal_block=causal_block,
            positions_minor=minor).reshape(2, s_q, heads, 128)

    with jax.default_matmul_precision("highest"):
        got, whole = kernel(diag_rows), kernel(128)
        want = flash.dense_attention(
            q, jnp.repeat(k, heads // kv, axis=2),
            jnp.repeat(v, heads // kv, axis=2), causal=True,
            causal_block=causal_block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole), rtol=2e-5,
                               atol=2e-5)


def test_sub_tiles_need_square_tiles_on_the_diagonal():
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    # half the tile, whole lane tiles of scores and whole blocks of the
    # mask's granularity; the whole tile where the diagonal misses the corners
    assert flash._diag_rows(1024, 1024, 0, 1) == 512
    assert flash._diag_rows(1024, 1024, 3072, 4) == 512
    assert flash._diag_rows(256, 256, 0, 4) == 128
    assert flash._diag_rows(256, 256, 0, 256) == 256
    assert flash._diag_rows(128, 128, 0, 1) == 128
    assert flash._diag_rows(2048, 1024, 0, 1) == 2048
    assert flash._diag_rows(1024, 1024, 512, 1) == 1024
    x = jnp.zeros((1, 256, 128))
    with pytest.raises(ValueError, match="sub-tiles of 64 rows"):
        flash._flash_call(x, jnp.zeros((1, 320, 128)),
                          jnp.zeros((1, 320, 128)), heads=1, kv_heads=1,
                          group=1, batch_rep=1, causal=True, block_q=128,
                          block_k=64, diag_rows=64, interpret=True)


@pytest.mark.parametrize("d,dv,heads,kv,form", [
    (192, 128, 4, 4, "in_place"),       # latent attention expanded
    (128, 128, 4, 2, "in_place"),       # grouped heads of 128
    (192, 128, 4, 2, "in_place"),       # 192 with grouped heads
    (64, 64, 2, 2, "heads_first"),      # a value head under the 128 lanes
])
def test_attention_says_where_the_flash_kernel_reads_its_operands(
        d, dv, heads, kv, form, monkeypatch):
    """With the kernels on (here through the interpreter) a causal
    ``Attention`` node at kernel widths runs the flash kernel, and
    ``smt_onnx_attention_flash_form_total`` says whether on the operands
    where they lie or on copies laid out heads first."""
    import functools

    import jax

    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import flash

    _fresh_programs(monkeypatch)
    monkeypatch.setattr(ops, "_kernels_on", lambda: True)
    monkeypatch.setattr(flash, "flash_attention", functools.partial(
        flash.flash_attention, interpret=True))
    b, s = 1, 128
    q, k, v, _, attrs, want = _latent_case(
        np.random.default_rng(37), b, s, s, heads, kv, d, dv, "causal")
    feeds = {n: x.reshape(b, s, -1) for n, x in zip("qkv", (q, k, v))}
    attrs.update(q_num_heads=heads, kv_num_heads=kv)
    fn = OnnxFunction(_model([ob.node("Attention", list(feeds), ["y"],
                                      name="att", **attrs)], feeds, ["y"]))
    families = ("smt_onnx_attention_lowering_total",
                "smt_onnx_attention_flash_form_total")
    before = {f: _gauge(f, fn=fn._fn_name) for f in families}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fn(feeds)["y"]).reshape(b, s, heads, dv)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    gained = {f: {key: n - before[f].get(key, 0)
                  for key, n in _gauge(f, fn=fn._fn_name).items()
                  if n != before[f].get(key, 0)} for f in families}
    assert gained == {
        "smt_onnx_attention_lowering_total": {(fn._fn_name, "flash"): 1},
        "smt_onnx_attention_flash_form_total": {(fn._fn_name, form): 1}}


# ------------------------------------------------- the program and its notes


def test_one_program_whose_latent_weights_are_arguments_read_by_both_forms(
        monkeypatch):
    import jax

    _fresh_programs(monkeypatch)
    fn = OnnxFunction(zoo.build_model_bytes("JoyAIFlashTiny", seed=6),
                      dtype_policy="bfloat16")
    lowered = jax.jit(fn._run_positional).lower(
        _prompts(2, seed=3).astype(np.int32), *fn._weights)
    text = lowered.as_text()
    n_args = text.split("func.func public @main(")[1].split(") ->")[0]
    assert n_args.count("%arg") == 1 + len(fn._weights)
    # W_uk and W_uv once a layer, though the prompt pass multiplies latents
    # by them and a decode pass queries and contexts
    for i in range(TINY["layers"]):
        for name in (f"l{i}_uk_w", f"l{i}_uv_w"):
            assert fn._weight_names.count(name) == 1
    assert "stablehlo.while" in text
    # no literal of the size of a weight (the smallest weight that is an
    # argument has 1,024 numbers)
    biggest = max((int(np.prod([int(d) for d in m.group(1).split("x")]))
                   for m in re.finditer(
                       r"stablehlo.constant dense<[^>]*> : tensor<([\dx]+)x",
                       text)), default=0)
    assert biggest < 64 * 64
    placed = sum(w.size * 2 for w in fn._weights)
    assert _gauge("smt_onnx_weight_argument_bytes",
                  fn=fn._fn_name) == {(fn._fn_name,): placed}


def test_the_trace_says_which_form_of_attention_ran_and_what_the_loop_holds(
        monkeypatch):
    _fresh_programs(monkeypatch)
    fn = OnnxFunction(zoo.build_model_bytes("JoyAIFlashTiny", seed=7),
                      dtype_policy="bfloat16")
    name, layers = fn._fn_name, TINY["layers"]
    families = ("smt_onnx_attention_lowering_total",
                "smt_onnx_attention_widths_total",
                "smt_onnx_attention_flash_form_total",
                "smt_onnx_expert_tile_total", "smt_onnx_expert_form_total")
    before = {f: _gauge(f, fn=name) for f in families}
    rows = 2
    fn({"input_ids": _prompts(rows, seed=4)})

    def since(family):  # counters add up over a process's traces
        return {k: v - before[family].get(k, 0)
                for k, v in _gauge(family, fn=name).items()}

    # the prompt pass could have had the kernel (dense on the CPU); a decode
    # pass is masked by the run
    assert since("smt_onnx_attention_lowering_total") == {
        (name, "dense"): layers, (name, "masked"): layers}
    # no node ran the flash kernel, so none says where it read its operands
    # (on the chip: 9 ``flash``, all of them ``in_place``, + 9 ``masked``)
    assert since("smt_onnx_attention_flash_form_total") == {}
    qk = TINY["nope"] + TINY["rope"]
    assert since("smt_onnx_attention_widths_total") == {
        (name, str(qk), str(TINY["v_dim"]), str(TINY["heads"])): layers,
        (name, str(LATENT), str(TINY["kv_lora_rank"]), "1"): layers}
    assert _gauge("smt_onnx_loop_trips", fn=name) == {
        (name, "decode"): GENERATE - 1}
    # one tensor of latents a layer, and what the loop fills: the last id,
    # tokens, chosen_logprob, the pooled sum
    cache = layers * rows * (PROMPT + GENERATE) * LATENT * 2
    outputs = rows * (1 + GENERATE) * 4 + rows * (GENERATE
                                                  + TINY["hidden"]) * 4
    assert _gauge("smt_onnx_loop_state_bytes", fn=name) == {
        (name,): cache + outputs}
    # two expert layers, each traced in both passes: a decode pass brings
    # rows x top_k pairs, fewer than experts (expected pairs an expert 0)
    assert since("smt_onnx_expert_tile_total") == {(name, "128"): 4}
    assert since("smt_onnx_expert_form_total") == {(name, "swiglu"): 4}
    assert _gauge("smt_onnx_expert_chunk_rows", fn=name) == {(name,): 128}


def test_builder_refuses_sizes_the_graph_cannot_have():
    from synapseml_tpu.models.joyai_flash import joyai_flash

    for kwargs in ({"layers": 1}, {"generate": 1}, {"rope": 7}, {"mtp": 2}):
        with pytest.raises(ValueError, match="layers"):
            joyai_flash(**{**TINY, **kwargs})


def test_the_flash_width_tool_rehearses_on_the_cpu(capsys):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import flash_width_forms

    assert flash_width_forms.main(["--rehearse-on-cpu"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    forms = ["heads_first", "in_place", "in_place:64", "in_place:32",
             "in_place:64:g2", "shipped"]
    assert [(line["load"], line["form"]) for line in lines] == [
        (load, form) for load in ("joyai", "nemotron", "sdar")
        for form in forms]
    assert all(line["max_abs_diff"] < 0.05 and "ms" not in line
               and line["max_abs_from_first_form"] < 0.01 for line in lines)
