"""The block-diffusion ``sdar_moe`` graph (an ONNX ``Loop`` carrying a
key-value cache, block-masked attention, rotary positions, gated experts) at
its tiny preset on the CPU: ``transform`` against the benchmark's plain
reference replayed at every (block, pass), cached passes against the uncached
full forward, and each new operator against a form written out by hand."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from synapseml_tpu.models import zoo  # noqa: E402
from synapseml_tpu.onnx import builder as ob  # noqa: E402
from synapseml_tpu.onnx.importer import OnnxFunction  # noqa: E402
from synapseml_tpu.onnx.wire import serialize_model  # noqa: E402

TINY = zoo.SDAR_MOE_TINY
BLOCK, PASSES, GENERATE = TINY["block"], TINY["passes"], TINY["generate"]
CONFIG = {
    "num_hidden_layers": TINY["layers"], "hidden_size": TINY["hidden"],
    "num_attention_heads": TINY["heads"],
    "num_key_value_heads": TINY["kv_heads"], "head_dim": TINY["head_dim"],
    "num_experts": TINY["experts"], "num_experts_per_tok": TINY["top_k"],
    "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "mask_token_id": TINY["mask_id"],
    "builder_kwargs": {"block": BLOCK, "passes": PASSES},
}
PROMPT = 16
# the row tile of ``ops._expert_tiling`` at its smallest
SMALL_TILE = 128


def _reference(model_bytes):
    from benchmark.reference import sdar_moe
    from benchmark.reference.onnx_initializers import read_initializers

    return sdar_moe.Reference(CONFIG, read_initializers(model_bytes))


def _prompts(rows, seed=0, length=PROMPT):
    return np.random.default_rng(seed).integers(0, TINY["vocab"],
                                                (rows, length))


def _relative(got, want):
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


def _gauge(family, **where):
    from synapseml_tpu.observability.metrics import get_registry

    fam = get_registry().snapshot()["families"].get(family) or {}
    names = fam.get("labelnames", [])
    return {tuple(s["labels"]): s.get("value", s.get("count"))
            for s in fam.get("series", [])
            if all(dict(zip(names, s["labels"])).get(k) == v
                   for k, v in where.items())}


def _fresh_programs(monkeypatch):
    import weakref

    from synapseml_tpu.onnx import importer

    monkeypatch.setattr(importer, "_PROGRAMS", weakref.WeakValueDictionary())


def _transform(model_bytes, prompts, policy):
    import jax

    from synapseml_tpu.core import Table
    from synapseml_tpu.onnx import ONNXModel

    model = ONNXModel(
        model_bytes=model_bytes, feed_dict={"input_ids": "input_ids"},
        fetch_dict={c: c for c in ("tokens", "unmask_pass", "chosen_logprob",
                                   "pooled")},
        batch_size=len(prompts), dtype_policy=policy)
    with jax.default_matmul_precision("highest"):
        out = model.transform(Table({"input_ids": prompts}))
    return {c: np.asarray(out[c]) for c in ("tokens", "unmask_pass",
                                            "chosen_logprob", "pooled")}


# float32 policy: the program (cache, loop, grouped products) and the
# reference (one full forward a state, a loop over experts) are the same
# arithmetic in another order: 1e-4 leaves two orders of magnitude over the
# 8e-7 read, and a choice the reference would not have made reads as a gap of
# tenths of a unit. bfloat16 policy: every node hands on 8 bits of mantissa,
# the logits too; 0.03 / 0.1 in logits' standard deviations is what a
# rounding of two near-tied logits can move (0.007 / 0.015 read), 0.02 of
# relative error in a log-probability of about -3.5 (0.003 read).
@pytest.mark.parametrize("policy,limit", [
    ("float32", {"logprob": 1e-4, "argmax_gap": 1e-4, "confidence_gap": 1e-4}),
    ("bfloat16", {"logprob": 0.02, "argmax_gap": 0.03,
                  "confidence_gap": 0.1})])
def test_transform_agrees_with_the_reference_replayed_at_every_pass(
        policy, limit):
    from benchmark.checks import replayed_generation as check

    model_bytes = zoo.build_model_bytes("SDARMoETiny", seed=3)
    prompts = _prompts(5, seed=1)
    got = _transform(model_bytes, prompts, policy)
    assert got["tokens"].shape == (5, GENERATE)
    assert not (got["tokens"] == TINY["mask_id"]).any()
    assert check.schedule_mismatch(got["unmask_pass"], BLOCK, PASSES) == 0
    blocks = [list(range(GENERATE // BLOCK))] * len(prompts)
    logits = _reference(model_bytes).replay(
        prompts, got["tokens"], got["unmask_pass"], blocks, block_rows=2
    )["logits"]
    numbers = check.replay_numbers(logits, got["tokens"], got["unmask_pass"],
                                   got["chosen_logprob"], blocks, BLOCK,
                                   TINY["mask_id"])
    assert numbers["chosen_logprob.rel_rms"] < limit["logprob"]
    assert numbers["argmax_gap"] < limit["argmax_gap"]
    assert numbers["confidence_gap"] < limit["confidence_gap"]


# the commit passes read the prompt's and the earlier blocks' keys and values
# from the cache; the reference runs one uncached forward over the row's
# final ids. float32: rounding alone (2e-7 read); bfloat16: 0.015 read with
# the reference in float32, 0.05 leaves a float8 product's 0.2 outside.
@pytest.mark.parametrize("policy,limit", [("float32", 1e-5),
                                          ("bfloat16", 0.05)])
def test_cached_passes_agree_with_the_uncached_full_forward(policy, limit):
    model_bytes = zoo.build_model_bytes("SDARMoETiny", seed=4)
    prompts = _prompts(4, seed=2, length=24)
    got = _transform(model_bytes, prompts, policy)
    want = _reference(model_bytes).pooled(prompts, got["tokens"])
    assert got["pooled"].dtype == np.float32
    assert _relative(got["pooled"], want) < limit


def test_a_tampered_choice_or_weight_shows_in_the_replay():
    from benchmark.checks import replayed_generation as check
    from benchmark.reference.onnx_initializers import read_initializers

    model_bytes = zoo.build_model_bytes("SDARMoETiny", seed=3)
    prompts = _prompts(3, seed=5)
    got = _transform(model_bytes, prompts, "float32")
    blocks = [list(range(GENERATE // BLOCK))] * len(prompts)
    reference = _reference(model_bytes)

    def numbers(tokens, ref=reference):
        logits = ref.replay(prompts, tokens, got["unmask_pass"],
                            blocks)["logits"]
        return check.replay_numbers(logits, tokens, got["unmask_pass"],
                                    got["chosen_logprob"], blocks, BLOCK,
                                    TINY["mask_id"])

    tampered = got["tokens"].copy()
    tampered[1, 5] = (tampered[1, 5] + 1) % (TINY["vocab"] - 1)
    assert numbers(tampered)["argmax_gap"] > 0.3
    from benchmark.reference import sdar_moe

    weights = dict(read_initializers(model_bytes))
    weights["l1_o_w"] = np.asarray(weights["l1_o_w"]).astype(np.float32) * 1.5
    other = sdar_moe.Reference(CONFIG, weights)
    assert numbers(got["tokens"], other)["chosen_logprob.rel_rms"] > 0.01


def test_one_dispatch_a_bucket_and_weights_are_arguments(monkeypatch):
    import jax

    _fresh_programs(monkeypatch)
    model_bytes = zoo.build_model_bytes("SDARMoETiny", seed=6)
    fn = OnnxFunction(model_bytes, dtype_policy="bfloat16")
    prompts = _prompts(2, seed=3)
    lowered = jax.jit(fn._run_positional).lower(prompts.astype(np.int32),
                                                *fn._weights)
    text = lowered.as_text()
    # the bodies read the outer graph's weights: each is one argument of the
    # program, the embedding (three passes read it) among them
    n_args = text.split("func.func public @main(")[1].split(") ->")[0]
    assert n_args.count("%arg") == 1 + len(fn._weights)
    assert "l0_experts_gate" in fn._weight_names
    assert "stablehlo.while" in text
    placed = sum(w.size * 2 for w in fn._weights)
    assert _gauge("smt_onnx_weight_argument_bytes",
                  fn=fn._fn_name) == {(fn._fn_name,): placed}
    # no literal of the size of a weight (the largest tiny weight is the
    # embedding, 256 x 64)
    import re

    biggest = max((int(np.prod([int(d) for d in m.group(1).split("x")]))
                   for m in re.finditer(
                       r"stablehlo.constant dense<[^>]*> : tensor<([\dx]+)x",
                       text)), default=0)
    assert biggest < 64 * 64


def test_loop_gauges_count_the_passes_of_a_call(monkeypatch):
    _fresh_programs(monkeypatch)
    model_bytes = zoo.build_model_bytes("SDARMoETiny", seed=7)
    fn = OnnxFunction(model_bytes, dtype_policy="bfloat16")
    name = fn._fn_name
    before = {family: _gauge(family, fn=name) for family in (
        "smt_onnx_attention_lowering_total", "smt_onnx_expert_form_total",
        "smt_onnx_attention_flash_form_total")}

    def since(family):  # counters add up over a process's traces
        return {k: v - before[family].get(k, 0)
                for k, v in _gauge(family, fn=name).items()}

    fn({"input_ids": _prompts(2, seed=4)})
    trips = _gauge("smt_onnx_loop_trips", fn=name)
    assert trips == {(name, "blocks"): GENERATE // BLOCK,
                     (name, "passes"): PASSES * GENERATE // BLOCK}
    assert sum(trips.values()) == (PASSES + 1) * GENERATE // BLOCK
    # the caches: 2 a layer of [rows, S + G, kv * size] bfloat16, and the
    # outputs the loop fills (tokens, unmask_pass, chosen_logprob, pooled sum)
    cache = 2 * TINY["layers"] * 2 * (PROMPT + GENERATE) \
        * TINY["kv_heads"] * TINY["head_dim"] * 2
    outputs = 2 * (3 * GENERATE + TINY["hidden"]) * 4
    assert _gauge("smt_onnx_loop_state_bytes", fn=name) == {
        (name,): cache + outputs}
    lowering = since("smt_onnx_attention_lowering_total")
    # the prompt's pass could have had the kernel (dense on the CPU); a pass
    # against the cache is masked by the run: a kind of its own
    assert lowering == {(name, "dense"): TINY["layers"],
                        (name, "masked"): 2 * TINY["layers"]}
    # no node ran the flash kernel, so none says where it read its operands
    # (on the chip: 6 ``flash``, all of them ``in_place``, + 12 ``cached``)
    assert since("smt_onnx_attention_flash_form_total") == {}
    assert since("smt_onnx_expert_form_total") == {
        (name, "swiglu"): 3 * TINY["layers"]}


# ---------------------------------------------------------------- operators


def _model(nodes, inputs, outputs, initializers=None, opset=24, domain=""):
    graph = ob.make_graph(
        nodes, "test",
        [ob.value_info(k, v.dtype, list(v.shape)) for k, v in inputs.items()],
        [ob.value_info(o, np.float32, None) for o in outputs],
        initializers or {})
    return serialize_model(ob.make_model(
        graph, opset=opset, domains={domain: 1} if domain else None))


def _counting_loop(trips, carried_out="acc_out", scan=False, cond_out=None,
                   trips_fed=False, cond_in=""):
    """``acc <- acc * 2 + i`` over ``trips`` trips, ``acc [2, 3]`` float32."""
    body_nodes = [
        ob.node("Cast", ["i"], ["i_f"], to=1),
        ob.node("Mul", ["acc", "two"], ["twice"]),
        ob.node("Add", ["twice", "i_f"], ["acc_out"]),
        ob.node("Concat", ["acc", "acc"], ["acc_wide"], axis=1),
        ob.node("Identity", ["keep"], ["keep_out"]),
        ob.node("Less", ["i_f", "two"], ["keep_traced"]),
    ]
    outs = [cond_out or "keep_out", carried_out] + (["twice"] if scan else [])
    body = ob.make_graph(
        body_nodes, "body",
        [ob.value_info("i", np.int64, []), ob.value_info("keep", np.bool_, []),
         ob.value_info("acc", np.float32, [2, 3])],
        [ob.value_info(o, np.float32, None) for o in outs])
    inits = {"two": np.asarray(2.0, np.float32)}
    if not trips_fed:
        inits["trips"] = np.asarray(trips, np.int64)
    if cond_in == "false":
        inits["go"] = np.asarray(False)
    loop_outs = ["y"] + (["ys"] if scan else [])
    return [ob.node("Loop", ["trips", "go" if cond_in else "", "x"],
                    loop_outs, name="count", body=body)], inits, loop_outs


def test_loop_runs_its_static_trip_count_over_carried_values():
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    nodes, inits, outs = _counting_loop(5)
    got = OnnxFunction(_model(nodes, {"x": x}, outs, inits))({"x": x})["y"]
    want = x.copy()
    for i in range(5):
        want = want * 2 + i
    np.testing.assert_allclose(np.asarray(got), want)
    # the body reads the outer graph's ``two``; the loop is one while
    assert _gauge("smt_onnx_loop_trips", loop="count")[
        ("onnx.test", "count")] == 5


@pytest.mark.parametrize("what,kwargs,error,says", [
    ("trip_count_at_run_time", dict(trips_fed=True), NotImplementedError,
     "trip count"),
    ("condition_not_true", dict(cond_in="false"), NotImplementedError,
     "condition"),
    ("condition_computed_in_the_body", dict(cond_out="keep_traced"),
     NotImplementedError, "computes its condition"),
    ("scan_outputs", dict(scan=True), NotImplementedError, "scan outputs"),
    ("carried_shape_changes", dict(carried_out="acc_wide"), ValueError,
     r"enters as float32\[2, 3\] and leaves as float32\[2, 6\]"),
    ("carried_type_changes", dict(carried_out="keep_traced"), ValueError,
     "leaves as bool")])
def test_loop_refuses_by_name_what_it_does_not_lower(what, kwargs, error,
                                                     says):
    x = np.ones((2, 3), np.float32)
    nodes, inits, outs = _counting_loop(3, **kwargs)
    feeds = {"x": x}
    if kwargs.get("trips_fed"):
        feeds["trips"] = np.asarray(3, np.int64)
    with pytest.raises(error, match="Loop count.*" + says):
        OnnxFunction(_model(nodes, feeds, outs, inits))(feeds)


def _rotated_by_hand(x, positions, theta, interleaved, rot):
    """``x [b, s, heads, size]`` float64, pair by pair."""
    out = x.copy()
    half = rot // 2
    for j in range(half):
        angle = positions[..., None] * theta ** (-2.0 * j / rot)  # [b, s, 1]
        a, b = (2 * j, 2 * j + 1) if interleaved else (j, j + half)
        out[..., a] = x[..., a] * np.cos(angle) - x[..., b] * np.sin(angle)
        out[..., b] = x[..., b] * np.cos(angle) + x[..., a] * np.sin(angle)
    return out


@pytest.mark.parametrize("layout", ["batch_seq_hidden", "batch_heads_seq"])
@pytest.mark.parametrize("interleaved,rot,with_ids", [
    (0, 8, True), (1, 8, True), (0, 4, True), (0, 8, False)])
def test_rotary_embedding_turns_pairs_by_their_position(layout, interleaved,
                                                        rot, with_ids):
    rng = np.random.default_rng(8)
    b, s, heads, size, theta = 2, 5, 3, 8, 100.0
    x = rng.standard_normal((b, s, heads, size), dtype=np.float32)
    positions = rng.integers(0, 40, (b, s))
    angles = np.arange(40)[:, None] * theta ** (
        -2.0 * np.arange(rot // 2) / rot)[None, :]
    cos, sin = (f(angles).astype(np.float32) for f in (np.cos, np.sin))
    want = _rotated_by_hand(x.astype(np.float64), positions, theta,
                            interleaved, rot)
    attrs = dict(interleaved=interleaved)
    if rot != size:
        attrs["rotary_embedding_dim"] = rot
    if layout == "batch_seq_hidden":
        fed, attrs["num_heads"] = x.reshape(b, s, heads * size), heads
        want = want.reshape(b, s, heads * size)
    else:
        fed, want = x.transpose(0, 2, 1, 3), want.transpose(0, 2, 1, 3)
    feeds = {"x": fed}
    if with_ids:
        inits = {"cos": cos, "sin": sin, "ids": positions.astype(np.int64)}
    else:  # the caches are the rows' own angles
        inits = {"cos": cos[positions], "sin": sin[positions]}
    model = _model([ob.node("RotaryEmbedding", ["x"] + list(inits), ["y"],
                            **attrs)], feeds, ["y"], inits)
    got = OnnxFunction(model)(feeds)["y"]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    narrow = OnnxFunction(model, dtype_policy="bfloat16")(feeds)["y"]
    assert _relative(narrow, want) < 0.01


def _attention_by_hand(q, k, v, visible, scale=None):
    """``q [b, sq, h, d]``, ``k [b, sk, hkv, d]``, ``v [b, sk, hkv, dv]``,
    ``visible [b, h, sq, sk]``; float64 ``[b, sq, h, dv]``."""
    b, sq, h, d = q.shape
    rep = h // k.shape[2]
    out = np.zeros((b, sq, h, v.shape[-1]), dtype=np.float64)
    for n in range(b):
        for head in range(h):
            s = q[n, :, head].astype(np.float64) @ k[n, :, head // rep].T \
                * (scale or 1 / np.sqrt(d))
            s = np.where(visible[n, head], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[n, :, head] = (p / p.sum(-1, keepdims=True)) @ v[n, :,
                                                                 head // rep]
    return out


def _qkv(rng, b, sq, sk, heads, kv, d):
    return (rng.standard_normal((b, sq, heads, d), dtype=np.float32),
            rng.standard_normal((b, sk, kv, d), dtype=np.float32),
            rng.standard_normal((b, sk, kv, d), dtype=np.float32))


@pytest.mark.parametrize("mask_kind,lowering", [
    ("block_causal_constant", "dense"), ("arbitrary_constant", "masked"),
    ("fed_at_run_time", "masked"), ("per_head", "masked")])
def test_attention_takes_a_boolean_mask(mask_kind, lowering, monkeypatch):
    import jax

    _fresh_programs(monkeypatch)
    rng = np.random.default_rng(9)
    b, s, heads, kv, d = 2, 12, 4, 2, 8
    q, k, v = _qkv(rng, b, s, s, heads, kv, d)
    if mask_kind == "block_causal_constant":
        blocks = np.arange(s) // 4
        mask = blocks[None, :] <= blocks[:, None]
    elif mask_kind == "per_head":
        mask = rng.random((1, heads, s, s)) < 0.6
        mask[..., 0] = True
    else:
        mask = rng.random((s, s)) < 0.6
        mask[:, 0] = True  # no row without a visible key
    want = _attention_by_hand(q, k, v, np.broadcast_to(mask, (b, heads, s, s)))
    feeds = {n: x.reshape(b, s, -1) for n, x in zip("qkv", (q, k, v))}
    inits = {}
    if mask_kind == "fed_at_run_time":
        feeds["mask"] = mask
    else:
        inits["mask"] = mask
    model = _model([ob.node("Attention", ["q", "k", "v", "mask"], ["y"],
                            name="att", q_num_heads=heads, kv_num_heads=kv)],
                   feeds, ["y"], inits)
    fn = OnnxFunction(model)
    before = _gauge("smt_onnx_attention_lowering_total", fn=fn._fn_name)
    with jax.default_matmul_precision("highest"):
        got = fn(feeds)["y"]
    np.testing.assert_allclose(np.asarray(got).reshape(b, s, heads, d), want,
                               rtol=1e-5, atol=1e-5)
    after = _gauge("smt_onnx_attention_lowering_total", fn=fn._fn_name)
    assert {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)} == {(fn._fn_name, lowering): 1}


@pytest.mark.parametrize("policy,limit", [("float32", 1e-5),
                                          ("bfloat16", 0.02)])
def test_attention_of_a_few_queries_against_a_longer_key_axis(policy, limit):
    """Four queries, a cache of 24 positions of which the run says how many
    are filled: grouped key-value heads, never repeated."""
    import jax

    rng = np.random.default_rng(10)
    b, sq, sk, heads, kv, d = 3, 4, 24, 4, 2, 8
    q, k, v = _qkv(rng, b, sq, sk, heads, kv, d)
    filled = np.asarray(17, np.int64)
    nodes = [ob.node("Less", ["positions", "filled"], ["visible"]),
             ob.node("Unsqueeze", ["visible", "axes_0"], ["mask"]),
             ob.node("Attention", ["q", "k", "v", "mask"], ["y"],
                     q_num_heads=heads, kv_num_heads=kv)]
    feeds = {"q": q.reshape(b, sq, -1), "k": k.reshape(b, sk, -1),
             "v": v.reshape(b, sk, -1), "filled": filled}
    model = _model(nodes, feeds, ["y"],
                   {"positions": np.arange(sk), "axes_0": np.asarray([0])})
    if policy == "bfloat16":
        import jax.numpy as jnp

        q, k, v = (np.asarray(jnp.asarray(x, jnp.bfloat16).astype(
            jnp.float32)) for x in (q, k, v))
    visible = np.broadcast_to(np.arange(sk) < 17, (b, heads, sq, sk))
    want = _attention_by_hand(q, k, v, visible)
    with jax.default_matmul_precision("highest"):
        got = OnnxFunction(model, dtype_policy=policy)(feeds)["y"]
    assert _relative(np.asarray(got).reshape(b, sq, heads, d), want) < limit


# heads / key-value heads, queries, cache length, (rows, keys) a step or None
# for what the kernel picks, scale, a fill of its own a row
_CACHED_CASES = {
    "rep_1": (4, 4, 4, 24, None, None, False),
    "rep_2": (4, 2, 4, 24, None, None, False),
    "rep_8_one_query": (8, 1, 1, 24, None, None, False),
    # 17 and the rows' own fills end inside a block of 8 keys
    "fill_inside_a_key_block": (4, 2, 4, 24, (3, 8), None, False),
    # 40 = two blocks of 16 and one that hangs over the cache's end
    "longer_than_a_key_block": (4, 2, 4, 40, (1, 16), None, True),
    "a_fill_a_row_and_scale": (8, 2, 1, 32, (3, 16), 0.3, True),
    "scale": (4, 1, 4, 24, None, 0.21, False),
    # query rows a key-value head that are no whole sublane tile
    "one_query_a_head": (4, 4, 1, 24, None, None, False),
    "twenty_rows_a_head": (20, 1, 1, 24, None, None, False),
}


@pytest.mark.parametrize("case", list(_CACHED_CASES))
@pytest.mark.parametrize("policy,limit", [("float32", 1e-5),
                                          ("bfloat16", 0.02)])
def test_the_cached_kernel_in_interpret_mode(policy, limit, case,
                                             monkeypatch):
    """``flash.cached_attention`` run by the Pallas interpreter against the
    form written out by hand: grouped heads, one query and four, a fill that
    ends inside a key block, a cache of several key blocks (the online
    softmax, the last block hanging over the end), a fill of its own a row,
    ``scale``."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    heads, kv, sq, sk, blocks, scale, per_row = _CACHED_CASES[case]
    rng = np.random.default_rng(11)
    b, d = 3, 8
    dtype = jnp.float32 if policy == "float32" else jnp.bfloat16
    q, k, v = (np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))
               for x in _qkv(rng, b, sq, sk, heads, kv, d))
    if per_row:
        filled = np.asarray([sk, 5, sk - 9])[:, None, None, None]
    else:
        filled = np.asarray(17).reshape(1, 1)
    mask = np.arange(sk) < filled            # [b, 1, 1, sk] or [1, sk]
    if blocks is not None:
        monkeypatch.setattr(flash, "_cached_blocks", lambda *a: blocks)
    with jax.default_matmul_precision("highest"):
        got = flash.cached_attention(
            *(jnp.asarray(x, dtype) for x in (q, k, v)), jnp.asarray(mask),
            scale=scale, interpret=True)
    assert got.shape == q.shape and got.dtype == dtype
    want = _attention_by_hand(
        q, k, v, np.broadcast_to(mask.reshape(-1, 1, 1, sk),
                                 (b, heads, sq, sk)), scale=scale)
    assert _relative(np.asarray(got.astype(jnp.float32)), want) < limit


def test_the_cached_kernel_refuses_a_mask_a_query_has_to_itself():
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    q, k, v = (jnp.asarray(x) for x in _qkv(np.random.default_rng(3),
                                            2, 4, 16, 4, 2, 8))
    with pytest.raises(ValueError, match="mask over key positions"):
        flash.cached_attention(q, k, v, jnp.ones((4, 16), bool),
                               interpret=True)


@pytest.mark.parametrize("mask_kind,lowering", [
    ("key_only", "cached"), ("key_only_a_row", "cached"),
    ("per_head", "masked"), ("per_query", "masked"),
    ("latent_576_512_1", "masked"), ("size_64", "masked"),
    ("one_query_a_head", "cached")])
def test_attention_picks_the_cached_kernel_by_what_it_sees(
        mask_kind, lowering, monkeypatch):
    """With the kernels on, a run-time mask over key positions alone and one
    head size of a multiple of 128 count ``cached`` and run the kernel (one
    query on a key-value head of its own too); a mask a head or a query has
    to itself, latent attention's absorbed form
    (576-wide keys, 512-wide values, one key-value head) and a head size of
    64 count ``masked`` and trace to ``masked_attention``."""
    import functools

    import jax

    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import flash

    _fresh_programs(monkeypatch)
    rng = np.random.default_rng(12)
    b, sq, sk, heads, kv, d, dv = 2, 4, 16, 4, 2, 128, 128
    if mask_kind == "latent_576_512_1":
        kv, d, dv = 1, 576, 512
    elif mask_kind == "size_64":
        d = dv = 64
    elif mask_kind == "one_query_a_head":
        sq, kv = 1, heads
    q = rng.standard_normal((b, sq, heads, d), dtype=np.float32)
    k = rng.standard_normal((b, sk, kv, d), dtype=np.float32)
    v = k[..., :dv] if dv != d else \
        rng.standard_normal((b, sk, kv, dv), dtype=np.float32)
    shape = {"key_only_a_row": (b, 1, 1, sk), "per_head": (1, heads, 1, sk),
             "per_query": (sq, sk)}.get(mask_kind, (1, sk))
    mask = rng.random(shape) < 0.6
    mask[..., 0] = True
    ran = []

    def counting(name, fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            ran.append(name)
            return fn(*args, **kwargs)
        return run

    monkeypatch.setattr(ops, "_kernels_on", lambda: True)
    monkeypatch.setattr(flash, "cached_attention", counting(
        "cached", functools.partial(flash.cached_attention, interpret=True)))
    monkeypatch.setattr(flash, "masked_attention",
                        counting("masked", flash.masked_attention))
    feeds = {"q": q.reshape(b, sq, -1), "k": k.reshape(b, sk, -1),
             "v": v.reshape(b, sk, -1), "mask": mask}
    fn = OnnxFunction(_model(
        [ob.node("Attention", ["q", "k", "v", "mask"], ["y"], name="att",
                 q_num_heads=heads, kv_num_heads=kv)], feeds, ["y"]))
    before = _gauge("smt_onnx_attention_lowering_total", fn=fn._fn_name)
    with jax.default_matmul_precision("highest"):
        got = fn(feeds)["y"]
    after = _gauge("smt_onnx_attention_lowering_total", fn=fn._fn_name)
    assert {key: n - before.get(key, 0) for key, n in after.items()
            if n != before.get(key, 0)} == {(fn._fn_name, lowering): 1}
    assert ran == [lowering]
    want = _attention_by_hand(
        q, k, v, np.broadcast_to(mask.reshape((1,) * (4 - mask.ndim)
                                              + mask.shape),
                                 (b, heads, sq, sk)))
    np.testing.assert_allclose(np.asarray(got).reshape(b, sq, heads, dv),
                               want, rtol=1e-5, atol=1e-5)


def test_the_cached_kernel_takes_whole_tiles_and_fits_its_memory():
    """What the dispatch asks of the shapes, and the rows and keys a step
    that follow from the VMEM: the cell's (128 rows, 32 query rows a head, a
    cache of 320: the whole key axis, a divisor of the rows), a cache beyond
    one block (blocks of a multiple of 128 keys), and query rows that are no
    whole sublane tile (``olmo_hybrid_7b``'s one a head against 384 keys:
    the whole key axis, 16 rows a step)."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    def takes(q, k, v=None, mask=(1, 320), dtype=jnp.bfloat16):
        return flash.cached_attention_takes(q, k, v or k, mask, dtype)

    cell = (128, 4, 32, 128), (128, 320, 4, 128)
    assert takes(*cell) and takes(*cell, mask=(128, 1, 1, 320))
    assert takes((16, 1, 32, 128), (16, 320, 1, 128))     # 32 heads on one
    assert not takes(*cell, mask=(4, 320))                # a mask a query
    assert not takes(*cell, mask=(1, 32, 1, 320))         # a mask a head
    assert not takes((16, 1, 32, 576), (16, 320, 1, 576),
                     (16, 320, 1, 512))                   # latent, absorbed
    assert not takes((128, 4, 32, 64), (128, 320, 4, 64))
    assert takes((128, 1, 4, 128), (128, 320, 4, 128))        # 1 query row
    assert not takes((128, 64, 32, 128), (128, 320, 4, 128))  # 512 of them
    assert takes((128, 1, 32, 128), (128, 320, 4, 128), dtype=jnp.float32)
    assert takes((128, 1, 32, 128), (128, 320, 4, 128))       # half a tile
    assert takes((128, 1, 30, 128), (128, 384, 30, 128), mask=(1, 384))
    assert takes((128, 1, 20, 128), (128, 256, 1, 128), mask=(1, 256))
    # one query row fills a bfloat16 tile of 16 in VMEM, and is counted so
    rows, keys = flash._cached_blocks(128, 1, 384, 128, 2)
    assert (rows, keys) == flash._cached_blocks(128, 16, 384, 128, 2) \
        == (16, 384)
    assert rows * keys * (4 * 128 * 2 + 12 * 16) <= flash._CACHED_VMEM
    rows, keys = flash._cached_blocks(128, 32, 320, 128, 2)
    assert keys == 320 and 128 % rows == 0 and rows >= 8
    rows, keys = flash._cached_blocks(16, 32, 4224, 128, 2)
    assert keys % 128 == 0 and 128 <= keys < 4224 and rows == 8
    assert flash._cached_blocks(3, 32, 320, 128, 2) == (3, 320)
    for n, length in ((32, 320), (128, 320), (32, 65536)):
        rows, keys = flash._cached_blocks(128, n, length, 128, 2)
        assert rows * keys * (4 * 128 * 2 + 12 * n) <= flash._CACHED_VMEM


def test_the_cached_attention_tool_rehearses_on_the_cpu(capsys):
    """``tools/cached_attention_forms.py --rehearse-on-cpu``: every form of
    the kernel answers within a bfloat16 step of the dense form, the kernel's
    forms count ``cached`` and the dense one ``masked``, none with a time."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "cached_attention_forms",
        os.path.join(ROOT, "tools", "cached_attention_forms.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--rehearse-on-cpu", "--loads",
                      "cell,long,olmo,jamba"]) == 0
    head, *lines = [json.loads(line)
                    for line in capsys.readouterr().out.splitlines()]
    assert head["rehearsal"] and head["device"]["platform"] == "cpu"
    assert [(line["load"], line["form"]) for line in lines] == [
        (load, form) for load in ("cell", "long", "olmo", "jamba")
        for form in tool.TOY_FORMS[load].split(",")]
    for line in lines:
        assert line["finite"] and line["same_bits_twice"]
        assert line["max_abs_from_dense"] < 0.02 and "ms_a_layer_and_pass" \
            not in line
        assert line["lowering"] == {
            "masked" if line["form"] == "dense" else "cached": line["layers"]}
    assert [line["rows_keys"] for line in lines[1:5]] == [
        [2, 48], [4, 48], [2, 16], [4, 32]]
    # the readers of a compiled loop body: one copy of a cache, one of less;
    # one float32 convert of a cache, one to bfloat16, one outside the loop
    text = """
%body.1 (p: (s32[], bf16[4,48,256])) -> (s32[], bf16[4,48,256]) {
  %copy.3 = bf16[4,48,2,128]{3,2,1,0} copy(%x)
  %copy.4 = bf16[4,4,256]{2,1,0} copy(%y)
  %fusion.2 = bf16[4,48,256]{2,1,0} fusion(%z), kind=kLoop
  %convert.5 = f32[4,48,256]{1,2,0} convert(%fusion.2)
  %convert_fusion.6 = bf16[4,48,256]{2,1,0} fusion(%convert.5), kind=kLoop
}
ENTRY %main (a: bf16[4,48,256]) -> bf16[4,48,256] {
  %copy.9 = bf16[4,48,256]{2,1,0} copy(%a)
  %convert.10 = f32[4,48,256]{2,1,0} convert(%a)
  %while.1 = (s32[], bf16[4,48,256]) while(%t), condition=%cond.1, body=%body.1
}
"""
    assert tool.cache_copies_in_loop(text, 4 * 48 * 256) == 1
    assert tool.cache_converts_in_loop(text, 4 * 48 * 256) == 1


@pytest.mark.parametrize("block", [2, 4, 8])
@pytest.mark.parametrize("s_q,s_k", [(16, 16), (8, 24)])
def test_flash_block_granular_causal_mask_in_interpret_mode(block, s_q, s_k):
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel.flash import dense_attention, flash_attention

    rng = np.random.default_rng(11)
    q, k, v = _qkv(rng, 2, s_q, s_k, 4, 2, 16)
    off = s_k - s_q
    visible = (np.arange(s_k)[None, :]
               <= ((np.arange(s_q)[:, None] + off) | (block - 1)))
    want = _attention_by_hand(q, k, v, np.broadcast_to(visible,
                                                       (2, 4, s_q, s_k)))
    with jax.default_matmul_precision("highest"):
        got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True, causal_block=block, block_q=8,
                              block_k=8, interpret=True)
        dense = dense_attention(jnp.asarray(q),
                                jnp.repeat(jnp.asarray(k), 2, axis=2),
                                jnp.repeat(jnp.asarray(v), 2, axis=2),
                                causal=True, causal_block=block)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dense), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal_block", [1, 4])
@pytest.mark.parametrize("s_q,s_k,diag_rows", [
    (256, 256, 64),     # the prompt's pass: two sub-tiles a diagonal tile
    (128, 384, 32),     # fewer queries than keys, the offset whole tiles
    (128, 320, 128),    # an offset off the tiles: the tile whole, masked
])
def test_flash_on_operands_where_they_lie_at_32_over_4_heads_of_128(
        causal_block, s_q, s_k, diag_rows):
    """``sdar_30b_a3b``'s prompt attention (32 query heads over 4 key-value
    heads of 128, causal at a granularity of 4 positions) through the kernel
    on ``[B, S, H x D]`` operands, in the interpreter, against dense
    attention."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    rng = np.random.default_rng(13)
    q, k, v = (jnp.asarray(x) for x in _qkv(rng, 1, s_q, s_k, 32, 4, 128))
    # as many of the eight heads that share a key-value head a step as fit
    assert flash._heads_a_step(32, 4, 128, 128, 256, 256, 2) == 8
    assert flash._heads_a_step(32, 2, 128, 128, 1024, 1024, 2) == 2
    assert flash._heads_a_step(32, 32, 192, 128, 1024, 1024, 2) == 2
    with jax.default_matmul_precision("highest"):
        got = flash._flash_call(
            q.reshape(1, s_q, -1), k.reshape(1, s_k, -1),
            v.reshape(1, s_k, -1), heads=32, kv_heads=4, group=8,
            batch_rep=1, causal=True, block_q=128,
            block_k=128 if s_k % 128 == 0 else 64, diag_rows=diag_rows,
            interpret=True, causal_block=causal_block)
        want = flash.dense_attention(q, jnp.repeat(k, 8, axis=2),
                                     jnp.repeat(v, 8, axis=2), causal=True,
                                     causal_block=causal_block)
    np.testing.assert_allclose(np.asarray(got).reshape(1, s_q, 32, 128),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_refuses_a_block_its_tiles_do_not_hold():
    import jax.numpy as jnp

    from synapseml_tpu.parallel.flash import auto_blocks_tile, flash_attention

    x = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="causal_block"):
        flash_attention(x, x, x, causal=True, causal_block=3, interpret=True)
    with pytest.raises(ValueError, match="causal_block"):
        flash_attention(x, x, x, causal=True, causal_block=16, block_q=8,
                        block_k=8, interpret=True)
    assert auto_blocks_tile(64, 256, 256, 4)
    assert not auto_blocks_tile(64, 256, 258, 4)


@pytest.mark.parametrize("write", ["constant", "uniform_at_run_time",
                                   "a_start_each_row", "absent"])
def test_tensor_scatter_writes_rows_from_their_start(write):
    rng = np.random.default_rng(12)
    cache = rng.standard_normal((3, 10, 4), dtype=np.float32)
    update = rng.standard_normal((3, 2, 4), dtype=np.float32)
    starts = {"constant": [5, 5, 5], "uniform_at_run_time": [7, 7, 7],
              "a_start_each_row": [0, 8, 3], "absent": [0, 0, 0]}[write]
    want = cache.copy()
    for r, at in enumerate(starts):
        want[r, at:at + 2] = update[r]
    feeds, inits = {"cache": cache, "update": update}, {}
    names = ["cache", "update"]
    if write != "absent":
        (inits if write == "constant" else feeds)["at"] = np.asarray(
            starts, np.int64)
        names.append("at")
    model = _model([ob.node("TensorScatter", names, ["y"], axis=1)], feeds,
                   ["y"], inits)
    got = OnnxFunction(model)(feeds)["y"]
    np.testing.assert_array_equal(np.asarray(got), want)


def _gated_layer(seed=13, shape=(3, 10), h=32, f=48, experts=8, k=2):
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((*shape, h), dtype=np.float32)
    w = {"router_w": rng.standard_normal((h, experts), dtype=np.float32),
         "experts_gate": rng.normal(0, h ** -0.5, (experts, h, f)
                                    ).astype(np.float32),
         "experts_up": rng.normal(0, h ** -0.5, (experts, h, f)
                                  ).astype(np.float32),
         "experts_down": rng.normal(0, f ** -0.5, (experts, f, h)
                                    ).astype(np.float32)}
    scores = u @ w["router_w"]
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    picks = np.argsort(-probs, axis=-1, kind="stable")[..., :k]
    top = np.take_along_axis(probs, picks, -1)
    return u, w, {"x": u, "index": picks.astype(np.int64),
                  "weight": (top / top.sum(-1, keepdims=True)
                             ).astype(np.float32)}


def _gated_share(feeds, w, lo, held, policy="float32"):
    import jax

    weights = {n: w[n][lo:lo + held] for n in ("experts_up", "experts_down",
                                               "experts_gate")}
    model = _model(
        [ob.node("ExpertFFN", list(feeds) + list(weights), ["y"],
                 domain="synapseml_tpu", first_expert=lo,
                 num_experts=w["router_w"].shape[1], activation="swiglu")],
        feeds, ["y"], weights, opset=23, domain="synapseml_tpu")
    with jax.default_matmul_precision("highest"):
        return np.asarray(OnnxFunction(model, dtype_policy=policy)(feeds)["y"])


def _gated_by_hand(feeds, w, lo, held):
    """``sum over a token's picks of a held expert e of weight x (silu(x G_e)
    * (x U_e)) D_e``, pair by pair in float64."""
    x = feeds["x"].astype(np.float64)
    out = np.zeros_like(x)
    for at in np.ndindex(*feeds["index"].shape):
        e = int(feeds["index"][at])
        if lo <= e < lo + held:
            gate = x[at[:-1]] @ w["experts_gate"][e].astype(np.float64)
            hidden = gate / (1 + np.exp(-gate)) * (
                x[at[:-1]] @ w["experts_up"][e].astype(np.float64))
            out[at[:-1]] += feeds["weight"][at] * (
                hidden @ w["experts_down"][e].astype(np.float64))
    return out


@pytest.mark.parametrize("chunk", [None, 8])
@pytest.mark.parametrize("policy,limit", [("float32", 1e-5),
                                          ("bfloat16", 0.01)])
def test_expert_ffn_swiglu_against_a_loop_over_experts(chunk, policy, limit,
                                                       monkeypatch):
    from synapseml_tpu.onnx import ops

    _fresh_programs(monkeypatch)
    if chunk:
        monkeypatch.setattr(ops, "_expert_tiling",
                            lambda n_pairs, num_experts: (chunk, chunk))
    u, w, feeds = _gated_layer(k=3)
    if policy == "bfloat16":
        import jax.numpy as jnp

        def rounded(a):
            return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))

        want = _gated_by_hand(dict(feeds, x=rounded(feeds["x"])),
                              {n: rounded(v) for n, v in w.items()}, 0, 8)
    else:
        want = _gated_by_hand(feeds, w, 0, 8)
    got = _gated_share(feeds, w, 0, 8, policy)
    assert got.dtype == np.float32
    assert _relative(got, want) < limit


@pytest.mark.parametrize("n_pairs,num_experts,want", [
    (65536 * 6, 128, (512, 24576)),   # nemotron3_nano.s4096: 3,072 an expert
    (128 * 256 * 8, 128, (512, 24576)),  # sdar_30b_a3b.gen64's prompt pass
    (128 * 4 * 8, 128, (SMALL_TILE, 4096)),  # a generating pass of it: 32
    (512 * 128, 128, (512, 24576)),   # exactly 512 an expert
    (511 * 128, 128, None),           # one under: a smaller tile
    (60, 8, (SMALL_TILE, -(-60 // SMALL_TILE) * SMALL_TILE)),
])
def test_tile_and_chunk_follow_the_pairs_an_expert_is_expected_to_get(
        n_pairs, num_experts, want):
    from synapseml_tpu.onnx import ops

    tile, chunk = ops._expert_tiling(n_pairs, num_experts)
    if want is None:
        assert tile < 512
    else:
        assert (tile, chunk) == want
    assert chunk == min(48 * tile, -(-n_pairs // tile) * tile)


def test_the_tile_rule_is_monotone_and_a_multiple_of_sixteen():
    from synapseml_tpu.onnx import ops

    tiles = [ops._expert_tiling(expected * 128, 128)[0]
             for expected in range(0, 1100)]
    assert tiles == sorted(tiles) and tiles[0] == SMALL_TILE
    assert set(tiles) == {128, 256, 512}  # bfloat16 packs 16 rows a tile
    assert tiles[512] == tiles[-1] == 512 and tiles[511] < 512
    for n_pairs in (1, 31, 32, 33, 4095, 4097, 24575, 24577, 10 ** 6):
        for experts in (1, 8, 128):
            tile, chunk = ops._expert_tiling(n_pairs, experts)
            assert tile % 16 == 0 and chunk % tile == 0
            assert chunk <= 48 * tile and chunk - tile < n_pairs


def _few_pairs(router, activation, tokens=12, experts=8, k=2):
    """A layer whose experts are expected ``tokens * k / experts`` = 3 pairs
    each, and a router's picks: ``even`` (every expert exactly 3),
    ``crowded`` (every pick on experts 2 and 5) or ``empty_experts`` (picks
    among 0, 1, 3 and 6 alone)."""
    u, w, feeds = _gated_layer(seed=17, shape=(1, tokens), experts=experts,
                               k=k)
    at = np.arange(tokens * k).reshape(1, tokens, k)
    index = {"even": at % experts,
             "crowded": np.broadcast_to(np.array([2, 5]), at.shape),
             "empty_experts": np.array([0, 1, 3, 6])[
                 (at % k) * 2 + (at // k) % 2]}[router]
    if activation == "relu2":
        w = {n: v for n, v in w.items() if n != "experts_gate"}
    return w, dict(feeds, index=index.astype(np.int64))


def _by_hand(feeds, w, activation):
    x = feeds["x"].astype(np.float64)
    out = np.zeros_like(x)
    w = {n: v.astype(np.float64) for n, v in w.items()}
    for at in np.ndindex(*feeds["index"].shape):
        e = int(feeds["index"][at])
        hidden = x[at[:-1]] @ w["experts_up"][e]
        if activation == "relu2":
            hidden = np.maximum(hidden, 0) ** 2
        else:
            gate = x[at[:-1]] @ w["experts_gate"][e]
            hidden = gate / (1 + np.exp(-gate)) * hidden
        out[at[:-1]] += feeds["weight"][at] * (hidden @ w["experts_down"][e])
    return out


@pytest.mark.parametrize("router", ["even", "crowded", "empty_experts"])
@pytest.mark.parametrize("activation", ["relu2", "swiglu"])
@pytest.mark.parametrize("policy,limit", [("float32", 1e-5),
                                          ("bfloat16", 0.01)])
def test_expert_ffn_at_a_few_pairs_an_expert(policy, limit, activation,
                                             router, monkeypatch):
    """Three pairs an expert, where the rule gives the small tile and one
    chunk of the pairs rounded up to it: the loop over experts, whichever
    way the router leans."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.onnx import ops

    _fresh_programs(monkeypatch)
    w, feeds = _few_pairs(router, activation)
    assert ops._expert_tiling(feeds["index"].size, 8) == (SMALL_TILE,
                                                          SMALL_TILE)
    names = ["experts_up", "experts_down"] + ["experts_gate"] * (
        activation == "swiglu")
    model = _model(
        [ob.node("ExpertFFN", list(feeds) + names, ["y"],
                 domain="synapseml_tpu", first_expert=0, num_experts=8,
                 activation=activation)],
        feeds, ["y"], {n: w[n] for n in names}, opset=23,
        domain="synapseml_tpu")
    with jax.default_matmul_precision("highest"):
        got = np.asarray(OnnxFunction(model, dtype_policy=policy)(feeds)["y"])
    if policy == "bfloat16":
        def rounded(a):
            return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))

        want = _by_hand(dict(feeds, x=rounded(feeds["x"])),
                        {n: rounded(v) for n, v in w.items()}, activation)
    else:
        want = _by_hand(feeds, w, activation)
    assert np.abs(want).max() > 0.01 and _relative(got, want) < limit


def test_the_grouped_kernel_at_the_small_tile_in_interpret_mode():
    """megablox ``gmm`` with the tiles ``ops._gmm_tiling`` gives the small
    row tile (the whole of ``k`` in one), run by the Pallas interpreter,
    against ``lax.ragged_dot``: groups that share a tile, empty groups, a
    group over several tiles, rows past the last group."""
    import jax.numpy as jnp
    from jax import lax

    from synapseml_tpu.onnx import ops

    try:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    except ImportError as error:
        pytest.skip(f"megablox is not importable here: {error}")
    rng = np.random.default_rng(5)
    m, k, n = 4 * SMALL_TILE, 256, 128
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((8, k, n)) * k ** -0.5,
                      jnp.bfloat16)
    sizes = jnp.asarray([3, 0, SMALL_TILE + 8, 17, 0, 30, 2, 20], jnp.int32)
    tiling = ops._gmm_tiling(SMALL_TILE, k, n, 2)
    assert tiling == (SMALL_TILE, k, n)
    try:
        got = gmm(lhs, rhs, sizes, preferred_element_type=jnp.bfloat16,
                  tiling=tiling, interpret=True)
    except NotImplementedError as error:
        pytest.skip(f"the Pallas interpreter cannot run gmm here: {error}")
    want = lax.ragged_dot(lhs, rhs, sizes,
                          preferred_element_type=jnp.float32)
    held = int(sizes.sum())
    np.testing.assert_allclose(
        np.asarray(got[:held], np.float32), np.asarray(want[:held]),
        rtol=2 ** -7, atol=2 ** -7)


def test_the_trace_counts_each_expert_node_under_its_tile(monkeypatch):
    """A prompt long enough for 512 pairs an expert and passes of 16: the
    prompt pass's nodes carry the 512-row tile, the two loop bodies' the
    small one, one count a node; the gauge holds the smallest chunk."""
    from synapseml_tpu.onnx import ops

    _fresh_programs(monkeypatch)
    fn = OnnxFunction(zoo.build_model_bytes("SDARMoETiny", seed=8),
                      dtype_policy="bfloat16")
    name, rows, length = fn._fn_name, 16, 128
    prompt_pairs = rows * length * TINY["top_k"]
    pass_pairs = rows * BLOCK * TINY["top_k"]
    assert ops._expert_tiling(prompt_pairs, TINY["experts"])[0] == 512
    small, chunk = ops._expert_tiling(pass_pairs, TINY["experts"])
    assert small == SMALL_TILE
    before = _gauge("smt_onnx_expert_tile_total", fn=name)
    fn({"input_ids": _prompts(rows, seed=5, length=length)})
    after = _gauge("smt_onnx_expert_tile_total", fn=name)
    assert {k: v - before.get(k, 0) for k, v in after.items()} == {
        (name, "512"): TINY["layers"], (name, str(small)): 2 * TINY["layers"]}
    assert _gauge("smt_onnx_expert_chunk_rows", fn=name) == {(name,): chunk}


def test_the_tile_tool_rehearses_on_the_cpu(capsys):
    """``tools/expert_tile_forms.py --rehearse-on-cpu``: every form answers
    as the first one does (the chunk alone applies here), none with a
    time."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "expert_tile_forms", os.path.join(ROOT, "tools",
                                          "expert_tile_forms.py"))
    expert_tile_forms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(expert_tile_forms)
    forms = "512:24576:512,32:load:whole,64:64:512,shipped"
    assert expert_tile_forms.main(["--rehearse-on-cpu", "--loads",
                                   "even,mask_ids", "--forms", forms]) == 0
    head, *lines = [json.loads(line)
                    for line in capsys.readouterr().out.splitlines()]
    assert head["rehearsal"] and head["device"]["platform"] == "cpu"
    assert [(line["load"], line["form"]) for line in lines] == [
        (load, form) for load in ("even", "mask_ids")
        for form in forms.split(",")]
    for line in lines:
        assert line["finite"] and line["same_bits_twice"]
        assert line["max_abs_from_first"] < 0.02 and "ms" not in line
    assert [line["chunk"] for line in lines[:3]] == [24576, 128, 64]
    assert lines[5]["largest_expert"] > lines[1]["largest_expert"]  # crowded


@pytest.mark.parametrize("shares", [2, 4])
def test_swiglu_shares_over_first_expert_add_up_to_the_uncut_layer(
        shares, monkeypatch):
    """The guide's share test: what the chips of a deployment that divides
    the experts each compute adds up to the whole layer, which is also what
    the plain reference gives for it."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import sdar_moe as ref

    _fresh_programs(monkeypatch)
    u, w, feeds = _gated_layer(experts=8, k=3)
    held = 8 // shares
    total = sum(_gated_share(feeds, w, lo, held)
                for lo in range(0, 8, held))
    whole = _gated_share(feeds, w, 0, 8)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(jnp.asarray(u), {n: jnp.asarray(v)
                                            for n, v in w.items()},
                           top_k=3, precision="float32")
    np.testing.assert_allclose(whole, np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("what", ["unknown_activation",
                                  "swiglu_without_its_gate",
                                  "relu2_with_a_gate"])
def test_expert_ffn_refuses_a_form_it_does_not_have(what):
    x = np.zeros((2, 3, 8), np.float32)
    feeds = {"x": x, "index": np.zeros((2, 3, 1), np.int64),
             "weight": np.ones((2, 3, 1), np.float32)}
    weights = {"up": np.zeros((2, 8, 4), np.float32),
               "down": np.zeros((2, 4, 8), np.float32)}
    activation = {"unknown_activation": "geglu",
                  "swiglu_without_its_gate": "swiglu",
                  "relu2_with_a_gate": "relu2"}[what]
    if what != "swiglu_without_its_gate":
        weights["gate"] = np.zeros((2, 8, 4), np.float32)
    model = _model([ob.node("ExpertFFN", list(feeds) + list(weights), ["y"],
                            domain="synapseml_tpu", first_expert=0,
                            num_experts=4, activation=activation)],
                   feeds, ["y"], weights, opset=23, domain="synapseml_tpu")
    error = NotImplementedError if what == "unknown_activation" else ValueError
    with pytest.raises(error, match="ExpertFFN"):
        OnnxFunction(model)(feeds)


def test_builder_refuses_sizes_the_schedule_cannot_have():
    from synapseml_tpu.models.sdar_moe import sdar_moe

    for kwargs in (dict(generate=6), dict(block=4, passes=3),
                   dict(block=6, generate=12, passes=2), dict(mask_id=256)):
        with pytest.raises(ValueError):
            sdar_moe(**{**TINY, **kwargs})
