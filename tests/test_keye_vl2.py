"""The ``keye_vl2`` graph (the Qwen3-MoE block whose attention reads only
the keys a learned indexer chose, generating in an ONNX ``Loop`` that carries
keys, values and index keys a layer) at its tiny preset on the CPU:
``transform`` against the benchmark's plain reference, teacher-forced;
decoding through the caches against one full forward; M-RoPE at text
positions against 1-D rotary; ``synapseml_tpu::SparseAttention``'s forms and
each of its Pallas kernels (through the interpreter) against the plain form,
ties to the lower position, and a query that sees fewer keys than ``topk``."""

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

TINY = zoo.KEYE_VL2_TINY
GENERATE, PROMPT = TINY["generate"], 16
with open(os.path.join(ROOT, "benchmark", "configs",
                       "keye_vl2_tiny.json")) as _f:
    CONFIG = json.load(_f)
COLUMNS = ("tokens", "chosen_logprob", "pooled", "prompt_pooled")


def _reference(model_bytes):
    from benchmark.reference import keye_vl2
    from benchmark.reference.onnx_initializers import read_initializers

    return keye_vl2.Reference(CONFIG, read_initializers(model_bytes))


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


# float32 policy: the program (prompt pass, then the loop over the three
# caches, the set by top_k on the CPU) and the reference (one full forward,
# every score written out) are the same arithmetic in another order: 1e-6 is
# read, 1e-5 allowed, every id the reference's own argmax. bfloat16 policy: a
# router of 8 experts, top-2, flips a pick on a rounding, and the index
# scores' rounding moves a key in or out of a set (pooled 0.04-0.10 over
# seeds, a log-probability within 0.01 of its size): about twice the largest
# reading is allowed. prompt_pooled, over the first topk positions where no
# set is chosen, reads 0.004-0.026 over the rehearsal cell's 12 seeds.
@pytest.mark.parametrize("policy,limit", [
    ("float32", {"logprob": 1e-5, "gap": 1e-6, "pooled": 1e-5,
                 "prompt_pooled": 1e-5}),
    ("bfloat16", {"logprob": 0.06, "gap": 0.5, "pooled": 0.2,
                  "prompt_pooled": 0.05})])
def test_transform_agrees_with_the_reference_teacher_forced(policy, limit,
                                                            monkeypatch):
    import jax

    _fresh_programs(monkeypatch)
    model_bytes = zoo.build_model_bytes("KeyeVL2Tiny", seed=3)
    prompts = _prompts(4, seed=1)
    got = _transform(model_bytes, prompts, policy)
    assert got["tokens"].shape == (4, GENERATE)
    assert got["pooled"].shape == (4, TINY["hidden"])
    with jax.default_matmul_precision("highest"):
        replayed = _reference(model_bytes).replay(prompts, got["tokens"],
                                                  block_rows=2)
    numbers = _numbers(got, replayed)
    assert numbers["chosen_logprob.rel_rms"] < limit["logprob"]
    assert numbers["argmax_gap"] <= limit["gap"]
    assert _relative(got["pooled"], replayed["pooled"]) < limit["pooled"]
    assert got["prompt_pooled"].shape == (4, TINY["hidden"])
    assert _relative(got["prompt_pooled"], replayed["prompt_pooled"]) \
        < limit["prompt_pooled"]
    if policy == "float32":  # the reference's own greedy choice, every pass
        np.testing.assert_array_equal(got["tokens"],
                                      replayed["logits"].argmax(-1))


@pytest.mark.parametrize("prompt", [5, PROMPT])
def test_decoding_through_the_caches_agrees_with_one_full_forward(
        prompt, monkeypatch):
    """Prompt pass + loop = one forward, inside the program: the ids a call
    decodes one at a time against its three caches are the ids the PROMPT
    pass (every position at once) gives for the same prefix. A prompt of 5
    positions is under ``topk`` (8) and grows past it while decoding."""
    _fresh_programs(monkeypatch)
    prompts = _prompts(3, seed=2, length=prompt)
    whole = _transform(zoo.build_model_bytes("KeyeVL2Tiny", seed=4), prompts,
                       "float32")
    # the same weights generating 2 ids: id 0 is the prompt pass's
    short = zoo.build_model_bytes("KeyeVL2Tiny", seed=4, generate=2)
    for t in (1, GENERATE - 1):
        prefix = np.concatenate([prompts, whole["tokens"][:, :t]], axis=1)
        again = _transform(short, prefix, "float32")
        np.testing.assert_array_equal(again["tokens"][:, 0],
                                      whole["tokens"][:, t])
        np.testing.assert_allclose(again["chosen_logprob"][:, 0],
                                   whole["chosen_logprob"][:, t],
                                   rtol=1e-5, atol=1e-5)


def test_mrope_at_text_positions_is_one_dimensional_rotary():
    """M-RoPE's three sections (``[16, 24, 24]`` of the 64 frequencies of a
    128-wide head) turned by one position each are 1-D rotary at theta 1e7;
    the index heads' 64-wide tables are every other column of the heads'
    (what the graph slices); an image's distinct height and width
    positions are not."""
    from benchmark.reference.keye_vl2 import mrope_angles

    theta, length = 1e7, 300
    text = np.broadcast_to(np.arange(length), (3, length))
    one_d = np.arange(length)[:, None] * theta ** (
        -np.arange(0, 128, 2, dtype=np.float64) / 128)[None, :]
    np.testing.assert_array_equal(
        mrope_angles(text, theta, 128, [16, 24, 24]), one_d)
    np.testing.assert_array_equal(
        mrope_angles(text, theta, 64, [32, 0, 0]), one_d[:, ::2])
    image = np.stack([np.zeros(length), np.arange(length) // 16,
                      np.arange(length) % 16])
    assert not np.allclose(mrope_angles(image, theta, 128, [16, 24, 24]),
                           one_d)
    with pytest.raises(ValueError, match="mrope_section"):
        mrope_angles(text, theta, 128, [16, 24, 16])


def test_the_graph_is_one_sparse_attention_a_layer_and_three_caches():
    from synapseml_tpu.models.keye_vl2 import keye_vl2
    from synapseml_tpu.onnx.importer import OPS

    model = keye_vl2(**{**TINY, "seed": 0})
    (loop,) = [n for n in model.graph.node if n.op_type == "Loop"]
    body = loop.attrs()["body"]
    custom_ops = {"SparseAttention", "ExpertFFN"}
    for nodes, pass_ in ((model.graph.node, "p"), (body.node, "d")):
        sparse = [n for n in nodes if n.op_type == "SparseAttention"]
        assert [n.name for n in sparse] == [f"{pass_}_l{i}_att"
                                            for i in range(TINY["layers"])]
        assert all(n.domain == "synapseml_tpu" for n in sparse)
        # the prompt pass's keys are its own; a decode pass gives the
        # position of its query
        assert {len(n.input) for n in sparse} == {6 if pass_ == "p" else 7}
        assert {n.op_type for n in nodes} - custom_ops <= set(OPS)
        assert "Attention" not in {n.op_type for n in nodes}
    # 4 values of the generation, then a layer's keys, values, index keys
    assert len(loop.input) == 2 + 4 + 3 * TINY["layers"]
    with pytest.raises(ValueError, match="generate"):
        keye_vl2(**{**TINY, "generate": 1})


def test_the_trace_counts_the_lowerings_and_the_pairs(monkeypatch):
    _fresh_programs(monkeypatch)
    fn = OnnxFunction(zoo.build_model_bytes("KeyeVL2Tiny", seed=7),
                      dtype_policy="bfloat16")
    name = fn._fn_name
    families = ("smt_onnx_sparse_attention_lowering_total",
                "smt_onnx_sparse_attention_keys_total")
    before = {f: _gauge(f, fn=name) for f in families}
    rows, layers, topk = 2, TINY["layers"], TINY["topk"]
    fn({"input_ids": _prompts(rows, seed=4)})

    def since(family):  # counters add up over a process's traces
        return {k: v - before[family].get(k, 0)
                for k, v in _gauge(family, fn=name).items()}

    # on the CPU both passes take the plain form (on the chip the kernels)
    assert since("smt_onnx_sparse_attention_lowering_total") == {
        (name, "plain"): 2 * layers}
    length = PROMPT + GENERATE
    prompt_chosen = topk * (topk + 1) // 2 + (PROMPT - topk) * topk
    assert since("smt_onnx_sparse_attention_keys_total") == {
        (name, "scored"): layers * rows * (PROMPT * (PROMPT + 1) // 2
                                           + length),
        (name, "selected"): layers * rows * (prompt_chosen + topk)}
    # three caches a layer: keys and values [N, L, kv, d], index keys
    cache = layers * rows * length * (2 * TINY["kv_heads"] * TINY["head_dim"]
                                      + TINY["index_dim"]) * 2
    outputs = rows * (1 + GENERATE) * 4 + rows * (GENERATE
                                                  + TINY["hidden"]) * 4
    assert _gauge("smt_onnx_loop_state_bytes", fn=name) == {
        (name,): cache + outputs}


# ---- the operator, its forms and its kernels

def _case(rng, n, s, length, *, heads=4, kv_heads=2, d=128, index_heads=4,
          index_dim=64):
    """Operands whose index scores are exact in float32 whatever the order
    of the sums (small multiples of 1/4 and 1/2), so every form chooses the
    same keys; they tie often."""
    import jax.numpy as jnp

    def coarse(shape, scale):
        return jnp.asarray(rng.integers(-2, 3, shape) * scale, jnp.bfloat16)

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    return dict(q=normal((n, s, heads * d)),
                k=normal((n, length, kv_heads, d)),
                v=normal((n, length, kv_heads, d)),
                q_index=coarse((n, s, index_heads * index_dim), 0.5),
                k_index=coarse((n, length, index_dim), 0.5),
                weights=jnp.asarray(rng.integers(-2, 3, (n, s, index_heads))
                                    * 0.25, jnp.float32))


def _chosen_by_kernel(case, start, topk):
    """The set ``index_select``'s ``(theta, p)`` stand for, ``[N, s, L]``."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import sparse_attention as sa

    n, s, _ = case["q"].shape
    index_heads = case["weights"].shape[-1]
    q_index = jnp.transpose(case["q_index"].reshape(n, s, index_heads, -1),
                            (0, 2, 1, 3))
    sel = sa.index_select(q_index, case["weights"], case["k_index"],
                          jnp.asarray(start, jnp.int32), topk=topk,
                          interpret=True)
    index = sa._index_scores(q_index.transpose(0, 2, 1, 3),
                             case["weights"], case["k_index"])
    keys = np.asarray(sa.order_keys(index))
    at = np.arange(keys.shape[-1])[None, None, :]
    position = np.asarray(start)[:, None, None] + np.arange(s)[None, :, None]
    theta, last = np.asarray(sel)[..., :1], np.asarray(sel)[..., 1:2]
    return (at <= position) & ((keys > theta)
                               | ((keys == theta) & (at <= last)))


def _chosen_by_top_k(case, start, topk):
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel import sparse_attention as sa

    n, s, _ = case["q"].shape
    index = sa._index_scores(case["q_index"].reshape(n, s, 4, -1),
                             case["weights"], case["k_index"])
    length = index.shape[-1]
    position = np.asarray(start)[:, None] + np.arange(s)[None, :]
    seen = np.arange(length)[None, None, :] <= position[..., None]
    _, picks = jax.lax.top_k(jnp.where(seen, index, -jnp.inf),
                             min(topk, length))
    chosen = np.zeros(index.shape, bool)
    for (b, i), row in np.ndenumerate(position):
        chosen[b, i, np.asarray(picks)[b, i, :min(row + 1, topk)]] = True
    return chosen


def test_the_indexers_kernel_chooses_the_top_k_set_ties_to_the_lower():
    """Bisection on the keys' bits against ``lax.top_k``: the same sets, at
    every query of a prompt (the first ``topk`` seeing every key; two blocks
    of index keys) and at single queries against a longer cache (three)."""
    rng = np.random.default_rng(11)
    case = _case(rng, 2, 2560, 2560)
    start = np.zeros(2, np.int64)
    want = _chosen_by_top_k(case, start, 64)
    np.testing.assert_array_equal(_chosen_by_kernel(case, start, 64), want)
    assert (want.sum(-1) == np.minimum(np.arange(2560) + 1, 64)).all()
    one = _case(rng, 3, 1, 2688)
    start = np.array([2600, 10, 2687])
    np.testing.assert_array_equal(_chosen_by_kernel(one, start, 64),
                                  _chosen_by_top_k(one, start, 64))


def test_ties_go_to_the_lower_position():
    """Index weights of 0 make every score 0: each query keeps the first
    ``topk`` positions it sees, in every form."""
    import jax.numpy as jnp

    rng = np.random.default_rng(12)
    case = _case(rng, 1, 256, 256)
    case["weights"] = jnp.zeros_like(case["weights"])
    chosen = _chosen_by_kernel(case, np.zeros(1, np.int64), 16)
    want = np.arange(256)[None, :] <= np.minimum(np.arange(256), 15)[:, None]
    np.testing.assert_array_equal(chosen[0], want)
    np.testing.assert_array_equal(
        _chosen_by_top_k(case, np.zeros(1, np.int64), 16)[0], want)


@pytest.mark.parametrize("form", ["plain", "prompt"])
def test_a_query_that_sees_fewer_keys_than_topk_sees_them_all(form):
    """Under ``topk`` visible keys the set is every key: causal attention."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash
    from synapseml_tpu.parallel import sparse_attention as sa

    case = _case(np.random.default_rng(13), 1, 128, 128)
    kw = dict(heads=4, kv_heads=2, topk=128)
    got = sa.plain_form(**case, **kw) if form == "plain" else \
        sa.prompt_form(**case, **kw, interpret=True)
    q = case["q"].reshape(1, 128, 4, 128)
    k, v = (jnp.repeat(case[x], 2, axis=2) for x in ("k", "v"))
    want = flash.dense_attention(q, k, v, causal=True).reshape(1, 128, -1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_the_prompt_kernels_agree_with_the_plain_form():
    """``index_select`` then ``blockwise_form`` (online softmax over five
    blocks of keys, the set recomputed a tile at a time) against scores of
    the gathered rows."""
    from synapseml_tpu.parallel import sparse_attention as sa

    case = _case(np.random.default_rng(14), 1, 2560, 2560)
    kw = dict(heads=4, kv_heads=2, topk=64)
    want = np.asarray(sa.plain_form(**case, **kw), np.float32)
    got = np.asarray(sa.prompt_form(**case, **kw, interpret=True),
                     np.float32)
    # the same sets; what differs is the order of the softmax's sums and
    # where the probabilities are rounded to bfloat16 (a unit in the last
    # place of an output near 2)
    np.testing.assert_allclose(got, want, atol=2e-2)
    assert np.abs(got - want).mean() < 1e-3


@pytest.mark.parametrize("zero_weights", [False, True])
def test_the_indexers_kernel_marks_the_tiles_a_block_chose_from(
        zero_weights):
    """``index_select``'s ``occupied`` against the sets its ``(theta, p)``
    stand for: tile ``c`` of a block of 32 queries is 1 where a query of the
    block keeps a key of positions ``512 c ..``; with every score 0 the
    queries keep positions ``0 .. 63`` and the later tiles are empty."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import sparse_attention as sa

    case = _case(np.random.default_rng(16), 1, 2560, 2560)
    if zero_weights:
        case["weights"] = jnp.zeros_like(case["weights"])
    start = np.zeros(1, np.int64)
    q_index = jnp.transpose(case["q_index"].reshape(1, 2560, 4, -1),
                            (0, 2, 1, 3))
    _, occupied = sa.index_select(q_index, case["weights"], case["k_index"],
                                  jnp.asarray(start, jnp.int32), topk=64,
                                  tile=512, interpret=True)
    chosen = _chosen_by_kernel(case, start, 64)
    want = chosen.reshape(1, 80, 32, 5, 512).any(axis=(2, 4))
    got = np.asarray(occupied)
    assert got.shape == (1, 80, 1, 128) and not got[..., 5:].any()
    np.testing.assert_array_equal(got[:, :, 0, :5], want.astype(np.int32))
    assert want[:, :, 1:].any() != zero_weights
    words = np.asarray(sa.tile_words(occupied, 4)).view(np.uint32)
    bits = (words.reshape(20, 4)[:, :1] >> np.arange(5)) & 1
    np.testing.assert_array_equal(bits, want.reshape(20, 4, 5).any(1))


def test_the_blockwise_kernel_skips_the_tiles_no_query_chose_from():
    """Every score 0 keeps positions ``0 .. 63``: the blockwise kernel reads
    the first tile of keys alone and agrees with the plain form, and with
    itself made to read every tile; where keys were chosen from later tiles,
    marking them empty changes the result, so the marks are obeyed."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import sparse_attention as sa

    rng = np.random.default_rng(17)
    kw = dict(heads=4, kv_heads=2, topk=64)
    every, first = (jnp.full((20 * 4,), v, jnp.int32) for v in (-1, 1))
    for zero_weights in (True, False):
        case = _case(rng, 1, 2560, 2560)
        if zero_weights:
            case["weights"] = jnp.zeros_like(case["weights"])
        q_index = jnp.transpose(case["q_index"].reshape(1, 2560, 4, -1),
                                (0, 2, 1, 3))
        sel = sa.index_select(q_index, case["weights"], case["k_index"],
                              jnp.zeros((1,), jnp.int32), topk=64,
                              interpret=True)

        def attend(words):
            k, v = (case[x].reshape(1, 2560, -1) for x in ("k", "v"))
            return np.asarray(sa.blockwise_form(
                case["q"], k, v, q_index, case["weights"], case["k_index"],
                sel, words, heads=4, kv_heads=2, interpret=True), np.float32)

        if zero_weights:
            want = np.asarray(sa.plain_form(**case, **kw), np.float32)
            got = np.asarray(sa.prompt_form(**case, **kw, interpret=True),
                             np.float32)
            np.testing.assert_allclose(got, want, atol=2e-2)
            assert np.abs(got - want).mean() < 1e-3
            np.testing.assert_array_equal(attend(first), attend(every))
        else:
            assert np.abs(attend(first) - attend(every)).max() > 0.1


@pytest.mark.parametrize("start", [[2600, 10, 2687], [63, 64, 65]])
def test_the_decode_kernels_agree_with_the_plain_form(start):
    """``index_select`` with its keys, ``compact`` and ``gather_form`` (one
    DMA a chosen row) against the plain form, at queries before, at and
    past ``topk`` positions."""
    from jax.experimental.pallas import tpu as pltpu

    from synapseml_tpu.parallel import sparse_attention as sa

    case = _case(np.random.default_rng(15), 3, 1, 2688)
    kw = dict(heads=4, kv_heads=2, topk=64)
    start = np.asarray(start)
    want = sa.plain_form(**case, start=start, **kw)
    got = sa.decode_form(**case, start=start, **kw,
                         interpret=pltpu.InterpretParams())
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_compact_lists_the_chosen_positions_in_order():
    from synapseml_tpu.parallel import sparse_attention as sa

    rng = np.random.default_rng(16)
    keys = rng.integers(-5, 5, (2, 384)).astype(np.int32)
    position = np.array([383, 40])
    # theta 2 with ties at 2 kept through position 200
    sel = np.zeros((2, 128), np.int32)
    sel[:, 0], sel[:, 1] = 2, 200
    index, count = sa.compact(keys, sel, position, topk=256)
    for b in range(2):
        at = np.arange(384)
        chosen = (at <= position[b]) & ((keys[b] > 2) | (
            (keys[b] == 2) & (at <= 200)))
        want = np.flatnonzero(chosen)
        assert int(count[b]) == len(want)
        np.testing.assert_array_equal(np.asarray(index[b, :len(want)]), want)
        assert not np.asarray(index[b, len(want):]).any()


def _node_model(n, s, length, start):
    from synapseml_tpu.onnx import builder as ob

    names = ["q", "k", "v", "q_index", "k_index", "weights"] \
        + ["start"] * start
    case = _case(np.random.default_rng(17), n, s, length)
    feeds = {k: np.asarray(v, np.float32) for k, v in case.items()}
    if start:
        feeds["start"] = np.full((n,), length - 1 - 40, np.int64)
    return _model(
        [ob.node("SparseAttention", names, ["out"], name="sparse",
                 domain="synapseml_tpu", q_num_heads=4, kv_num_heads=2,
                 topk=32)],
        feeds, ["out"], domain="synapseml_tpu"), feeds


@pytest.mark.parametrize("s,length,start,kernels,form", [
    (256, 256, False, True, "prompt_kernel"),
    (256, 256, False, False, "plain"),
    (1, 384, True, True, "decode_kernel"),
    (1, 384, True, False, "plain"),
    (1, 200, True, True, "plain"),   # a cache off the blocks of 128
    (4, 384, True, True, "plain")])  # several queries against a cache
def test_the_operator_chooses_its_lowering_from_shapes_and_backend(
        s, length, start, kernels, form, monkeypatch):
    """Through ``OnnxFunction``: the lowering, the note that counts it, and
    the plain form's answer whichever ran (the kernels through the
    interpreter)."""
    from jax.experimental.pallas import tpu as pltpu

    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import sparse_attention as sa

    _fresh_programs(monkeypatch)
    monkeypatch.setattr(ops, "_kernels_on", lambda: kernels)
    monkeypatch.setattr(sa, "prompt_form", functools.partial(
        sa.prompt_form, interpret=True))
    monkeypatch.setattr(sa, "decode_form", functools.partial(
        sa.decode_form, interpret=pltpu.InterpretParams()))
    model, feeds = _node_model(2, s, length, start)
    fn = OnnxFunction(model, dtype_policy="bfloat16")
    family = "smt_onnx_sparse_attention_lowering_total"
    before = _gauge(family, fn=fn._fn_name)
    got = np.asarray(fn(feeds)["out"], np.float32)
    gained = {k: v - before.get(k, 0)
              for k, v in _gauge(family, fn=fn._fn_name).items()}
    assert {k: v for k, v in gained.items() if v} == {(fn._fn_name, form): 1}
    case = {k: np.asarray(v) for k, v in feeds.items() if k != "start"}
    import jax.numpy as jnp
    case = {k: jnp.asarray(v, jnp.float32 if k == "weights"
                           else jnp.bfloat16) for k, v in case.items()}
    want = sa.plain_form(**case, start=feeds.get("start"), heads=4,
                         kv_heads=2, topk=32)
    np.testing.assert_allclose(got, np.asarray(want, np.float32), atol=2e-2)


def test_the_operator_refuses_operands_that_do_not_fit():
    model, feeds = _node_model(2, 16, 16, False)
    feeds["k_index"] = feeds["k_index"][:, :8]
    with pytest.raises(ValueError, match="SparseAttention"):
        OnnxFunction(model)(feeds)
