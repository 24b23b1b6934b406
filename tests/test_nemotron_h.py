"""The hybrid ``nemotron_h`` graph (Mamba-2 chunked scan, routed experts,
causal grouped-query attention) at its tiny preset on the CPU: the executor
against the benchmark's plain reference (sequential scan, materialised
scores, a loop over experts), the new operators against dense forms, and
weights as arguments of the program."""

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

TINY = zoo.NEMOTRON_H_TINY
CONFIG = {
    "hybrid_override_pattern": TINY["pattern"],
    "num_hidden_layers": len(TINY["pattern"]), "norm_eps": 1e-5,
    "mamba_num_heads": TINY["mamba_heads"], "n_groups": TINY["groups"],
    "num_attention_heads": TINY["heads"],
    "num_key_value_heads": TINY["kv_heads"],
    "num_experts_per_tok": TINY["top_k"], "routed_scaling_factor": 2.5,
    "builder_kwargs": {},
}


def _reference(model_bytes, config=CONFIG):
    from benchmark.reference import nemotron_h
    from benchmark.reference.onnx_initializers import read_initializers

    return nemotron_h.Reference(config, read_initializers(model_bytes))


def _ids(rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab"], (rows, length))


def _relative(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


def _compiles(fn_name):
    from synapseml_tpu.observability.metrics import get_registry

    family = get_registry().snapshot()["families"].get(
        "smt_compile_seconds") or {}
    names = family.get("labelnames", [])
    return sum(int(s["count"]) for s in family.get("series", [])
               if dict(zip(names, s["labels"])).get("fn") == fn_name)


# float32 policy: the graph and the reference are the same arithmetic in
# another order (chunked against sequential, grouped against looped), so they
# differ by float32 rounding alone: 1e-5 leaves an order of magnitude over the
# 4e-7 read. bfloat16 policy: every node hands on 8 bits of mantissa and a
# near-tie in a tiny router flips a pick; 0.03 / 0.12 is what the reference
# itself reads with bfloat16 operands (0.009 / 0.046), with room.
@pytest.mark.parametrize("policy,limit", [("float32", {"logits": 1e-5,
                                                       "pooled": 1e-5}),
                                          ("bfloat16", {"logits": 0.03,
                                                        "pooled": 0.12})])
@pytest.mark.parametrize("chunks", [2, 3])
def test_transform_agrees_with_the_sequential_reference(policy, limit, chunks):
    import jax

    from synapseml_tpu.core import Table
    from synapseml_tpu.onnx import ONNXModel

    model_bytes = zoo.build_model_bytes("NemotronHTiny", seed=3)
    ids = _ids(5, chunks * TINY["chunk"], seed=chunks)
    want = _reference(model_bytes).forward_blocks({"input_ids": ids}, 5)
    model = ONNXModel(model_bytes=model_bytes,
                      feed_dict={"input_ids": "input_ids"},
                      fetch_dict={"logits": "logits", "pooled": "pooled"},
                      batch_size=4, dtype_policy=policy)
    with jax.default_matmul_precision("highest"):
        out = model.transform(Table({"input_ids": ids}))
    for name in ("logits", "pooled"):
        got = np.asarray(out[name])
        assert got.shape == want[name].shape and got.dtype == np.float32
        assert _relative(got, want[name]) < limit[name], name


def _one_node_model(op_type, inputs, attrs, initializers=None, domain="",
                    n_outputs=1):
    """``inputs``: name -> array fed; one node, outputs ``y0..``."""
    outs = [f"y{i}" for i in range(n_outputs)]
    graph = ob.make_graph(
        [ob.node(op_type, list(inputs) + list(initializers or {}), outs,
                 domain=domain, **attrs)],
        "one_" + op_type.lower(),
        [ob.value_info(k, v.dtype, list(v.shape)) for k, v in inputs.items()],
        [ob.value_info(o, np.float32, None) for o in outs],
        initializers or {})
    return serialize_model(ob.make_model(
        graph, opset=23, domains={domain: 1} if domain else None))


def _small_chunks(monkeypatch, chunk):
    """``ExpertFFN`` in chunks of ``chunk`` sorted pairs, whatever
    ``ops._expert_tiling`` would give (None: what it gives, one chunk that
    holds them all), and no program of an earlier test to borrow."""
    import weakref

    from synapseml_tpu.onnx import importer, ops

    if chunk:
        monkeypatch.setattr(ops, "_expert_tiling",
                            lambda n_pairs, num_experts: (chunk, chunk))
    # a live model of the same graph would lend its program
    monkeypatch.setattr(importer, "_PROGRAMS", weakref.WeakValueDictionary())


def _routed_layer(seed=11, shape=(3, 10), h=32, f=48, experts=8, k=2):
    """A sparse-expert layer's input and weights, and its router's picks."""
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h as ref

    rng = np.random.default_rng(seed)
    u = rng.standard_normal((*shape, h), dtype=np.float32)
    w = {"router_w": rng.standard_normal((h, experts), dtype=np.float32),
         "router_bias": rng.normal(0, 0.01, experts).astype(np.float32),
         "experts_up": rng.normal(0, h ** -0.5, (experts, h, f)
                                  ).astype(np.float32),
         "experts_down": rng.normal(0, f ** -0.5, (experts, f, h)
                                    ).astype(np.float32),
         "moe_shared_up_w": rng.normal(0, h ** -0.5, (h, 2 * f)
                                       ).astype(np.float32),
         "moe_shared_down_w": rng.normal(0, f ** -0.5, (2 * f, h)
                                         ).astype(np.float32)}
    picks, weights = ref.route(jnp.asarray(u),
                               {n: jnp.asarray(v) for n, v in w.items()},
                               k, 2.5, "float32")
    return u, w, {"x": u, "index": np.asarray(picks, np.int64),
                  "weight": np.asarray(weights)}


def _share_function(feeds, w, lo, held, policy="float32"):
    """The one-node program of experts ``lo .. lo + held - 1``."""
    model = _one_node_model(
        "ExpertFFN", feeds,
        dict(first_expert=lo, num_experts=w["experts_up"].shape[0],
             activation="relu2"),
        {"up": w["experts_up"][lo:lo + held],
         "down": w["experts_down"][lo:lo + held]}, domain="synapseml_tpu")
    return OnnxFunction(model, dtype_policy=policy)


def _share(feeds, w, lo, held, policy="float32"):
    import jax

    with jax.default_matmul_precision("highest"):
        return np.asarray(_share_function(feeds, w, lo, held, policy)(
            feeds)["y0"])


def _share_by_hand(feeds, w, lo, held):
    """``sum over a token's picks of a held expert e of weight x relu(x
    U_e)² D_e``, pair by pair in float64."""
    x = feeds["x"].astype(np.float64)
    out = np.zeros_like(x)
    for at in np.ndindex(*feeds["index"].shape):
        e = int(feeds["index"][at])
        if lo <= e < lo + held:
            hidden = np.maximum(x[at[:-1]] @ w["experts_up"][e].astype(
                np.float64), 0) ** 2
            out[at[:-1]] += feeds["weight"][at] * (
                hidden @ w["experts_down"][e].astype(np.float64))
    return out


def _reference_share(u, w, lo, held, shared, k=2):
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h as ref

    wj = {name: jnp.asarray(v) for name, v in w.items()}
    part = dict(wj, experts_up=wj["experts_up"][lo:lo + held],
                experts_down=wj["experts_down"][lo:lo + held])
    return np.asarray(ref.expert_mixer(
        jnp.asarray(u), part, top_k=k, scaling=2.5, first_expert=lo,
        precision="float32", shared=shared))


@pytest.mark.parametrize("first,chunk", [(None, None), (None, 8), (0, None),
                                         (2, 8), (4, None), (6, 8)])
def test_expert_shares_add_up_to_the_uncut_layer(first, chunk, monkeypatch):
    """Four ``ExpertFFN`` shares of two experts each, summed, plus the shared
    expert counted once, are the reference's uncut layer; each share alone is
    the reference's share. With ``chunk`` the 60 sorted pairs take several
    chunks of 8, as 393,216 take several of 24,576 on the chip."""
    _small_chunks(monkeypatch, chunk)
    u, w, feeds = _routed_layer()
    experts = 8
    if first is not None:
        got = _share(feeds, w, first, 2)
        want = _reference_share(u, w, first, 2, shared=False)
        assert np.abs(want).max() > 0.1  # the share is not empty
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        return
    routed = sum(_share(feeds, w, lo, 2) for lo in (0, 2, 4, 6))
    uncut = _reference_share(u, w, 0, experts, shared=False)
    np.testing.assert_allclose(routed, uncut, rtol=2e-5, atol=2e-5)
    with_shared = _reference_share(u, w, 0, experts, shared=True)
    np.testing.assert_allclose(routed + (with_shared - uncut), with_shared,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("chunk", [None, 8])
@pytest.mark.parametrize("load", ["nobody_picked", "all_held"])
def test_expert_share_at_the_ends_of_its_load(load, chunk, monkeypatch):
    """A share no token picked gives exact zeros (its loop runs no chunk);
    a share that holds every expert of its router (every pair is held, the
    loop runs every chunk) is the reference's uncut routed layer."""
    _small_chunks(monkeypatch, chunk)
    u, w, feeds = _routed_layer()
    if load == "all_held":
        np.testing.assert_allclose(
            _share(feeds, w, 0, 8), _reference_share(u, w, 0, 8, False),
            rtol=2e-5, atol=2e-5)
        return
    feeds = dict(feeds, index=feeds["index"] % 6)  # experts 0-5 alone
    got = _share(feeds, w, 6, 2)
    assert got.shape == u.shape and not got.any()


def _boundary_case():
    """Twelve tokens, top-3 of eight experts, experts 2-5 held, chunks of 8:
    every token picks expert 2, so its group of 12 crosses the first chunk
    boundary; token 0's picks (2, 3, 4) are all held and their rows lie in
    chunks 0, 1 and 2; tokens 8-11 pick nothing else that is held."""
    u, w, feeds = _routed_layer(seed=7, shape=(1, 12), k=3)
    index = np.array([[2, 3, 4]] + [[2, 3, 7]] * 3 + [[2, 5, 0]] * 4
                     + [[2, 1, 6]] * 4, np.int64)
    return w, dict(feeds, index=index[None])


def test_expert_chunks_may_cut_a_group_and_a_tokens_picks(monkeypatch):
    _small_chunks(monkeypatch, 8)
    w, feeds = _boundary_case()
    got = _share(feeds, w, 2, 4)
    np.testing.assert_allclose(got, _share_by_hand(feeds, w, 2, 4),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(got[0, 8:]).min() > 1e-3  # one held pick is still an answer


def test_expert_rows_past_a_chunks_last_group_never_reach_the_sum(monkeypatch):
    """The chip's grouped kernel leaves NaN in the rows past the last group
    (PERF.md section 5); ``lax.ragged_dot`` on the CPU leaves zeros. With NaN
    put there the answer is finite and the same, bit for bit: those rows are
    selected away, never multiplied."""
    import jax.numpy as jnp

    from synapseml_tpu.onnx import ops

    w, feeds = _boundary_case()
    _small_chunks(monkeypatch, 8)
    want = _share(feeds, w, 2, 4)
    plain = ops._grouped_product

    def nan_past_the_groups(lhs, rhs, sizes, rows):
        out = plain(lhs, rhs, sizes, rows)
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(ops, "_grouped_product", nan_past_the_groups)
    _small_chunks(monkeypatch, 8)
    got = _share(feeds, w, 2, 4)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


def test_expert_answers_repeat_bit_for_bit(monkeypatch):
    """The order of the float32 sum over a token's picks is the program's
    own, and the same from call to call and from program to program
    (``correct``'s ``repeat_mismatch`` has limit 0)."""
    _small_chunks(monkeypatch, 8)
    u, w, feeds = _routed_layer(k=4)
    first = _share_function(feeds, w, 0, 8)
    a, b = (np.asarray(first(feeds)["y0"]) for _ in range(2))
    _small_chunks(monkeypatch, 8)  # a program of its own
    second = _share_function(feeds, w, 0, 8)
    assert second._jit is not first._jit
    c = np.asarray(second(feeds)["y0"])
    assert a.tobytes() == b.tobytes() == c.tobytes() and a.any()


def _lopsided_case(held_counts):
    """41 tokens, top-6 of sixteen experts, experts 4-7 held: a router that
    spreads its picks evenly fills 1.5 of a token's picks here, so the first
    3 held picks are gathered for every token; ``held_counts[t]`` of token
    ``t``'s picks are held, at places of their own among its six."""
    u, w, feeds = _routed_layer(seed=13, shape=(1, 41), experts=16, k=6)
    rng = np.random.default_rng(5)
    index = np.empty((1, 41, 6), np.int64)
    for t, n_held in enumerate(held_counts):
        picks = np.concatenate([
            rng.permutation([4, 5, 6, 7])[:n_held],
            rng.permutation([0, 1, 2, 3, 8, 9, 10, 11, 12, 13, 14, 15]
                            )[:6 - n_held]])
        index[0, t] = rng.permutation(picks)
    return w, dict(feeds, index=index)


@pytest.mark.parametrize("rest", ["none", "few", "many"])
def test_expert_held_picks_beyond_those_gathered_for_every_token(
        rest, monkeypatch):
    """A token's held picks past the first three are added row by row, in as
    many goes of 2 rows as the load asks for: none, two (3 such picks) or 21
    (a fourth pick of all 41 tokens)."""
    from synapseml_tpu.onnx import ops

    _small_chunks(monkeypatch, 8)
    monkeypatch.setattr(ops, "_REST_ROWS", 2)
    counts = np.random.default_rng(3).integers(0, 4, 41)
    if rest == "few":
        counts[[2, 17, 40]] = 4
    elif rest == "many":
        counts[:] = 4
    w, feeds = _lopsided_case(counts)
    got = _share(feeds, w, 4, 4)
    np.testing.assert_allclose(got, _share_by_hand(feeds, w, 4, 4),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(got[0, counts > 0]).min() > 1e-4
    assert not got[0, counts == 0].any()


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
def test_expert_program_gathers_a_tokens_first_held_picks_only(
        policy, monkeypatch):
    """41 tokens x top-6 = 246 pairs, h = 32, a quarter of the experts held:
    the lowered program gathers 3 rows a token, not 6, into one float32
    ``[41, 32]`` sum; nothing in it has 246 rows of 32 (the sorted pairs'
    buffer has its padding's 248)."""
    import re

    import jax

    _small_chunks(monkeypatch, 8)
    w, feeds = _lopsided_case(np.full(41, 2))
    fn = _share_function(feeds, w, 4, 4, policy)
    text = jax.jit(fn._run_positional).lower(
        *(feeds[name] for name in fn.input_names)).as_text(dialect="hlo")
    shapes = set(re.findall(r"\b[a-z]+[0-9]+\[[0-9,]*\]", text))
    rows = "bf16" if policy == "bfloat16" else "f32"
    assert {"f32[41,32]", rows + "[3,41,32]", rows + "[248,32]"} <= shapes
    assert not [s for s in shapes
                if re.search(r"\[(6,41|246),32\]", s)], sorted(shapes)


def test_expert_sum_over_picks_is_float32_rounded_once(monkeypatch):
    """Under the bfloat16 policy rows leave the grouped product in bfloat16;
    the weights, the sum over a token's picks and its one rounding are
    float32's. Against the pair-by-pair float64 sum of the same bfloat16
    operands: 0.00436 read here, 0.00436 by the gather of every pair before
    it (bfloat16's 2^-9 through two products and one rounding of the
    sum)."""
    import jax.numpy as jnp

    _small_chunks(monkeypatch, 8)
    u, w, feeds = _routed_layer(k=4)
    bf16 = lambda a: np.asarray(  # noqa: E731
        jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
    rounded = {name: bf16(v) for name, v in w.items()}
    want = _share_by_hand(dict(feeds, x=bf16(feeds["x"])), rounded, 0, 8)
    got = _share(feeds, w, 0, 8, policy="bfloat16")
    assert got.dtype == np.float32
    assert _relative(got, want) < 0.006


def test_expert_ffn_refuses_what_it_does_not_do():
    x = np.zeros((2, 3, 8), np.float32)
    feeds = {"x": x, "index": np.zeros((2, 3, 1), np.int64),
             "weight": np.ones((2, 3, 1), np.float32)}
    weights = {"up": np.zeros((2, 8, 4), np.float32),
               "down": np.zeros((2, 4, 8), np.float32)}
    for attrs, error in (
            (dict(first_expert=0, num_experts=4, activation="silu"),
             NotImplementedError),
            (dict(first_expert=3, num_experts=4, activation="relu2"),
             ValueError)):
        model = _one_node_model("ExpertFFN", feeds, attrs, weights,
                                domain="synapseml_tpu")
        with pytest.raises(error):
            OnnxFunction(model)(feeds)


@pytest.mark.parametrize("layout", ["batch_seq_hidden", "batch_heads_seq"])
@pytest.mark.parametrize("causal", [0, 1])
def test_attention_is_dense_grouped_query_attention(layout, causal):
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel.flash import dense_attention

    rng = np.random.default_rng(5)
    b, s, heads, kv, d = 2, 12, 4, 2, 8
    q = rng.standard_normal((b, s, heads, d), dtype=np.float32)
    k = rng.standard_normal((b, s, kv, d), dtype=np.float32)
    v = rng.standard_normal((b, s, kv, d), dtype=np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(dense_attention(
            jnp.asarray(q), jnp.repeat(jnp.asarray(k), heads // kv, 2),
            jnp.repeat(jnp.asarray(v), heads // kv, 2), causal=bool(causal)))
    if layout == "batch_seq_hidden":
        feeds = {n: x.reshape(b, s, -1) for n, x in zip("qkv", (q, k, v))}
        attrs = dict(q_num_heads=heads, kv_num_heads=kv, is_causal=causal)
        want = want.reshape(b, s, -1)
    else:
        feeds = {n: x.transpose(0, 2, 1, 3) for n, x in zip("qkv", (q, k, v))}
        attrs = dict(is_causal=causal)
        want = want.transpose(0, 2, 1, 3)
    with jax.default_matmul_precision("highest"):
        got = OnnxFunction(_one_node_model("Attention", feeds, attrs))(feeds)
    np.testing.assert_allclose(np.asarray(got["y0"]), want, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("what", ["softcap", "qk_matmul_output_mode",
                                  "softmax_precision", "float_attn_mask",
                                  "past_key", "second_output"])
def test_attention_refuses_what_it_does_not_lower(what):
    """A boolean ``attn_mask`` is lowered (``tests/test_sdar_moe.py`` holds
    it to a form written out by hand); an additive one is not, nor a cache
    handed in as ``past_key``."""
    x = np.zeros((1, 4, 8), np.float32)
    feeds = {"q": x, "k": x, "v": x}
    attrs = dict(q_num_heads=2, kv_num_heads=2)
    n_outputs = 1
    if what == "float_attn_mask":
        feeds["mask"] = np.zeros((4, 4), np.float32)
    elif what == "past_key":
        feeds["mask"] = np.ones((4, 4), bool)
        feeds["past_key"] = np.zeros((1, 2, 3, 4), np.float32)
    elif what == "second_output":
        n_outputs = 2
    else:
        attrs[what] = 1.5 if what == "softcap" else 1
    model = _one_node_model("Attention", feeds, attrs, n_outputs=n_outputs)
    with pytest.raises(NotImplementedError, match="Attention"):
        OnnxFunction(model)(feeds)


def test_rms_normalization_reduces_in_float32():
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    x = rng.standard_normal((3, 5, 16), dtype=np.float32)
    scale = rng.standard_normal(16, dtype=np.float32)
    model = _one_node_model("RMSNormalization", {"x": x},
                            dict(axis=-1, epsilon=1e-5), {"scale": scale})
    want = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-5) * scale
    got = OnnxFunction(model)({"x": x})["y0"]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    narrow = OnnxFunction(model, dtype_policy="bfloat16")({"x": x})["y0"]
    rounded = jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)
    assert _relative(narrow, np.asarray(
        rounded / np.sqrt(np.mean(rounded * rounded, -1, keepdims=True)
                          + 1e-5) * scale)) < 8e-3


def test_the_trace_says_how_attention_was_lowered_and_what_experts_hold(
        monkeypatch):
    from synapseml_tpu.observability.metrics import get_registry

    _small_chunks(monkeypatch, None)  # a program of its own: it is traced
    fn = OnnxFunction(zoo.build_model_bytes("NemotronHTiny", seed=5),
                      dtype_policy="bfloat16")

    def series(name):
        family = get_registry().snapshot()["families"].get(name) or {}
        return {tuple(s["labels"]): s["value"]
                for s in family.get("series", [])
                if s["labels"][0] == fn._jit.name}

    held_first = (fn._jit.name, "held_first")
    before = series("smt_onnx_expert_combine_total").get(held_first, 0)
    fn({"input_ids": _ids(3, 16)})
    # the CPU has no Pallas kernel: the dense form, and the counter says so
    lowered = series("smt_onnx_attention_lowering_total")
    assert lowered.get((fn._jit.name, "dense"), 0) >= 1
    assert (fn._jit.name, "flash") not in lowered
    # so no node says where the kernel read its operands (on the chip: 1
    # ``flash``, ``in_place``)
    assert series("smt_onnx_attention_flash_form_total") == {}
    # two E blocks: 3 x 16 tokens x top-2 pairs each, 8 experts held each
    assert series("smt_onnx_expert_pairs")[(fn._jit.name,)] == 2 * 3 * 16 * 2
    assert series("smt_onnx_experts_held")[(fn._jit.name,)] == 2 * 8
    placed = series("smt_onnx_weight_argument_bytes")[(fn._jit.name,)]
    assert placed == sum(w.nbytes for w in fn._weights) > 0
    # once a traced program, one count an ExpertFFN node, in the one form
    combine = series("smt_onnx_expert_combine_total")
    assert combine == {held_first: before + 2}
    fn({"input_ids": _ids(3, 16, seed=1)})
    assert series("smt_onnx_expert_combine_total") == combine


@pytest.mark.parametrize("builder", ["BERTTiny", "NemotronHTiny"])
def test_two_weight_seeds_share_one_compiled_program(builder):
    """Weights are arguments: a second checkpoint of the same graph runs the
    first one's executable (``smt_compile_seconds`` gains ONE sample), and
    its answers are its own."""
    # every seeded tensor at or over the 16 elements that make an argument
    kwargs = {"experts": 16, "experts_held": 16, "mamba_heads": 16,
              "mamba_head_dim": 4} if builder == "NemotronHTiny" else {}
    ids = _ids(2, 16)
    first = OnnxFunction(zoo.build_model_bytes(builder, seed=21, **kwargs))
    name = first._jit.name
    before = _compiles(name)
    a = np.asarray(first({"input_ids": ids})["logits"])
    assert _compiles(name) == before + 1
    second = OnnxFunction(zoo.build_model_bytes(builder, seed=22, **kwargs))
    b = np.asarray(second({"input_ids": ids})["logits"])
    assert _compiles(name) == before + 1
    assert second._jit is first._jit and not np.allclose(a, b)
    # the program outlives the instance that compiled it
    del first
    np.testing.assert_array_equal(
        b, np.asarray(second({"input_ids": ids})["logits"]))
    # another policy is another program
    third = OnnxFunction(zoo.build_model_bytes(builder, seed=22, **kwargs),
                         dtype_policy="bfloat16")
    assert third._jit is not second._jit


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
def test_weights_as_arguments_answer_as_weights_as_literals(policy):
    """``_run_positional`` with the feeds alone closes over the placed
    weights, which then compile in as literals (the path before weights
    were arguments). bfloat16: the same bits. float32: XLA folds and lays
    out a literal operand ahead of time, so a product sums in another order:
    float32 rounding, 5e-7 read, 1e-5 allowed."""
    import jax

    fn = OnnxFunction(zoo.build_model_bytes("BERTTiny", seed=4),
                      dtype_policy=policy)
    ids = np.random.default_rng(1).integers(0, 1000, (3, 16))
    literal = jax.jit(fn._run_positional)(ids)
    for got, want in zip(fn({"input_ids": ids}).values(), literal):
        if policy == "bfloat16":
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-6)
    assert len(fn._weight_names) > 30
    assert all(name in fn.constants for name in fn._weight_names)


def test_small_and_integer_initializers_stay_constants():
    fn = OnnxFunction(zoo.build_model_bytes("NemotronHTiny", seed=1))
    weights = set(fn._weight_names)
    for name, const in fn.constants.items():
        is_weight = const.dtype.kind == "f" and const.size >= 16
        assert (name in weights) == is_weight, name
        assert isinstance(const, np.ndarray) != is_weight, name
