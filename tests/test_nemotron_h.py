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


@pytest.mark.parametrize("first,chunk", [(None, None), (None, 8), (0, None),
                                         (2, 8), (4, None), (6, 8)])
def test_expert_shares_add_up_to_the_uncut_layer(first, chunk, monkeypatch):
    """Four ``ExpertFFN`` shares of two experts each, summed, plus the shared
    expert counted once, are the reference's uncut layer; each share alone is
    the reference's share. With ``chunk`` the 60 sorted pairs take several
    chunks of 8, as 393,216 take several of 24,576 on the chip."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h as ref
    from synapseml_tpu.onnx import ops

    if chunk:
        import weakref

        from synapseml_tpu.onnx import importer

        monkeypatch.setattr(ops, "_PAIR_CHUNK", chunk)
        # a live model of the same graph would lend its one-chunk program
        monkeypatch.setattr(importer, "_PROGRAMS",
                            weakref.WeakValueDictionary())

    rng = np.random.default_rng(11)
    h, f, experts, k = 32, 48, 8, 2
    u = rng.standard_normal((3, 10, h), dtype=np.float32)
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
    wj = {name: jnp.asarray(v) for name, v in w.items()}
    picks, weights = ref.route(jnp.asarray(u), wj, k, 2.5, "float32")
    feeds = {"x": u, "index": np.asarray(picks, np.int64),
             "weight": np.asarray(weights)}

    def share(lo, held):
        model = _one_node_model(
            "ExpertFFN", feeds, dict(first_expert=lo, num_experts=experts,
                                     activation="relu2"),
            {"up": w["experts_up"][lo:lo + held],
             "down": w["experts_down"][lo:lo + held]},
            domain="synapseml_tpu")
        with jax.default_matmul_precision("highest"):
            return np.asarray(OnnxFunction(model)(feeds)["y0"])

    def reference(lo, held, shared):
        part = dict(wj, experts_up=wj["experts_up"][lo:lo + held],
                    experts_down=wj["experts_down"][lo:lo + held])
        return np.asarray(ref.expert_mixer(
            jnp.asarray(u), part, top_k=k, scaling=2.5, first_expert=lo,
            precision="float32", shared=shared))

    if first is not None:
        got, want = share(first, 2), reference(first, 2, shared=False)
        assert np.abs(want).max() > 0.1  # the share is not empty
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        return
    routed = sum(share(lo, 2) for lo in (0, 2, 4, 6))
    np.testing.assert_allclose(routed, reference(0, experts, shared=False),
                               rtol=2e-5, atol=2e-5)
    shared_once = reference(0, experts, True) - reference(0, experts, False)
    np.testing.assert_allclose(routed + shared_once,
                               reference(0, experts, shared=True),
                               rtol=2e-5, atol=2e-5)


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
                                  "softmax_precision", "attn_mask",
                                  "second_output"])
def test_attention_refuses_what_it_does_not_lower(what):
    x = np.zeros((1, 4, 8), np.float32)
    feeds = {"q": x, "k": x, "v": x}
    attrs = dict(q_num_heads=2, kv_num_heads=2)
    n_outputs = 1
    if what == "attn_mask":
        feeds["mask"] = np.zeros((4, 4), np.float32)
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


def test_the_trace_says_how_attention_was_lowered_and_what_experts_hold():
    from synapseml_tpu.observability.metrics import get_registry

    fn = OnnxFunction(zoo.build_model_bytes("NemotronHTiny", seed=5),
                      dtype_policy="bfloat16")
    fn({"input_ids": _ids(3, 16)})
    families = get_registry().snapshot()["families"]

    def series(name):
        family = families[name]
        return {tuple(s["labels"]): s["value"] for s in family["series"]
                if s["labels"][0] == fn._jit.name}

    # the CPU has no Pallas kernel: the dense form, and the counter says so
    lowered = series("smt_onnx_attention_lowering_total")
    assert lowered.get((fn._jit.name, "dense"), 0) >= 1
    assert (fn._jit.name, "flash") not in lowered
    # two E blocks: 3 x 16 tokens x top-2 pairs each, 8 experts held each
    assert series("smt_onnx_expert_pairs")[(fn._jit.name,)] == 2 * 3 * 16 * 2
    assert series("smt_onnx_experts_held")[(fn._jit.name,)] == 2 * 8
    placed = series("smt_onnx_weight_argument_bytes")[(fn._jit.name,)]
    assert placed == sum(w.nbytes for w in fn._weights) > 0


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
