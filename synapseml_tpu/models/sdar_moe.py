"""``sdar_moe``: the block-diffusion sparse-expert decoder of SDAR-30B-A3B-Chat,
generation included, as ONE ONNX graph for the zoo.

The layer is the Qwen3-MoE block: ``x <- x + Attn(RMSNorm(x))``, ``x <- x +
MoE(RMSNorm(x))``; grouped-query attention with a per-head RMSNorm on queries
and keys, rotate-half rotary positions, and the mask of a model that denoises
a BLOCK of ``block`` positions at a time: a position sees every earlier block
and all of its own, both directions. ``MoE``: a softmax router over all
experts, the ``top_k`` largest renormalised, gated experts ``(silu(x G_e) *
(x U_e)) D_e``, no shared expert.

The graph generates. Input ``input_ids [N, S]`` (``S`` a multiple of
``block``); outputs, a row: ``tokens [generate]``, ``unmask_pass [generate]``
(the pass at which a position was fixed), ``chosen_logprob [generate]`` (the
log-softmax of the chosen id at that pass) and ``pooled [hidden]`` (the mean,
over the generated positions, of the final norm's output in their commit
pass).

- The prompt's pass (nodes ``p_l#_...``): all ``S`` positions through the
  layers under the block mask, written out as a constant boolean ``attn_mask``
  (the executor reads it as the kernel's block-granular causal mask). Each
  layer's keys and values, after norm and rotary, are padded to ``S +
  generate`` positions: the cache, allocated once.
- ``Loop`` ``blocks`` (``generate / block`` trips) carries the cache and the
  outputs. A block's ids start as ``mask_id``. Its body holds ``Loop``
  ``passes`` (``passes`` trips; nodes ``b_l#_...``): embed the block's ids,
  run the layers with the block's queries against the cache (``TensorScatter``
  puts the block's own keys and values at its positions first; ``attn_mask``
  hides the positions after the block, so what an earlier pass or nothing
  wrote there is never seen), head, float32 logits with the mask id's at -inf;
  of the still-masked positions the ``block / passes`` whose candidate
  (argmax) has the highest softmax probability take it (``TopK``: ties to the
  lower position). Then the commit pass (nodes ``c_l#_...``): the block's
  final ids through the layers once more, keys and values into the cache, no
  head. A block costs ``passes + 1`` passes.

Everything is a standard operator of opset 24 (``Loop``, ``Attention``,
``RotaryEmbedding``, ``RMSNormalization``, ``TensorScatter``, ``TopK``, ...)
except ``synapseml_tpu::ExpertFFN``. Bodies read the outer graph's
initializers: weights are named ``l#_...`` once and used by all three passes.
The rotary angles are computed in the graph from integer positions and a
float32 ``Constant`` of inverse frequencies, so no executor's policy narrows
them.

Weights are seeded draws as ``nemotron_h``'s: matrices ``N(0, 1/fan_in)``
rounded to BFLOAT16, the embedding ``N(0, 1)``, norm weights 1.
"""

from __future__ import annotations

from types import SimpleNamespace as _Sizes
from typing import List

import numpy as np

from ..onnx.builder import constant_node, make_graph, make_model, node, \
    value_info
from ..onnx.wire import DataType, ModelProto
from .decoder import EXPERT_DOMAIN, Weights, head_norm_rotary, infos, \
    of_shape, softmax_experts

__all__ = ["sdar_moe", "MASK_ID"]

MASK_ID = 151669  # the tokeniser's <|MASK|>
_FLOAT, _INT32, _BOOL = DataType.FLOAT, DataType.INT32, DataType.BOOL


def _layer(add, z: _Sizes, c: str, i: int, x: str, positions: str,
           attend) -> str:
    """Layer ``i`` in context ``c`` (``p``, ``b`` or ``c``) over ``x [N, s,
    hidden]`` at ``positions [N, s]``; ``attend(prefix, q, k, v)`` adds the
    context's attention (and what it does with the keys and values) and
    names its output."""
    p, w = f"{c}_l{i}", f"l{i}"
    wide = {"q": z.heads, "k": z.kv_heads}
    add(node("RMSNormalization", [x, w + "_norm_in_w"], [p + "_u"],
             name=p + "_norm_in", axis=-1, epsilon=z.eps))
    for proj in "qkv":
        add(node("MatMul", [p + "_u", f"{w}_{proj}_w"], [f"{p}_{proj}"],
                 name=f"{p}_att_{proj}"))
    for proj, heads in wide.items():
        # the norm over a head's size, then rotary at the absolute position
        head_norm_rotary(add, p, w, proj, heads, positions, z.eps)
    ctx = attend(p, p + "_q_r", p + "_k_r", p + "_v")
    add(node("MatMul", [ctx, w + "_o_w"], [p + "_att"], name=p + "_att_o"))
    add(node("Add", [x, p + "_att"], [p + "_mid"], name=p + "_res_att"))

    add(node("RMSNormalization", [p + "_mid", w + "_norm_post_w"],
             [p + "_v2"], name=p + "_norm_post", axis=-1, epsilon=z.eps))
    moe = softmax_experts(add, p, w, p + "_v2", z.experts)
    add(node("Add", [p + "_mid", moe], [p + "_out"], name=p + "_res_moe"))
    return p + "_out"


def _layers(add, z: _Sizes, c: str, ids: str, positions: str, attend) -> str:
    """Embedding, every layer and the final norm; names ``[N, s, hidden]``."""
    add(node("Gather", ["tok_emb", ids], [c + "_tok"], name=c + "_tok",
             axis=0))
    x = c + "_tok"
    for i in range(z.layers):
        x = _layer(add, z, c, i, x, positions,
                   lambda p, q, k, v, i=i: attend(i, p, q, k, v))
    add(node("RMSNormalization", [x, "norm_f_w"], [c + "_final"],
             name=c + "_norm_f", axis=-1, epsilon=z.eps))
    return c + "_final"


def _cached_pass(z: _Sizes, c: str, ids: str, caches: List[str]):
    """The layers over one block's ids against the cache: nodes, the final
    norm's output and the caches' new names. ``block_positions [N, block]``,
    ``block_start [N]`` and ``block_visible [1, L]`` are the enclosing
    body's."""
    nodes: List = []
    new = list(caches)

    def attend(i, p, q, k, v):
        for slot, (what, fresh) in enumerate((("k", k), ("v", v))):
            at = 2 * i + slot
            new[at] = f"{p}_cache_{what}"
            nodes.append(node("TensorScatter", [caches[at], fresh,
                                                "block_start"], [new[at]],
                              name=f"{p}_cache_{what}", axis=1))
        nodes.append(node("Attention", [q, new[2 * i], new[2 * i + 1],
                                        "block_visible"], [p + "_ctx"],
                          name=p + "_att", q_num_heads=z.heads,
                          kv_num_heads=z.kv_heads))
        return p + "_ctx"

    final = _layers(nodes.append, z, c, ids, "block_positions", attend)
    return nodes, final, new


def sdar_moe(layers: int = 6, hidden: int = 2048, vocab: int = 151936,
             heads: int = 32, kv_heads: int = 4, head_dim: int = 128,
             experts: int = 128, top_k: int = 8, expert_width: int = 768,
             rope_theta: float = 1e6, eps: float = 1e-6, generate: int = 64,
             block: int = 4, passes: int = 2, mask_id: int = MASK_ID,
             seed: int = 0) -> ModelProto:
    """One pipeline stage's layers of ``sdar_moe`` with both ends' embedding
    and head (module docstring); the defaults are the published widths of
    SDAR-30B-A3B-Chat with six of its 48 layers, generating ``generate``
    positions in blocks of ``block`` with ``passes`` denoising passes each."""
    if generate % block or block % passes or block & (block - 1):
        raise ValueError(
            f"generate {generate} must be a multiple of block {block}, block "
            f"a power of two and a multiple of passes {passes}")
    if not 0 <= mask_id < vocab:
        raise ValueError(f"mask_id {mask_id} is not among {vocab} ids")
    z = _Sizes(layers=layers, heads=heads, kv_heads=kv_heads, eps=eps,
               experts=experts)
    w = Weights(seed)
    w.normal("tok_emb", (vocab, hidden), 1.0)
    for i in range(layers):
        p, std = f"l{i}", hidden ** -0.5
        w.full(p + "_norm_in_w", (hidden,), 1.0)
        w.normal(p + "_q_w", (hidden, heads * head_dim), std)
        w.normal(p + "_k_w", (hidden, kv_heads * head_dim), std)
        w.normal(p + "_v_w", (hidden, kv_heads * head_dim), std)
        w.full(p + "_q_norm_w", (head_dim,), 1.0)
        w.full(p + "_k_norm_w", (head_dim,), 1.0)
        w.normal(p + "_o_w", (heads * head_dim, hidden),
                 (heads * head_dim) ** -0.5)
        w.full(p + "_norm_post_w", (hidden,), 1.0)
        w.normal(p + "_router_w", (hidden, experts), std)
        w.normal(p + "_experts_gate", (experts, hidden, expert_width), std)
        w.normal(p + "_experts_up", (experts, hidden, expert_width), std)
        w.normal(p + "_experts_down", (experts, expert_width, hidden),
                 expert_width ** -0.5)
    w.full("norm_f_w", (hidden,), 1.0)
    w.normal("lm_head", (hidden, vocab), hidden ** -0.5)
    for name, values in (
            ("zero", 0), ("one", 1), ("index0", [0]), ("index1", [1]),
            ("axes_0", [0]), ("axes_1", [1]), ("axes_last", [-1]),
            ("block", block), ("block_1d", [block]), ("generate_1d",
                                                      [generate]),
            ("hidden_1d", [hidden]), ("top_k", [top_k]),
            ("fixed_a_pass", [block // passes]), ("passes", passes),
            ("blocks", generate // block), ("mask_id", mask_id),
            ("vocab", vocab), ("zero_one", [0, 1]),
            ("heads_q_shape", [0, 0, heads, head_dim]),
            ("heads_k_shape", [0, 0, kv_heads, head_dim]),
            ("flat_shape", [0, 0, -1])):
        w.ints(name, values)

    nodes: List = []
    add = nodes.append
    # sizes from the feed's shape (constants of a trace): N, S, L = S + G
    add(node("Shape", ["input_ids"], ["ids_shape"], name="ids_shape"))
    add(node("Gather", ["ids_shape", "index0"], ["n_1d"], name="n_1d"))
    add(node("Gather", ["ids_shape", "index1"], ["s_1d"], name="s_1d"))
    add(node("Squeeze", ["s_1d", "axes_0"], ["prompt_len"],
             name="prompt_len"))
    add(node("Add", ["prompt_len", "generate_1d"], ["total_1d"],
             name="total_1d"))
    add(node("Squeeze", ["total_1d", "axes_0"], ["total_len"],
             name="total_len"))
    add(node("Range", ["zero", "total_len", "one"], ["all_positions"],
             name="all_positions"))
    add(node("Range", ["zero", "prompt_len", "one"], ["prompt_range"],
             name="prompt_range"))
    add(node("Range", ["zero", "block", "one"], ["block_range"],
             name="block_range"))
    add(node("Concat", ["n_1d", "block_1d"], ["n_block_shape"],
             name="n_block_shape", axis=0))
    add(node("Concat", ["n_1d", "generate_1d"], ["n_generate_shape"],
             name="n_generate_shape", axis=0))
    add(node("Concat", ["n_1d", "hidden_1d"], ["n_hidden_shape"],
             name="n_hidden_shape", axis=0))
    # rotary angles in float32: position x theta^(-2i/d), from integers and
    # one float32 constant that no policy narrows
    inv_freq = (float(rope_theta) ** (-np.arange(head_dim // 2,
                                                 dtype=np.float64)
                                      * 2.0 / head_dim)).astype(np.float32)
    add(constant_node("rope_inv_freq", inv_freq))
    add(constant_node("neg_inf", np.asarray(-np.inf, np.float32)))
    add(node("Cast", ["all_positions"], ["positions_f"], name="positions_f",
             to=_FLOAT))
    add(node("Unsqueeze", ["positions_f", "axes_1"], ["positions_col"],
             name="positions_col"))
    add(node("Mul", ["positions_col", "rope_inv_freq"], ["rope_angles"],
             name="rope_angles"))
    add(node("Cos", ["rope_angles"], ["rope_cos"], name="rope_cos"))
    add(node("Sin", ["rope_angles"], ["rope_sin"], name="rope_sin"))
    # the prompt's mask: key j visible to query i where j's block starts at
    # or before i
    add(node("Mod", ["prompt_range", "block"], ["in_block"], name="in_block"))
    add(node("Sub", ["prompt_range", "in_block"], ["block_of"],
             name="block_of"))
    add(node("Unsqueeze", ["block_of", "axes_0"], ["key_block"],
             name="key_block"))
    add(node("Unsqueeze", ["prompt_range", "axes_1"], ["query_position"],
             name="query_position"))
    add(node("LessOrEqual", ["key_block", "query_position"], ["prompt_mask"],
             name="prompt_mask"))
    add(node("Unsqueeze", ["prompt_range", "axes_0"], ["prompt_row"],
             name="prompt_row"))
    add(node("Expand", ["prompt_row", "ids_shape"], ["prompt_positions"],
             name="prompt_positions"))
    w.ints("cache_pad", [0, 0, 0, 0, generate, 0])
    caches: List[str] = []

    def prompt_attend(i, p, q, k, v):
        for what, fresh in (("k", k), ("v", v)):
            add(node("Pad", [fresh, "cache_pad"], [f"{p}_cache_{what}"],
                     name=f"{p}_cache_{what}", mode="constant"))
            caches.append(f"{p}_cache_{what}")
        add(node("Attention", [q, k, v, "prompt_mask"], [p + "_ctx"],
                 name=p + "_att", q_num_heads=heads, kv_num_heads=kv_heads))
        return p + "_ctx"

    _layers(add, z, "p", "input_ids", "prompt_positions", prompt_attend)

    # ---- the body of Loop "passes": one denoising pass of a block
    state = ["ids", "masked", "fixed_at", "logprob"]
    n_caches = 2 * layers
    b_in = ["pass", "pass_cond"] + ["b_" + s for s in state] \
        + [f"b_cache{j}" for j in range(n_caches)]
    b_nodes, b_final, b_caches = _cached_pass(z, "b", "b_ids", b_in[6:])
    b_add = b_nodes.append
    b_add(node("MatMul", [b_final, "lm_head"], ["b_logits"], name="b_head"))
    b_add(node("Cast", ["b_logits"], ["b_logits_f"], name="b_logits_f",
               to=_FLOAT))
    b_add(node("Where", ["is_mask_id", "neg_inf", "b_logits_f"],
               ["b_logits_m"], name="b_logits_m"))
    b_add(node("ArgMax", ["b_logits_m"], ["b_candidate"], name="b_candidate",
               axis=-1, keepdims=0))
    b_add(node("ReduceMax", ["b_logits_m", "axes_last"], ["b_top"],
               name="b_top", keepdims=0))
    b_add(node("ReduceLogSumExp", ["b_logits_m", "axes_last"], ["b_lse"],
               name="b_lse", keepdims=0))
    # the candidate's log-probability; its order is its confidence's
    b_add(node("Sub", ["b_top", "b_lse"], ["b_confidence"],
               name="b_confidence"))
    b_add(node("Where", ["b_masked", "b_confidence", "neg_inf"], ["b_score"],
               name="b_score"))
    b_add(node("TopK", ["b_score", "fixed_a_pass"], ["b_best", "b_best_at"],
               name="b_most_confident", axis=-1))
    b_add(node("OneHot", ["b_best_at", "block", "zero_one"], ["b_hot"],
               name="b_hot", axis=-1))
    b_add(node("ReduceMax", ["b_hot", "axes_1"], ["b_fix_n"], name="b_fix_n",
               keepdims=0))
    b_add(node("Cast", ["b_fix_n"], ["b_fix"], name="b_fix", to=_BOOL))
    b_add(node("Where", ["b_fix", "b_candidate", "b_ids"], ["b_ids_out"],
               name="b_ids_out"))
    b_add(node("Not", ["b_fix"], ["b_not_fix"], name="b_not_fix"))
    b_add(node("And", ["b_masked", "b_not_fix"], ["b_masked_out"],
               name="b_masked_out"))
    b_add(node("Cast", ["pass"], ["b_pass"], name="b_pass", to=_INT32))
    b_add(node("Where", ["b_fix", "b_pass", "b_fixed_at"], ["b_fixed_at_out"],
               name="b_fixed_at_out"))
    b_add(node("Where", ["b_fix", "b_confidence", "b_logprob"],
               ["b_logprob_out"], name="b_logprob_out"))
    b_add(node("Identity", ["pass_cond"], ["pass_cond_out"],
               name="pass_cond_out"))
    b_out = ["pass_cond_out", "b_ids_out", "b_masked_out", "b_fixed_at_out",
             "b_logprob_out"] + b_caches
    kinds = [np.int64, np.bool_, np.int32, np.float32]  # of ``state``
    passes_body = make_graph(
        b_nodes, "denoising_pass",
        infos(b_in, [np.int64, np.bool_] + kinds, n_caches),
        infos(b_out, [np.bool_] + kinds, n_caches))

    # ---- the body of Loop "blocks": a block's passes, then its commit pass
    o_state = ["tokens", "unmask_pass", "chosen_logprob", "pooled_sum"]
    o_in = ["block_index", "block_cond"] + ["o_" + s for s in o_state] \
        + [f"o_cache{j}" for j in range(n_caches)]
    o_nodes: List = []
    o_add = o_nodes.append
    o_add(node("Mul", ["block_index", "block"], ["generated_before"],
               name="generated_before"))
    o_add(node("Add", ["generated_before", "prompt_len"], ["block_first"],
               name="block_first"))
    o_add(node("Expand", ["block_first", "n_1d"], ["block_start"],
               name="block_start"))
    o_add(node("Expand", ["generated_before", "n_1d"], ["block_slot"],
               name="block_slot"))
    o_add(node("Add", ["block_range", "block_first"], ["block_positions_1d"],
               name="block_positions_1d"))
    o_add(node("Unsqueeze", ["block_positions_1d", "axes_0"],
               ["block_positions_row"], name="block_positions_row"))
    o_add(node("Expand", ["block_positions_row", "n_block_shape"],
               ["block_positions"], name="block_positions"))
    # a pass sees the cache as far as its own block's end
    o_add(node("Add", ["block_first", "block"], ["block_end"],
               name="block_end"))
    o_add(node("Less", ["all_positions", "block_end"], ["visible_1d"],
               name="visible_1d"))
    o_add(node("Unsqueeze", ["visible_1d", "axes_0"], ["block_visible"],
               name="block_visible"))
    o_add(of_shape("ids_start", "n_block_shape", np.int64(mask_id)))
    o_add(of_shape("masked_start", "n_block_shape", np.bool_(True)))
    o_add(of_shape("fixed_at_start", "n_block_shape", np.int32(0)))
    o_add(of_shape("logprob_start", "n_block_shape", np.float32(0)))
    passes_out = ["block_ids", "block_masked", "block_fixed_at",
                  "block_logprob"] + [f"d_cache{j}" for j in range(n_caches)]
    o_add(node("Loop", ["passes", "", "ids_start", "masked_start",
                        "fixed_at_start", "logprob_start"] + o_in[6:],
               passes_out, name="passes", body=passes_body))
    c_nodes, c_final, c_caches = _cached_pass(z, "c", "block_ids",
                                              passes_out[4:])
    o_nodes += c_nodes
    o_add(node("Cast", [c_final], ["c_final_f"], name="c_final_f",
               to=_FLOAT))
    o_add(node("ReduceSum", ["c_final_f", "axes_1"], ["c_final_sum"],
               name="c_final_sum", keepdims=0))
    o_add(node("Add", ["o_pooled_sum", "c_final_sum"], ["pooled_sum_out"],
               name="pooled_sum_out"))
    for what, new in (("tokens", "block_ids"),
                      ("unmask_pass", "block_fixed_at"),
                      ("chosen_logprob", "block_logprob")):
        o_add(node("TensorScatter", ["o_" + what, new, "block_slot"],
                   [what + "_out"], name=what + "_out", axis=1))
    o_add(node("Identity", ["block_cond"], ["block_cond_out"],
               name="block_cond_out"))
    o_out = ["block_cond_out", "tokens_out", "unmask_pass_out",
             "chosen_logprob_out", "pooled_sum_out"] + c_caches
    kinds = [np.int64, np.int32, np.float32, np.float32]  # of ``o_state``
    blocks_body = make_graph(
        o_nodes, "block", infos(o_in, [np.int64, np.bool_] + kinds, n_caches),
        infos(o_out, [np.bool_] + kinds, n_caches))

    # ---- the loop over blocks and the outputs
    add(node("Range", ["zero", "vocab", "one"], ["vocab_range"],
             name="vocab_range"))
    add(node("Equal", ["vocab_range", "mask_id"], ["is_mask_id"],
             name="is_mask_id"))
    add(of_shape("tokens_start", "n_generate_shape", np.int64(0)))
    add(of_shape("unmask_pass_start", "n_generate_shape", np.int32(0)))
    add(of_shape("chosen_logprob_start", "n_generate_shape", np.float32(0)))
    add(of_shape("pooled_start", "n_hidden_shape", np.float32(0)))
    add(node("Loop", ["blocks", "", "tokens_start", "unmask_pass_start",
                      "chosen_logprob_start", "pooled_start"] + caches,
             ["tokens", "unmask_pass", "chosen_logprob", "pooled_total"]
             + [f"final_cache{j}" for j in range(n_caches)],
             name="blocks", body=blocks_body))
    add(node("Cast", ["generate_1d"], ["generate_f"], name="generate_f",
             to=_FLOAT))
    add(node("Div", ["pooled_total", "generate_f"], ["pooled"],
             name="pooled"))

    w.fill_all()
    graph = make_graph(
        nodes, f"sdar_moe_{layers}l_h{hidden}_g{generate}",
        [value_info("input_ids", np.int64, ["N", "S"])],
        [value_info("tokens", np.int64, ["N", generate]),
         value_info("unmask_pass", np.int32, ["N", generate]),
         value_info("chosen_logprob", np.float32, ["N", generate]),
         value_info("pooled", np.float32, ["N", hidden])],
        w.store)
    return make_model(graph, opset=24, domains={EXPERT_DOMAIN: 1})
