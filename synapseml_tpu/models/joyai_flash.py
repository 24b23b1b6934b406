"""``joyai_llm_flash``: the latent-attention sparse-expert decoder of
JoyAI-LLM-Flash (the DeepSeek-V3 block), generation included, as ONE ONNX
graph for the zoo.

Block ``i``: ``x <- x + Attn(RMSNorm(x))``, ``x <- x + FFN_i(RMSNorm(x))``.
``Attn`` is multi-head LATENT attention: a position's keys and values are
expansions of one latent row ``l = [c_kv (kv_lora_rank); k_rope (rope)]``,
``c_kv`` a normed down-projection and ``k_rope`` one rotated key part that
every head shares; queries come through a normed down-projection of their
own and are ``[q_nope (nope); q_rope (rope)]`` a head; the values are ``v``
wide, which is not the queries' ``nope + rope``. The rotation turns
NEIGHBOURING pairs of the ``rope`` numbers (``rope_interleave``). ``FFN_0``
(the leading dense layer) is a gated feed-forward; every later ``FFN_i`` a
sigmoid router over all experts (``decoder.router``: the same
equations), gated routed experts of which THIS chip holds ``experts_held``
from ``first_expert`` on, and a gated shared expert that every chip computes.

The graph generates, greedily, and runs the attention in BOTH of its forms.
Input ``input_ids [N, S]``; outputs a row: ``tokens [generate]``,
``chosen_logprob [generate]`` (the log-softmax of the chosen id) and ``pooled
[hidden]`` (the mean, over the ``generate`` positions the ids were chosen
from, of the final norm's output).

- The prompt pass (nodes ``p_l#_...``) is the EXPANDED, published form: ``k =
  [c_kv W_uk; k_rope]`` and ``v = c_kv W_uv`` for every head, one causal
  ``Attention`` whose queries and keys are ``nope + rope`` wide and whose
  values are ``v`` wide. Each layer's latent rows go into a cache ``[N, S +
  generate, kv_lora_rank + rope]``, padded once to full length. The final
  norm and the head at the last position give id 0.
- ``Loop`` ``decode`` (``generate - 1`` trips; nodes ``d_l#_...``) embeds
  the last id at the next position, ONE token a row, and runs the layers in
  the ABSORBED form: ``q~ = [q_nope W_uk^T; q_rope]`` a head (``att_absorb``),
  ``TensorScatter`` writes the position's latent row, ``Attention`` scores
  every head's ``q~`` against the cache as ONE key-value head whose values
  are its first ``kv_lora_rank`` columns, under a mask computed from the trip
  counter; the context comes back through ``W_uv`` (``att_uv``). ``W_uk``
  and ``W_uv`` are two initializers that both forms read. Keys and values are
  never expanded to every head here.
- ``mtp=1`` adds the multi-token-prediction module: for position ``t`` with
  the next id known, ``[RMSNorm(Emb(x_{t+1})); RMSNorm(h_t)] W_eh`` (``h_t``
  the final norm's output) through one more expert block with a cache of
  its own, a norm and the main head: a draft of the id after next. Outputs
  ``draft_tokens`` and ``draft_logprob`` ``[generate]``: slot ``j`` is
  drafted beside id ``j`` and guesses id ``j + 1``.

Everything is a standard operator of opset 24 except
``synapseml_tpu::ExpertFFN``. Bodies read the outer graph's initializers:
weights are named ``l#_...`` once and used by both passes. Weights are seeded
draws as ``nemotron_h``'s and ``sdar_moe``'s: matrices ``N(0, 1/fan_in)``
rounded to BFLOAT16, the embedding ``N(0, 1)``, norm weights 1, the router's
bias ``N(0, 0.01^2)``; the prediction module's are drawn last, so the main
model's do not depend on ``mtp``.
"""

from __future__ import annotations

from types import SimpleNamespace as _Sizes
from typing import List

import numpy as np

from ..onnx.builder import make_graph, make_model, node, value_info
from ..onnx.wire import DataType, ModelProto
from .decoder import EXPERT_DOMAIN, KINDS, STATE, Weights, choose, \
    decode_loop, decode_tail, first_token, gated_ffn, gated_weights, infos, \
    rotary_tables, router

__all__ = ["joyai_flash"]

_FLOAT = DataType.FLOAT


def _attention_weights(w: Weights, z: _Sizes, p: str) -> None:
    h, qk = z.hidden, z.nope + z.rope
    w.full(p + "_norm_in_w", (h,), 1.0)
    w.normal(p + "_dq_w", (h, z.q_rank), h ** -0.5)
    w.full(p + "_q_norm_w", (z.q_rank,), 1.0)
    w.normal(p + "_uq_w", (z.q_rank, z.heads * qk), z.q_rank ** -0.5)
    w.normal(p + "_dkv_w", (h, z.kv_rank + z.rope), h ** -0.5)
    w.full(p + "_kv_norm_w", (z.kv_rank,), 1.0)
    w.normal(p + "_uk_w", (z.kv_rank, z.heads * z.nope), z.kv_rank ** -0.5)
    w.normal(p + "_uv_w", (z.kv_rank, z.heads * z.v), z.kv_rank ** -0.5)
    w.normal(p + "_o_w", (z.heads * z.v, h), (z.heads * z.v) ** -0.5)
    w.full(p + "_norm_post_w", (h,), 1.0)


def _expert_weights(w: Weights, z: _Sizes, p: str) -> None:
    h, f = z.hidden, z.expert_width
    w.normal(p + "_router_w", (h, z.experts), h ** -0.5)
    w.normal(p + "_router_bias", (z.experts,), 0.01)
    w.normal(p + "_experts_gate", (z.experts_held, h, f), h ** -0.5)
    w.normal(p + "_experts_up", (z.experts_held, h, f), h ** -0.5)
    w.normal(p + "_experts_down", (z.experts_held, f, h), f ** -0.5)
    gated_weights(w, p + "_shared", h, z.shared_width)


def _queries_and_latents(add, z: _Sizes, p: str, wp: str, u: str,
                         positions: str):
    """What both forms share, over ``u [N, s, hidden]`` at ``positions [N,
    s]``: the queries' two parts a head, ``[N, s, heads, nope]`` and (rotated)
    ``[N, s, heads, rope]``; the normed latent ``[N, s, kv_rank]``, the
    rotated shared key part ``[N, s, rope]`` and the cache's row of both."""
    add(node("MatMul", [u, wp + "_dq_w"], [p + "_cq_raw"], name=p + "_att_dq"))
    add(node("RMSNormalization", [p + "_cq_raw", wp + "_q_norm_w"],
             [p + "_cq"], name=p + "_q_norm", axis=-1, epsilon=z.eps))
    add(node("MatMul", [p + "_cq", wp + "_uq_w"], [p + "_q"],
             name=p + "_att_uq"))
    add(node("Reshape", [p + "_q", "heads_qk_shape"], [p + "_q_h"],
             name=p + "_q_heads"))
    add(node("Split", [p + "_q_h", "nope_rope"],
             [p + "_q_nope", p + "_q_rope_h"], name=p + "_q_split", axis=-1))
    add(node("Reshape", [p + "_q_rope_h", "flat_shape"], [p + "_q_rope_f"],
             name=p + "_q_rope_flat"))
    add(node("RotaryEmbedding", [p + "_q_rope_f", "rope_cos", "rope_sin",
                                 positions], [p + "_q_rope_t"],
             name=p + "_rope_q", num_heads=z.heads, interleaved=1))
    add(node("Reshape", [p + "_q_rope_t", "heads_rope_shape"],
             [p + "_q_rope"], name=p + "_q_rope_heads"))

    add(node("MatMul", [u, wp + "_dkv_w"], [p + "_kv_raw"],
             name=p + "_att_dkv"))
    add(node("Split", [p + "_kv_raw", "rank_rope"],
             [p + "_ckv_raw", p + "_k_r"], name=p + "_kv_split", axis=-1))
    add(node("RMSNormalization", [p + "_ckv_raw", wp + "_kv_norm_w"],
             [p + "_ckv"], name=p + "_kv_norm", axis=-1, epsilon=z.eps))
    add(node("RotaryEmbedding", [p + "_k_r", "rope_cos", "rope_sin",
                                 positions], [p + "_k_rope"],
             name=p + "_rope_k", num_heads=1, interleaved=1))
    add(node("Concat", [p + "_ckv", p + "_k_rope"], [p + "_latent"],
             name=p + "_latent", axis=-1))
    return p + "_q_nope", p + "_q_rope", p + "_ckv", p + "_k_rope", \
        p + "_latent"


def _expanded_attention(add, z: _Sizes, p: str, wp: str, u: str,
                        positions: str):
    """The published form over every position of ``u``; names the context
    ``[N, S, heads * v]`` and the latent rows ``[N, S, kv_rank + rope]``."""
    q_nope, q_rope, ckv, k_rope, latent = _queries_and_latents(
        add, z, p, wp, u, positions)
    add(node("Concat", [q_nope, q_rope], [p + "_q_full_h"],
             name=p + "_q_full_heads", axis=-1))
    add(node("Reshape", [p + "_q_full_h", "flat_shape"], [p + "_q_full"],
             name=p + "_q_full"))
    add(node("MatMul", [ckv, wp + "_uk_w"], [p + "_k_nope_f"],
             name=p + "_att_uk"))
    add(node("Reshape", [p + "_k_nope_f", "heads_nope_shape"],
             [p + "_k_nope"], name=p + "_k_nope_heads"))
    add(node("Unsqueeze", [k_rope, "axes_2"], [p + "_k_rope_1"],
             name=p + "_k_rope_head"))
    add(node("Expand", [p + "_k_rope_1", "every_head_shape"],
             [p + "_k_rope_h"], name=p + "_k_rope_heads"))
    add(node("Concat", [p + "_k_nope", p + "_k_rope_h"], [p + "_k_h"],
             name=p + "_k_heads", axis=-1))
    add(node("Reshape", [p + "_k_h", "flat_shape"], [p + "_k"],
             name=p + "_k"))
    add(node("MatMul", [ckv, wp + "_uv_w"], [p + "_v"], name=p + "_att_uv"))
    add(node("Attention", [p + "_q_full", p + "_k", p + "_v"], [p + "_ctx"],
             name=p + "_att", q_num_heads=z.heads, kv_num_heads=z.heads,
             is_causal=1, scale=z.scale))
    return p + "_ctx", latent


def _absorbed_attention(add, z: _Sizes, p: str, wp: str, u: str, cache: str):
    """One token a row against ``cache [N, L, kv_rank + rope]``, the
    enclosing body's ``position`` (``d_positions [N, 1]``, ``d_position_1d
    [N]``, ``d_visible [1, L]``): names the context ``[N, 1, heads * v]`` and
    the cache with this position's row written."""
    q_nope, q_rope, _, _, latent = _queries_and_latents(
        add, z, p, wp, u, "d_positions")
    add(node("TensorScatter", [cache, latent, "d_position_1d"],
             [p + "_cache"], name=p + "_cache", axis=1))
    # W_uk and W_uv a head at a time, as [kv_rank, heads, size]: a view
    add(node("Reshape", [wp + "_uk_w", "rank_heads_nope"], [p + "_uk_h"],
             name=p + "_uk_heads"))
    add(node("Einsum", [q_nope, p + "_uk_h"], [p + "_q_lat"],
             name=p + "_att_absorb", equation="bqhd,chd->bqhc"))
    add(node("Concat", [p + "_q_lat", q_rope], [p + "_q_abs_h"],
             name=p + "_q_abs_heads", axis=-1))
    add(node("Reshape", [p + "_q_abs_h", "flat_shape"], [p + "_q_abs"],
             name=p + "_q_abs"))
    add(node("Slice", [p + "_cache", "index0", "kv_rank_1d", "axes_2"],
             [p + "_cache_v"], name=p + "_cache_v"))
    add(node("Attention", [p + "_q_abs", p + "_cache", p + "_cache_v",
                           "d_visible"], [p + "_o_lat_f"], name=p + "_att",
             q_num_heads=z.heads, kv_num_heads=1, scale=z.scale))
    add(node("Reshape", [p + "_o_lat_f", "heads_rank_shape"], [p + "_o_lat"],
             name=p + "_o_lat"))
    add(node("Reshape", [wp + "_uv_w", "rank_heads_v"], [p + "_uv_h"],
             name=p + "_uv_heads"))
    add(node("Einsum", [p + "_o_lat", p + "_uv_h"], [p + "_ctx_h"],
             name=p + "_att_uv", equation="bqhc,chd->bqhd"))
    add(node("Reshape", [p + "_ctx_h", "flat_shape"], [p + "_ctx"],
             name=p + "_ctx"))
    return p + "_ctx", p + "_cache"


def _block(nodes: List, w: Weights, z: _Sizes, c: str, i: int, x: str,
           attend, dense: bool) -> str:
    """Block ``i`` in pass ``c`` (``p`` or ``d``) over ``x``; ``attend(p,
    wp, u)`` adds the pass's form of the attention and names its context."""
    p, wp = f"{c}_l{i}", f"l{i}"
    add = nodes.append
    add(node("RMSNormalization", [x, wp + "_norm_in_w"], [p + "_u"],
             name=p + "_norm_in", axis=-1, epsilon=z.eps))
    ctx = attend(p, wp, p + "_u")
    add(node("MatMul", [ctx, wp + "_o_w"], [p + "_att_out"],
             name=p + "_att_o"))
    add(node("Add", [x, p + "_att_out"], [p + "_mid"], name=p + "_res_att"))
    add(node("RMSNormalization", [p + "_mid", wp + "_norm_post_w"],
             [p + "_u2"], name=p + "_norm_post", axis=-1, epsilon=z.eps))
    if dense:
        ffn = gated_ffn(add, p + "_ffn", wp + "_ffn", p + "_u2")
    else:
        top_i, top_w = router(nodes, w, p, p + "_u2", z.hidden, z.experts,
                               z.top_k, z.routed_scaling, weights=wp)
        add(node("ExpertFFN",
                 [p + "_u2", top_i, top_w, wp + "_experts_up",
                  wp + "_experts_down", wp + "_experts_gate"],
                 [p + "_routed"], name=p + "_moe_experts",
                 domain=EXPERT_DOMAIN, first_expert=z.first_expert,
                 num_experts=z.experts, activation="swiglu"))
        shared = gated_ffn(add, p + "_moe_shared", wp + "_shared", p + "_u2")
        add(node("Add", [p + "_routed", shared], [p + "_ffn_out"],
                 name=p + "_moe_sum_shared"))
        ffn = p + "_ffn_out"
    add(node("Add", [p + "_mid", ffn], [p + "_out"], name=p + "_res_ffn"))
    return p + "_out"


def _draft_input(add, z: _Sizes, c: str, next_ids: str, final: str) -> str:
    """The prediction module's input: ``[RMSNorm(Emb(next id));
    RMSNorm(final)] W_eh``."""
    add(node("Gather", ["tok_emb", next_ids], [c + "_mtp_tok"],
             name=c + "_mtp_tok", axis=0))
    add(node("RMSNormalization", [c + "_mtp_tok", "mtp_norm_e_w"],
             [c + "_mtp_e"], name=c + "_mtp_norm_e", axis=-1, epsilon=z.eps))
    add(node("RMSNormalization", [final, "mtp_norm_h_w"], [c + "_mtp_h"],
             name=c + "_mtp_norm_h", axis=-1, epsilon=z.eps))
    add(node("Concat", [c + "_mtp_e", c + "_mtp_h"], [c + "_mtp_eh"],
             name=c + "_mtp_concat", axis=-1))
    add(node("MatMul", [c + "_mtp_eh", "mtp_eh_w"], [c + "_mtp_in"],
             name=c + "_mtp_eh"))
    return c + "_mtp_in"


def joyai_flash(layers: int = 9, hidden: int = 2048, vocab: int = 129280,
                heads: int = 32, q_lora_rank: int = 1536,
                kv_lora_rank: int = 512, nope: int = 128, rope: int = 64,
                v_dim: int = 128, dense_width: int = 7168,
                experts: int = 256, top_k: int = 8, expert_width: int = 768,
                shared_width: int = 768, routed_scaling: float = 2.5,
                first_expert: int = 0, experts_held: int = 32,
                rope_theta: float = 32e6, eps: float = 1e-6,
                generate: int = 128, mtp: int = 0, seed: int = 0
                ) -> ModelProto:
    """One chip's share of a pipeline stage of ``joyai_llm_flash`` with both
    ends' embedding and head (module docstring); the defaults are the
    published widths of JoyAI-LLM-Flash with the leading dense layer and
    eight of the 39 expert layers, 32 of each layer's 256 routed experts held
    under the published router, generating ``generate`` ids."""
    if layers < 2 or generate < 2 or rope % 2 or mtp not in (0, 1):
        raise ValueError(
            f"layers {layers} (one dense, then expert layers) and generate "
            f"{generate} are at least 2, rope {rope} even, mtp {mtp} 0 or 1")
    z = _Sizes(hidden=hidden, heads=heads, q_rank=q_lora_rank,
               kv_rank=kv_lora_rank, nope=nope, rope=rope, v=v_dim,
               experts=experts, top_k=top_k, expert_width=expert_width,
               shared_width=shared_width, routed_scaling=routed_scaling,
               first_expert=first_expert, experts_held=experts_held, eps=eps,
               scale=float((nope + rope) ** -0.5))
    n_blocks = layers + mtp  # the prediction module's block comes last
    w = Weights(seed)
    w.normal("tok_emb", (vocab, hidden), 1.0)
    for i in range(layers):
        _attention_weights(w, z, f"l{i}")
        if i == 0:
            gated_weights(w, "l0_ffn", hidden, dense_width)
        else:
            _expert_weights(w, z, f"l{i}")
    w.full("norm_f_w", (hidden,), 1.0)
    w.normal("lm_head", (hidden, vocab), hidden ** -0.5)
    latent = kv_lora_rank + rope
    for name, values in (
            ("zero", 0), ("one", 1), ("index0", [0]), ("index1", [1]),
            ("axes_0", [0]), ("axes_1", [1]), ("axes_2", [2]),
            ("axes_last", [-1]), ("one_1d", [1]), ("generate_1d", [generate]),
            ("hidden_1d", [hidden]), ("kv_rank_1d", [kv_lora_rank]),
            ("trips", generate - 1), ("huge_1d", [np.iinfo(np.int64).max]),
            ("nope_rope", [nope, rope]), ("rank_rope", [kv_lora_rank, rope]),
            ("heads_qk_shape", [0, 0, heads, nope + rope]),
            ("heads_rope_shape", [0, 0, heads, rope]),
            ("heads_nope_shape", [0, 0, heads, nope]),
            ("heads_rank_shape", [0, 0, heads, kv_lora_rank]),
            ("rank_heads_nope", [kv_lora_rank, heads, nope]),
            ("rank_heads_v", [kv_lora_rank, heads, v_dim]),
            ("every_head_shape", [1, 1, heads, 1]),
            ("flat_shape", [0, 0, -1]),
            ("cache_pad", [0, 0, 0, 0, generate, 0])):
        w.ints(name, values)
    if mtp:  # drawn last: the main model's weights do not depend on mtp
        _attention_weights(w, z, f"l{layers}")
        _expert_weights(w, z, f"l{layers}")
        w.full("mtp_norm_e_w", (hidden,), 1.0)
        w.full("mtp_norm_h_w", (hidden,), 1.0)
        w.normal("mtp_eh_w", (2 * hidden, hidden), (2 * hidden) ** -0.5)
        w.full("mtp_norm_s_w", (hidden,), 1.0)

    nodes: List = []
    add = nodes.append
    # sizes from the feed's shape (constants of a trace): N, S, L = S + G
    add(node("Shape", ["input_ids"], ["ids_shape"], name="ids_shape"))
    add(node("Gather", ["ids_shape", "index0"], ["n_1d"], name="n_1d"))
    add(node("Gather", ["ids_shape", "index1"], ["s_1d"], name="s_1d"))
    add(node("Squeeze", ["s_1d", "axes_0"], ["prompt_len"],
             name="prompt_len"))
    add(node("Add", ["s_1d", "generate_1d"], ["total_1d"], name="total_1d"))
    add(node("Squeeze", ["total_1d", "axes_0"], ["total_len"],
             name="total_len"))
    add(node("Sub", ["s_1d", "one_1d"], ["last_1d"], name="last_1d"))
    add(node("Range", ["zero", "total_len", "one"], ["all_positions"],
             name="all_positions"))
    add(node("Range", ["zero", "prompt_len", "one"], ["prompt_range"],
             name="prompt_range"))
    add(node("Concat", ["n_1d", "one_1d"], ["n_one_shape"],
             name="n_one_shape", axis=0))
    add(node("Concat", ["n_1d", "generate_1d"], ["n_generate_shape"],
             name="n_generate_shape", axis=0))
    rotary_tables(add, rope_theta, rope)
    add(node("Unsqueeze", ["prompt_range", "axes_0"], ["prompt_row"],
             name="prompt_row"))
    add(node("Expand", ["prompt_row", "ids_shape"], ["prompt_positions"],
             name="prompt_positions"))

    # ---- the prompt pass: the expanded form, the latents into the caches
    caches: List[str] = []

    def prompt_attend(p, wp, u):
        ctx, rows = _expanded_attention(add, z, p, wp, u, "prompt_positions")
        add(node("Pad", [rows, "cache_pad"], [p + "_cache"],
                 name=p + "_cache", mode="constant"))
        caches.append(p + "_cache")
        return ctx

    add(node("Gather", ["tok_emb", "input_ids"], ["p_tok"], name="p_tok",
             axis=0))
    x = "p_tok"
    for i in range(layers):
        x = _block(nodes, w, z, "p", i, x, prompt_attend, dense=i == 0)
    if mtp:  # the prediction module reads the final norm at every position
        add(node("RMSNormalization", [x, "norm_f_w"], ["p_final_all"],
                 name="p_norm_f", axis=-1, epsilon=eps))
        add(node("Gather", ["p_final_all", "last_1d"], ["p_final"],
                 name="p_last", axis=1))
    else:  # the last position alone goes through the norm and the head
        add(node("Gather", [x, "last_1d"], ["p_last"], name="p_last", axis=1))
        add(node("RMSNormalization", ["p_last", "norm_f_w"], ["p_final"],
                 name="p_norm_f", axis=-1, epsilon=eps))
    state, kinds = list(STATE), list(KINDS)
    starts = first_token(add, choose)
    if mtp:
        # position t's next id: the prompt shifted by one, then id 0
        add(node("Slice", ["input_ids", "index1", "huge_1d", "axes_1"],
                 ["p_ids_after"], name="p_ids_after"))
        add(node("Concat", ["p_ids_after", starts[0]],  # id 0
                 ["p_next_ids"],
                 name="p_next_ids", axis=1))
        x = _draft_input(add, z, "p", "p_next_ids", "p_final_all")
        x = _block(nodes, w, z, "p", layers, x, prompt_attend, dense=False)
        add(node("Gather", [x, "last_1d"], ["p_mtp_last"], name="p_mtp_last",
                 axis=1))
        add(node("RMSNormalization", ["p_mtp_last", "mtp_norm_s_w"],
                 ["p_mtp_final"], name="p_mtp_norm_s", axis=-1, epsilon=eps))
        draft, draft_logprob = choose(add, "p_mtp", "p_mtp_final")
        add(node("TensorScatter", ["tokens_zero", draft, "row_zero"],
                 ["draft_tokens_start"], name="draft_tokens_start", axis=1))
        add(node("TensorScatter", ["logprob_zero", draft_logprob,
                                   "row_zero"], ["draft_logprob_start"],
                 name="draft_logprob_start", axis=1))
        state += ["draft_tokens", "draft_logprob"]
        kinds += [np.int64, np.float32]
        starts += ["draft_tokens_start", "draft_logprob_start"]

    # ---- the body of Loop "decode": one token a row, the absorbed form
    d_in = ["trip", "trip_cond"] + ["d_" + s for s in state] \
        + [f"d_cache{j}" for j in range(n_blocks)]
    d_nodes: List = []
    d_add = d_nodes.append
    d_add(node("Add", ["trip", "prompt_len"], ["d_position"],
               name="d_position"))
    d_add(node("Expand", ["d_position", "n_1d"], ["d_position_1d"],
               name="d_position_1d"))
    d_add(node("Expand", ["d_position", "n_one_shape"], ["d_positions"],
               name="d_positions"))
    d_add(node("LessOrEqual", ["all_positions", "d_position"],
               ["d_visible_1d"], name="d_visible_1d"))
    d_add(node("Unsqueeze", ["d_visible_1d", "axes_0"], ["d_visible"],
               name="d_visible"))
    d_add(node("Add", ["trip", "one"], ["d_slot"], name="d_slot"))
    d_add(node("Expand", ["d_slot", "n_1d"], ["d_slot_1d"], name="d_slot_1d"))
    new_caches = list(d_in[2 + len(state):])

    def cached_attend(i):
        def attend(p, wp, u):
            ctx, new_caches[i] = _absorbed_attention(d_add, z, p, wp, u,
                                                     new_caches[i])
            return ctx
        return attend

    d_add(node("Gather", ["tok_emb", "d_last_id"], ["d_tok"], name="d_tok",
               axis=0))
    x = "d_tok"
    for i in range(layers):
        x = _block(d_nodes, w, z, "d", i, x, cached_attend(i), dense=i == 0)
    d_out = decode_tail(d_add, choose, x, eps)
    if mtp:
        x = _draft_input(d_add, z, "d", d_out[1], "d_final")  # the new id
        x = _block(d_nodes, w, z, "d", layers, x, cached_attend(layers),
                   dense=False)
        d_add(node("RMSNormalization", [x, "mtp_norm_s_w"], ["d_mtp_final"],
                   name="d_mtp_norm_s", axis=-1, epsilon=eps))
        draft, draft_logprob = choose(d_add, "d_mtp", "d_mtp_final")
        d_add(node("TensorScatter", ["d_draft_tokens", draft, "d_slot_1d"],
                   ["d_draft_tokens_out"], name="d_draft_tokens_out", axis=1))
        d_add(node("TensorScatter", ["d_draft_logprob", draft_logprob,
                                     "d_slot_1d"], ["d_draft_logprob_out"],
                   name="d_draft_logprob_out", axis=1))
        d_out += ["d_draft_tokens_out", "d_draft_logprob_out"]
    body = make_graph(
        d_nodes, "decode_pass",
        infos(d_in, [np.int64, np.bool_] + kinds, n_blocks),
        infos(d_out + new_caches, [np.bool_] + kinds, n_blocks))

    # ---- the loop and the outputs
    outputs = decode_loop(
        add, body, state, starts + caches,
        [f"final_cache{j}" for j in range(n_blocks)], generate, hidden)
    if mtp:
        add(node("Identity", ["draft_tokens_total"], ["draft_tokens"],
                 name="draft_tokens"))
        add(node("Identity", ["draft_logprob_total"], ["draft_logprob"],
                 name="draft_logprob"))
        outputs += [value_info("draft_tokens", np.int64, ["N", generate]),
                    value_info("draft_logprob", np.float32, ["N", generate])]

    w.fill_all()
    graph = make_graph(
        nodes, f"joyai_flash_{layers}l_h{hidden}_g{generate}",
        [value_info("input_ids", np.int64, ["N", "S"])], outputs, w.store)
    return make_model(graph, opset=24, domains={EXPERT_DOMAIN: 1})
