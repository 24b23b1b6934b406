"""``olmo_hybrid``: the hybrid Gated DeltaNet / attention decoder of
Olmo-Hybrid-7B, generation included, as ONE ONNX graph for the zoo.

Layer ``i`` is the Olmo 2/3 block, norms AFTER each sublayer: ``x <- x +
RMSNorm(mixer_i(x))``, ``x <- x + RMSNorm(W_down(silu(W_gate x) * (W_up
x)))``. ``mixer_i`` is full attention where ``i % attn_period ==
attn_offset`` (``heads`` heads of ``head_dim`` on as many key-value heads;
queries and keys each through an RMSNorm over the whole projection, the
family's ``q_norm`` / ``k_norm``; no bias and NO positional term:
``rope_theta`` is null) and a gated delta rule layer everywhere else, over
``x [N, S, hidden]``:

1. ``[q, k, v] = silu(conv(x W_qkv))``: one projection, then a depthwise
   causal convolution over positions, ``conv_kernel`` wide, no bias, over
   all ``2 H dk + H dv`` channels; ``q``, ``k`` ``[N, S, H, dk]``, ``v`` ``[N,
   S, H, dv]``;
2. ``g = -exp(A_log) softplus(x W_a + b_dt)``, ``beta = 2 sigmoid(x W_b)``
   (``linear_allow_neg_eigval``: the state's eigenvalues may be negative),
   float32, ``[N, S, H]``;
3. the rule, every position in order: ``S <- exp(g) S``, ``u = beta (v - S^T
   k)``, ``S <- S + k u^T``, ``o = S^T q`` with ``q``, ``k`` divided by their
   norms and ``q`` by ``sqrt(dk)``;
4. ``out = (RMSNorm(o) * silu(x W_gate)) W_out``, the norm a head over
   ``dv`` with one weight every head shares.

Step 3 is ONE node of a custom domain, ``synapseml_tpu::GatedDeltaRule(q,
k, v, g, beta[, state_in]) -> (out, state_out)``: what an exporter of the
modelling code's kernel path (``chunk_gated_delta_rule``,
``use_qk_l2norm_in_kernel``) would write. After the last layer an RMSNorm
and an untied head.

The graph generates, greedily. Input ``input_ids [N, S]``; outputs a row:
``tokens [generate]``, ``chosen_logprob [generate]`` (the log-softmax of the
chosen id) and ``pooled [hidden]`` (the mean, over the ``generate`` positions
the ids were chosen from, of the final norm's output).

- The prompt pass (nodes ``p_l#_...``) runs every position. A delta rule
  layer leaves the state after the last position (``GatedDeltaRule``'s
  second output, ``[N, dk, H x dv]`` float32) and the last ``conv_kernel -
  1`` rows of step 1's projection, positions major (``[conv_kernel - 1, N,
  2 H dk + H dv]``); an attention layer its normed keys and its values,
  padded once to a cache ``[N, S + generate, heads x head_dim]``. The final
  norm and the head at the last position give id 0.
- ``Loop`` ``decode`` (``generate - 1`` trips; nodes ``d_l#_...``) embeds
  the last id, ONE position a row, and runs the same layers: the
  convolution as the published single step over the window ``[conv_kernel,
  N, channels]`` of the kept rows and the new one, in float32;
  ``GatedDeltaRule`` at one position from the carried state;
  ``TensorScatter`` of the position's key and value and ``Attention``
  against the cache under a mask from the trip counter. It carries two
  kinds of state side by side: a delta rule layer's (state, convolution
  rows), REPLACED every pass, and an attention layer's (keys, values),
  written in place.

Everything else is a standard operator of opset 24. Bodies read the outer
graph's initializers: weights are named ``l#_...`` once and used by both
passes. Weights are seeded draws as ``jamba``'s: matrices ``N(0,
1/fan_in)`` rounded to BFLOAT16, norm weights 1, the convolution ``N(0,
1/conv_kernel)``, ``b_dt`` the inverse softplus of a log-uniform step in
``[dt_min, dt_max]``; ``A_log`` the log of a uniform draw in ``(0, 16]`` a
head (the family's own initialisation), a FLOAT initializer of numbers a
bfloat16 holds, since the checkpoint is one.
"""

from __future__ import annotations

from types import SimpleNamespace as _Sizes
from typing import List

import numpy as np

from ..onnx.builder import make_graph, make_model, node, value_info
from ..onnx.wire import DataType, ModelProto
from .joyai_flash import _choose, _gated_ffn, _gated_weights
from .nemotron_h import EXPERT_DOMAIN, _Weights
from .sdar_moe import _of_shape

__all__ = ["olmo_hybrid"]

_FLOAT = DataType.FLOAT


def _is_attention(z: _Sizes, i: int) -> bool:
    return i % z.attn_period == z.attn_offset


def _delta_weights(w: _Weights, z: _Sizes, p: str) -> None:
    h, lh = z.hidden, z.linear_heads
    w.normal(p + "_qkv_w", (h, z.channels), h ** -0.5)
    w.normal(p + "_conv_w", (z.channels, 1, z.conv), z.conv ** -0.5)
    w.normal(p + "_ab_w", (h, 2 * lh), h ** -0.5)

    def dt_bias(rng, scratch):
        # the inverse softplus of a log-uniform step
        dt = np.exp(rng.uniform(np.log(z.dt_min), np.log(z.dt_max),
                                scratch.size))
        scratch[...] = dt + np.log(-np.expm1(-dt))

    w.draw(p + "_dt_b", (lh,), dt_bias)
    # a float32 initializer of numbers a bfloat16 holds (module docstring)
    a = np.random.default_rng((w.seed, len(w.store))).uniform(0, 16, lh)
    w.store[p + "_a_log"] = np.log(np.maximum(a, 1e-4)).astype(
        w.bfloat16).astype(np.float32)
    w.normal(p + "_gate_w", (h, lh * z.value_dim), h ** -0.5)
    w.full(p + "_o_norm_w", (z.value_dim,), 1.0)
    w.normal(p + "_out_w", (lh * z.value_dim, h), (lh * z.value_dim) ** -0.5)


def _attention_weights(w: _Weights, z: _Sizes, p: str) -> None:
    h, wide = z.hidden, z.heads * z.head_dim
    for part in ("q", "k", "v"):
        w.normal(f"{p}_{part}_w", (h, wide), h ** -0.5)
    w.full(p + "_q_norm_w", (wide,), 1.0)
    w.full(p + "_k_norm_w", (wide,), 1.0)
    w.normal(p + "_o_w", (wide, h), wide ** -0.5)


def _silu(add, p: str, x: str) -> str:
    add(node("Sigmoid", [x], [x + "_s"], name=x + "_silu_s"))
    add(node("Mul", [x, x + "_s"], [p], name=p))
    return p


def _delta(add, z: _Sizes, p: str, wp: str, x: str, conv_rows: str = None,
           state_in: str = None):
    """The gated delta rule mixer over ``x [N, s, hidden]``. The prompt pass
    (no ``conv_rows``) pads the convolution with zeros and starts the state
    from zero; a decode pass convolves ``conv_rows [conv - 1, N, channels]``
    and its one new row and starts from ``state_in``. Names the mix, the
    state after the last position and the rows the next pass's convolution
    reads."""
    add(node("MatMul", [x, wp + "_qkv_w"], [p + "_qkv_raw"],
             name=p + "_qkv_proj"))
    if conv_rows is None:
        add(node("Slice", [p + "_qkv_raw", "conv_keep_from", "huge_1d",
                           "axes_1"], [p + "_conv_kept"],
                 name=p + "_conv_kept"))
        add(node("Transpose", [p + "_conv_kept"], [p + "_conv_rows"],
                 name=p + "_conv_rows", perm=[1, 0, 2]))
        add(node("Transpose", [p + "_qkv_raw"], [p + "_conv_in"],
                 name=p + "_conv_in", perm=[0, 2, 1]))
        add(node("Conv", [p + "_conv_in", wp + "_conv_w"], [p + "_conv_t"],
                 name=p + "_conv", group=z.channels, kernel_shape=[z.conv],
                 pads=[z.conv - 1, 0]))
        add(node("Transpose", [p + "_conv_t"], [p + "_conv_out"],
                 name=p + "_conv_out", perm=[0, 2, 1]))
    else:
        # the published single step over a window [conv, N, channels]
        add(node("Transpose", [p + "_qkv_raw"], [p + "_qkv_new"],
                 name=p + "_qkv_new", perm=[1, 0, 2]))
        add(node("Concat", [conv_rows, p + "_qkv_new"], [p + "_window"],
                 name=p + "_window", axis=0))
        add(node("Slice", [p + "_window", "index1", "huge_1d", "axes_0"],
                 [p + "_conv_rows"], name=p + "_conv_rows"))
        add(node("Cast", [p + "_window"], [p + "_window_f"],
                 name=p + "_window_f", to=_FLOAT))
        add(node("Mul", [p + "_window_f", wp + "_conv_taps"],
                 [p + "_conv_terms"], name=p + "_conv_terms"))
        add(node("ReduceSum", [p + "_conv_terms", "axes_0"],
                 [p + "_conv_sum"], name=p + "_conv_sum", keepdims=0))
        add(node("Unsqueeze", [p + "_conv_sum", "axes_1"],
                 [p + "_conv_row"], name=p + "_conv_row"))
        add(node("CastLike", [p + "_conv_row", p + "_qkv_raw"],
                 [p + "_conv_out"], name=p + "_conv_out"))
    qkv = _silu(add, p + "_qkv", p + "_conv_out")
    add(node("Split", [qkv, "qkv_split"],
             [p + "_q_flat", p + "_k_flat", p + "_v_flat"],
             name=p + "_qkv_split", axis=-1))
    for part, heads in (("q", "qk_heads"), ("k", "qk_heads"), ("v", "v_heads")):
        add(node("Reshape", [f"{p}_{part}_flat", heads], [f"{p}_{part}"],
                 name=f"{p}_{part}_heads"))
    # the decay's log and the write strength, float32 [N, s, H]
    add(node("MatMul", [x, wp + "_ab_w"], [p + "_ab"], name=p + "_ab_proj"))
    add(node("Cast", [p + "_ab"], [p + "_ab_f"], name=p + "_ab_f",
             to=_FLOAT))
    add(node("Split", [p + "_ab_f", "ab_split"], [p + "_a_raw", p + "_b_raw"],
             name=p + "_ab_split", axis=-1))
    add(node("Add", [p + "_a_raw", wp + "_dt_b_f"], [p + "_dt_raw"],
             name=p + "_dt_raw"))
    add(node("Softplus", [p + "_dt_raw"], [p + "_dt"], name=p + "_dt"))
    add(node("Mul", [p + "_dt", wp + "_a"], [p + "_g"], name=p + "_g"))
    add(node("Sigmoid", [p + "_b_raw"], [p + "_b_s"], name=p + "_b_s"))
    add(node("Mul", [p + "_b_s", "two_f"], [p + "_beta"], name=p + "_beta"))
    add(node("GatedDeltaRule",
             [p + "_q", p + "_k", p + "_v", p + "_g", p + "_beta"]
             + ([state_in] if state_in else []),
             [p + "_o", p + "_state"], name=p + "_gdn", domain=EXPERT_DOMAIN))
    add(node("RMSNormalization", [p + "_o", wp + "_o_norm_w"],
             [p + "_o_n"], name=p + "_o_norm", axis=-1, epsilon=z.eps))
    add(node("MatMul", [x, wp + "_gate_w"], [p + "_gate_flat"],
             name=p + "_gate_proj"))
    add(node("Reshape", [p + "_gate_flat", "v_heads"], [p + "_gate"],
             name=p + "_gate_heads"))
    gate = _silu(add, p + "_gate_a", p + "_gate")
    add(node("Mul", [p + "_o_n", gate], [p + "_o_gated"],
             name=p + "_o_gated"))
    add(node("Reshape", [p + "_o_gated", "v_merged"], [p + "_o_flat"],
             name=p + "_o_merged"))
    add(node("MatMul", [p + "_o_flat", wp + "_out_w"], [p + "_mix"],
             name=p + "_out_proj"))
    return p + "_mix", p + "_state", p + "_conv_rows"


def _projections(add, z: _Sizes, p: str, wp: str, x: str):
    """Queries and keys through their norms, and values, ``[N, s, heads x
    head_dim]``."""
    for part in ("q", "k", "v"):
        add(node("MatMul", [x, f"{wp}_{part}_w"], [f"{p}_{part}_raw"],
                 name=f"{p}_att_{part}"))
    for part in ("q", "k"):
        add(node("RMSNormalization", [f"{p}_{part}_raw",
                                      f"{wp}_{part}_norm_w"],
                 [f"{p}_{part}"], name=f"{p}_{part}_norm", axis=-1,
                 epsilon=z.eps))
    return p + "_q", p + "_k", p + "_v_raw"


def _block(nodes: List, z: _Sizes, c: str, i: int, x: str, mixer) -> str:
    """Layer ``i`` in pass ``c`` (``p`` or ``d``) over ``x``; ``mixer(p, wp,
    x)`` adds the pass's form of the layer's mixer and names its result."""
    p, wp = f"{c}_l{i}", f"l{i}"
    add = nodes.append
    add(node("RMSNormalization", [mixer(p, wp, x), wp + "_norm_mix_w"],
             [p + "_mix_n"], name=p + "_norm_mix", axis=-1, epsilon=z.eps))
    add(node("Add", [x, p + "_mix_n"], [p + "_mid"], name=p + "_res_mix"))
    ffn = _gated_ffn(add, p + "_ffn", wp + "_ffn", p + "_mid")
    add(node("RMSNormalization", [ffn, wp + "_norm_ffn_w"], [p + "_ffn_n"],
             name=p + "_norm_ffn", axis=-1, epsilon=z.eps))
    add(node("Add", [p + "_mid", p + "_ffn_n"], [p + "_out"],
             name=p + "_res_ffn"))
    return p + "_out"


def olmo_hybrid(layers: int = 32, hidden: int = 3840, vocab: int = 100352,
                heads: int = 30, head_dim: int = 128, linear_heads: int = 30,
                key_dim: int = 96, value_dim: int = 192, conv_kernel: int = 4,
                width: int = 11008, attn_period: int = 4,
                attn_offset: int = 3, eps: float = 1e-6, dt_min: float = 1e-3,
                dt_max: float = 1e-1, generate: int = 128, seed: int = 0
                ) -> ModelProto:
    """The ``olmo_hybrid`` decoder (module docstring); the defaults are
    Olmo-Hybrid-7B as published: 32 layers, full attention at every fourth
    (3, 7, ... 31), every width, the whole vocabulary, generating
    ``generate`` ids."""
    if generate < 2 or layers < 1:
        raise ValueError(f"generate {generate} is at least 2, layers "
                         f"{layers} at least 1")
    z = _Sizes(hidden=hidden, heads=heads, head_dim=head_dim,
               linear_heads=linear_heads, key_dim=key_dim,
               value_dim=value_dim, conv=conv_kernel,
               channels=linear_heads * (2 * key_dim + value_dim),
               attn_period=attn_period, attn_offset=attn_offset, eps=eps,
               dt_min=dt_min, dt_max=dt_max)
    w = _Weights(seed)
    w.normal("tok_emb", (vocab, hidden), hidden ** -0.5)
    for i in range(layers):
        p = f"l{i}"
        if _is_attention(z, i):
            _attention_weights(w, z, p)
        else:
            _delta_weights(w, z, p)
        w.full(p + "_norm_mix_w", (hidden,), 1.0)
        _gated_weights(w, p + "_ffn", hidden, width)
        w.full(p + "_norm_ffn_w", (hidden,), 1.0)
    w.full("norm_f_w", (hidden,), 1.0)
    w.normal("lm_head", (hidden, vocab), hidden ** -0.5)
    w.store["two"] = np.asarray(2.0, np.float32)
    lh, dk, dv = linear_heads, key_dim, value_dim
    for name, values in (
            ("zero", 0), ("one", 1), ("index0", [0]), ("index1", [1]),
            ("axes_0", [0]), ("axes_1", [1]), ("axes_last", [-1]),
            ("one_1d", [1]),
            ("generate_1d", [generate]), ("trips", generate - 1),
            ("huge_1d", [np.iinfo(np.int64).max]),
            ("conv_keep_from", [-(conv_kernel - 1)]),
            ("qkv_split", [lh * dk, lh * dk, lh * dv]),
            ("ab_split", [lh, lh]), ("qk_heads", [0, 0, lh, dk]),
            ("v_heads", [0, 0, lh, dv]), ("v_merged", [0, 0, lh * dv]),
            ("cache_pad", [0, 0, 0, 0, generate, 0])):
        w.ints(name, values)

    nodes: List = []
    add = nodes.append
    delta = [i for i in range(layers) if not _is_attention(z, i)]
    add(node("Cast", ["two"], ["two_f"], name="two_f", to=_FLOAT))
    # what both passes read of a delta rule layer, made once: -exp(A_log)
    # and the step's bias in float32, the decode pass's taps [conv, 1, C]
    for i in delta:
        p = f"l{i}"
        add(node("Cast", [p + "_a_log"], [p + "_a_log_f"],
                 name=p + "_a_log_f", to=_FLOAT))
        add(node("Exp", [p + "_a_log_f"], [p + "_a_exp"], name=p + "_a_exp"))
        add(node("Neg", [p + "_a_exp"], [p + "_a"], name=p + "_a"))
        add(node("Cast", [p + "_dt_b"], [p + "_dt_b_f"], name=p + "_dt_b_f",
                 to=_FLOAT))
        add(node("Transpose", [p + "_conv_w"], [p + "_conv_taps_t"],
                 name=p + "_conv_taps_t", perm=[2, 1, 0]))
        add(node("Cast", [p + "_conv_taps_t"], [p + "_conv_taps"],
                 name=p + "_conv_taps", to=_FLOAT))
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
    add(node("Concat", ["n_1d", "generate_1d"], ["n_generate_shape"],
             name="n_generate_shape", axis=0))

    # ---- the prompt pass: every position; the states and caches it leaves
    carried = {}  # a layer's two carried values, as the prompt pass names them

    def prompt_mixer(i):
        def mixer(p, wp, x):
            if not _is_attention(z, i):
                mix, *carried[i] = _delta(add, z, p, wp, x)
                return mix
            q, k, v = _projections(add, z, p, wp, x)
            add(node("Attention", [q, k, v], [p + "_ctx"], name=p + "_att",
                     q_num_heads=z.heads, kv_num_heads=z.heads, is_causal=1))
            carried[i] = []
            for rows in (k, v):
                add(node("Pad", [rows, "cache_pad"], [rows + "_cache"],
                         name=rows + "_cache", mode="constant"))
                carried[i].append(rows + "_cache")
            add(node("MatMul", [p + "_ctx", wp + "_o_w"], [p + "_mix"],
                     name=p + "_att_o"))
            return p + "_mix"
        return mixer

    add(node("Gather", ["tok_emb", "input_ids"], ["p_tok"], name="p_tok",
             axis=0))
    x = "p_tok"
    for i in range(layers):
        x = _block(nodes, z, "p", i, x, prompt_mixer(i))
    add(node("Gather", [x, "last_1d"], ["p_last"], name="p_last", axis=1))
    add(node("RMSNormalization", ["p_last", "norm_f_w"], ["p_final"],
             name="p_norm_f", axis=-1, epsilon=eps))
    first_id, first_logprob = _choose(add, "p", "p_final")
    add(_of_shape("row_zero", "n_1d", np.int64(0)))
    add(_of_shape("tokens_zero", "n_generate_shape", np.int64(0)))
    add(_of_shape("logprob_zero", "n_generate_shape", np.float32(0)))
    add(node("TensorScatter", ["tokens_zero", first_id, "row_zero"],
             ["tokens_start"], name="tokens_start", axis=1))
    add(node("TensorScatter", ["logprob_zero", first_logprob, "row_zero"],
             ["logprob_start"], name="logprob_start", axis=1))
    add(node("Cast", ["p_final"], ["p_final_f"], name="p_final_f", to=_FLOAT))
    add(node("Squeeze", ["p_final_f", "axes_1"], ["pooled_start"],
             name="pooled_start"))
    state = ["last_id", "tokens", "chosen_logprob", "pooled_sum"]
    kinds = [np.int64, np.int64, np.float32, np.float32]
    starts = [first_id, "tokens_start", "logprob_start", "pooled_start"]

    # ---- the body of Loop "decode": one position a row
    # a delta rule layer carries (state, convolution rows), replaced every
    # pass; an attention layer (keys, values), written in place
    layer_kinds = {i: ([w.bfloat16] * 2 if _is_attention(z, i)
                       else [np.float32, w.bfloat16])
                   for i in range(layers)}
    d_carried = {i: [f"d_carried{i}_{j}" for j in range(2)]
                 for i in range(layers)}
    d_nodes: List = []
    d_add = d_nodes.append
    d_add(node("Add", ["trip", "prompt_len"], ["d_position"],
               name="d_position"))
    d_add(node("Expand", ["d_position", "n_1d"], ["d_position_1d"],
               name="d_position_1d"))
    d_add(node("LessOrEqual", ["all_positions", "d_position"],
               ["d_visible_1d"], name="d_visible_1d"))
    d_add(node("Unsqueeze", ["d_visible_1d", "axes_0"], ["d_visible"],
               name="d_visible"))
    d_add(node("Add", ["trip", "one"], ["d_slot"], name="d_slot"))
    d_add(node("Expand", ["d_slot", "n_1d"], ["d_slot_1d"], name="d_slot_1d"))
    d_left = {}

    def decode_mixer(i):
        def mixer(p, wp, x):
            if not _is_attention(z, i):
                state_in, conv_rows = d_carried[i]
                mix, *d_left[i] = _delta(d_add, z, p, wp, x, conv_rows,
                                         state_in)
                return mix
            q, k, v = _projections(d_add, z, p, wp, x)
            d_left[i] = []
            for rows, cache in zip((k, v), d_carried[i]):
                d_add(node("TensorScatter", [cache, rows, "d_position_1d"],
                           [rows + "_cache"], name=rows + "_cache", axis=1))
                d_left[i].append(rows + "_cache")
            d_add(node("Attention", [q, *d_left[i], "d_visible"],
                       [p + "_ctx"], name=p + "_att", q_num_heads=z.heads,
                       kv_num_heads=z.heads))
            d_add(node("MatMul", [p + "_ctx", wp + "_o_w"], [p + "_mix"],
                       name=p + "_att_o"))
            return p + "_mix"
        return mixer

    d_add(node("Gather", ["tok_emb", "d_last_id"], ["d_tok"], name="d_tok",
               axis=0))
    x = "d_tok"
    for i in range(layers):
        x = _block(d_nodes, z, "d", i, x, decode_mixer(i))
    d_add(node("RMSNormalization", [x, "norm_f_w"], ["d_final"],
               name="d_norm_f", axis=-1, epsilon=eps))
    new_id, new_logprob = _choose(d_add, "d", "d_final")
    d_add(node("TensorScatter", ["d_tokens", new_id, "d_slot_1d"],
               ["d_tokens_out"], name="d_tokens_out", axis=1))
    d_add(node("TensorScatter", ["d_chosen_logprob", new_logprob,
                                 "d_slot_1d"], ["d_chosen_logprob_out"],
               name="d_chosen_logprob_out", axis=1))
    d_add(node("Cast", ["d_final"], ["d_final_f"], name="d_final_f",
               to=_FLOAT))
    d_add(node("Squeeze", ["d_final_f", "axes_1"], ["d_final_row"],
               name="d_final_row"))
    d_add(node("Add", ["d_pooled_sum", "d_final_row"], ["d_pooled_sum_out"],
               name="d_pooled_sum_out"))
    d_add(node("Identity", ["trip_cond"], ["trip_cond_out"],
               name="trip_cond_out"))
    of_layers = [k for i in range(layers) for k in layer_kinds[i]]
    d_in = ["trip", "trip_cond"] + ["d_" + s for s in state] \
        + [name for i in range(layers) for name in d_carried[i]]
    d_out = ["trip_cond_out", new_id, "d_tokens_out", "d_chosen_logprob_out",
             "d_pooled_sum_out"] \
        + [name for i in range(layers) for name in d_left[i]]
    body = make_graph(
        d_nodes, "decode_pass",
        [value_info(n, t) for n, t in zip(
            d_in, [np.int64, np.bool_] + kinds + of_layers)],
        [value_info(n, t) for n, t in zip(
            d_out, [np.bool_] + kinds + of_layers)])

    # ---- the loop and the outputs
    add(node("Loop", ["trips", ""] + starts
             + [name for i in range(layers) for name in carried[i]],
             [s + "_total" for s in state]
             + [f"final_carried{i}_{j}" for i in range(layers)
                for j in range(2)],
             name="decode", body=body))
    add(node("Identity", ["tokens_total"], ["tokens"], name="tokens"))
    add(node("Identity", ["chosen_logprob_total"], ["chosen_logprob"],
             name="chosen_logprob"))
    add(node("Cast", ["generate_1d"], ["generate_f"], name="generate_f",
             to=_FLOAT))
    add(node("Div", ["pooled_sum_total", "generate_f"], ["pooled"],
             name="pooled"))

    w.fill_all()
    graph = make_graph(
        nodes, f"olmo_hybrid_{layers}l_h{hidden}_g{generate}",
        [value_info("input_ids", np.int64, ["N", "S"])],
        [value_info("tokens", np.int64, ["N", generate]),
         value_info("chosen_logprob", np.float32, ["N", generate]),
         value_info("pooled", np.float32, ["N", hidden])], w.store)
    return make_model(graph, opset=24, domains={EXPERT_DOMAIN: 1})
