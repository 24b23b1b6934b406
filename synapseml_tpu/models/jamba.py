"""``jamba``: the hybrid Mamba-1 / attention decoder of AI21-Jamba2-3B,
generation included, as ONE ONNX graph for the zoo.

Layer ``i``: ``x <- x + mixer_i(RMSNorm(x))``, ``x <- x + W_down(silu(W_gate
u) * (W_up u))`` with ``u = RMSNorm(x)`` (``num_experts`` 1: every
feed-forward is the dense gated one, never a router). ``mixer_i`` is causal
attention where ``i % attn_period == attn_offset`` (``heads`` query heads on
``kv_heads`` key-value heads, no bias and NO positional term of any kind)
and a Mamba-1 mixer everywhere else, over ``u [N, S, hidden]``:

1. ``[x, z] = u W_in``, each ``[N, S, d]``, ``d = expand x hidden``;
2. ``x = silu(conv(x))``: a depthwise causal convolution over positions,
   ``conv_kernel`` wide, with bias;
3. ``[dt_r, B, C] = x W_x`` (``dt_rank``, ``state``, ``state`` wide), each
   through an RMSNorm of its own;
4. ``delta = softplus(dt_r W_dt + b_dt)``; 5. ``A = -exp(A_log)``; for every
   position in order ``s <- exp(delta A) s + delta B x``, ``y = C s + D x``;
6. ``out = (y * silu(z)) W_out``.

Steps 4's bias and softplus, 5 and 6's gate are ONE node of a custom domain,
``synapseml_tpu::SelectiveScan(u, delta, A, B, C, D, z, delta_bias[,
state_in]) -> (out, state_out)``: what an exporter of the modelling code's
kernel path (``selective_scan_fn``) would write. After the last layer an
RMSNorm; the head is the embedding's transpose (``tie_word_embeddings``).

The graph generates, greedily. Input ``input_ids [N, S]``; outputs a row:
``tokens [generate]``, ``chosen_logprob [generate]`` (the log-softmax of the
chosen id) and ``pooled [hidden]`` (the mean, over the ``generate`` positions
the ids were chosen from, of the final norm's output).

- The prompt pass (nodes ``p_l#_...``) runs every position. A Mamba layer
  leaves the state after the last position (``SelectiveScan``'s second
  output, ``[N, state, d]`` float32, channels minor) and the last
  ``conv_kernel - 1`` rows of step 1's ``x``, positions major (``[conv_kernel
  - 1, N, d]``: one ``Transpose`` a call); an attention layer its keys
  and values, padded once to a cache ``[N, S + generate, kv_heads x
  head_dim]``. The final norm and the head at the last position give id 0.
- ``Loop`` ``decode`` (``generate - 1`` trips; nodes ``d_l#_...``) embeds
  the last id, ONE position a row, and runs the same layers: the
  convolution as the published single step (the window ``[conv_kernel, N,
  d]`` of the kept rows and the new one times the taps, summed over its
  leading axis, in float32; the window's last ``conv_kernel - 1`` rows are
  the next pass's), ``SelectiveScan`` at one
  position from the carried state, ``TensorScatter`` of the position's key
  and value and ``Attention`` against the cache under a mask computed from
  the trip counter. It carries two kinds of state side by side: a Mamba
  layer's (state, convolution rows), REPLACED every pass, and an attention
  layer's (keys, values), written in place.

Everything else is a standard operator of opset 24. Bodies read the outer
graph's initializers: weights are named ``l#_...`` once and used by both
passes. Weights are seeded draws as ``nemotron_h``'s: matrices ``N(0,
1/fan_in)`` rounded to BFLOAT16 (the embedding too: it is the head's
matrix), norm weights 1, the convolution ``N(0, 1/conv_kernel)`` with a bias
``N(0, 0.02^2)``, ``b_dt`` the inverse softplus of a log-uniform step in
``[dt_min, dt_max]``; ``A_log[c, j] = log(j + 1)`` and ``D = 1`` (the family's
own initialisation) are FLOAT initializers whose numbers a bfloat16 holds
exactly, since the checkpoint is one.
"""

from __future__ import annotations

from types import SimpleNamespace as _Sizes
from typing import List

import numpy as np

from ..onnx.builder import make_graph, make_model, node, value_info
from ..onnx.wire import DataType, ModelProto
from .joyai_flash import _gated_ffn, _gated_weights, _greedy
from .nemotron_h import EXPERT_DOMAIN, _Weights
from .sdar_moe import _of_shape

__all__ = ["jamba"]

_FLOAT = DataType.FLOAT


def _is_attention(z: _Sizes, i: int) -> bool:
    return i % z.attn_period == z.attn_offset


def _mamba_weights(w: _Weights, z: _Sizes, p: str) -> None:
    h, d, n, r = z.hidden, z.d, z.state, z.dt_rank
    w.normal(p + "_in_w", (h, 2 * d), h ** -0.5)
    w.normal(p + "_conv_w", (d, 1, z.conv), z.conv ** -0.5)
    w.normal(p + "_conv_b", (d,), 0.02)
    w.normal(p + "_x_w", (d, r + 2 * n), d ** -0.5)
    w.full(p + "_dt_norm_w", (r,), 1.0)
    w.full(p + "_b_norm_w", (n,), 1.0)
    w.full(p + "_c_norm_w", (n,), 1.0)
    w.normal(p + "_dt_w", (r, d), r ** -0.5)

    def dt_bias(rng, scratch):
        # the inverse softplus of a log-uniform step
        dt = np.exp(rng.uniform(np.log(z.dt_min), np.log(z.dt_max),
                                scratch.size))
        scratch[...] = dt + np.log(-np.expm1(-dt))

    w.draw(p + "_dt_b", (d,), dt_bias)
    # float32 initializers of numbers a bfloat16 holds (module docstring)
    a_log = np.log(np.arange(1, n + 1, dtype=np.float32))
    w.store[p + "_a_log"] = np.tile(
        a_log.astype(w.bfloat16).astype(np.float32), (d, 1))
    w.store[p + "_d"] = np.ones((d,), np.float32)
    w.normal(p + "_out_w", (d, h), d ** -0.5)


def _attention_weights(w: _Weights, z: _Sizes, p: str) -> None:
    h, wide = z.hidden, z.heads * z.head_dim
    w.normal(p + "_q_w", (h, wide), h ** -0.5)
    w.normal(p + "_k_w", (h, z.kv_heads * z.head_dim), h ** -0.5)
    w.normal(p + "_v_w", (h, z.kv_heads * z.head_dim), h ** -0.5)
    w.normal(p + "_o_w", (wide, h), wide ** -0.5)


def _mamba(add, z: _Sizes, p: str, wp: str, u: str, conv_rows: str = None,
           state_in: str = None):
    """The Mamba mixer over ``u [N, s, hidden]``. The prompt pass (no
    ``conv_rows``) pads the convolution with zeros and starts the state from
    zero; a decode pass convolves ``conv_rows [conv - 1, N, d]`` and its one
    new row and starts from ``state_in``. Names the mix, the state after the
    last position and the rows the next pass's convolution reads."""
    add(node("MatMul", [u, wp + "_in_w"], [p + "_xz"], name=p + "_in_proj"))
    add(node("Split", [p + "_xz"], [p + "_x_raw", p + "_z"],
             name=p + "_in_split", axis=-1, num_outputs=2))
    if conv_rows is None:
        add(node("Slice", [p + "_x_raw", "conv_keep_from", "huge_1d",
                           "axes_1"], [p + "_conv_kept"],
                 name=p + "_conv_kept"))
        add(node("Transpose", [p + "_conv_kept"], [p + "_conv_rows"],
                 name=p + "_conv_rows", perm=[1, 0, 2]))
        add(node("Transpose", [p + "_x_raw"], [p + "_conv_in"],
                 name=p + "_conv_in", perm=[0, 2, 1]))
        add(node("Conv", [p + "_conv_in", wp + "_conv_w", wp + "_conv_b"],
                 [p + "_conv_t"], name=p + "_conv", group=z.d,
                 kernel_shape=[z.conv], pads=[z.conv - 1, 0]))
        add(node("Transpose", [p + "_conv_t"], [p + "_conv_out"],
                 name=p + "_conv_out", perm=[0, 2, 1]))
    else:
        # the published single step over a window [conv, N, d]: positions
        # lead, so every join, slice and product is of whole [N, d] rows
        add(node("Transpose", [p + "_x_raw"], [p + "_x_new"],
                 name=p + "_x_new", perm=[1, 0, 2]))
        add(node("Concat", [conv_rows, p + "_x_new"], [p + "_window"],
                 name=p + "_window", axis=0))
        add(node("Slice", [p + "_window", "index1", "huge_1d", "axes_0"],
                 [p + "_conv_rows"], name=p + "_conv_rows"))
        add(node("Cast", [p + "_window"], [p + "_window_f"],
                 name=p + "_window_f", to=_FLOAT))
        add(node("Mul", [p + "_window_f", wp + "_conv_taps"],
                 [p + "_conv_terms"], name=p + "_conv_terms"))
        add(node("ReduceSum", [p + "_conv_terms", "axes_0"],
                 [p + "_conv_sum"], name=p + "_conv_sum", keepdims=0))
        add(node("Add", [p + "_conv_sum", wp + "_conv_b_f"],
                 [p + "_conv_f"], name=p + "_conv_f"))
        add(node("Unsqueeze", [p + "_conv_f", "axes_1"], [p + "_conv_row"],
                 name=p + "_conv_row"))
        add(node("CastLike", [p + "_conv_row", p + "_x_raw"],
                 [p + "_conv_out"], name=p + "_conv_out"))
    add(node("Sigmoid", [p + "_conv_out"], [p + "_conv_s"],
             name=p + "_silu_s"))
    add(node("Mul", [p + "_conv_out", p + "_conv_s"], [p + "_x"],
             name=p + "_silu"))
    add(node("MatMul", [p + "_x", wp + "_x_w"], [p + "_dbc"],
             name=p + "_x_proj"))
    add(node("Split", [p + "_dbc", "dt_b_c"],
             [p + "_dt_raw", p + "_b_raw", p + "_c_raw"], name=p + "_x_split",
             axis=-1))
    for part in ("dt", "b", "c"):
        add(node("RMSNormalization", [f"{p}_{part}_raw",
                                      f"{wp}_{part}_norm_w"],
                 [f"{p}_{part}_n"], name=f"{p}_{part}_norm", axis=-1,
                 epsilon=z.eps))
    add(node("MatMul", [p + "_dt_n", wp + "_dt_w"], [p + "_delta"],
             name=p + "_dt_proj"))
    # B and C in float32 from here on, as the state they multiply
    add(node("Cast", [p + "_b_n"], [p + "_b"], name=p + "_b_f", to=_FLOAT))
    add(node("Cast", [p + "_c_n"], [p + "_c"], name=p + "_c_f", to=_FLOAT))
    add(node("SelectiveScan",
             [p + "_x", p + "_delta", wp + "_a", p + "_b", p + "_c",
              wp + "_d_f", p + "_z", wp + "_dt_b_f"]
             + ([state_in] if state_in else []),
             [p + "_y", p + "_state"], name=p + "_ssm", domain=EXPERT_DOMAIN,
             delta_softplus=1))
    add(node("MatMul", [p + "_y", wp + "_out_w"], [p + "_mix"],
             name=p + "_out_proj"))
    return p + "_mix", p + "_state", p + "_conv_rows"


def _projections(add, z: _Sizes, p: str, wp: str, u: str):
    for part in ("q", "k", "v"):
        add(node("MatMul", [u, f"{wp}_{part}_w"], [f"{p}_{part}"],
                 name=f"{p}_att_{part}"))
    return p + "_q", p + "_k", p + "_v"


def _block(nodes: List, z: _Sizes, c: str, i: int, x: str, mixer) -> str:
    """Layer ``i`` in pass ``c`` (``p`` or ``d``) over ``x``; ``mixer(p, wp,
    u)`` adds the pass's form of the layer's mixer and names its result."""
    p, wp = f"{c}_l{i}", f"l{i}"
    add = nodes.append
    add(node("RMSNormalization", [x, wp + "_norm_in_w"], [p + "_u"],
             name=p + "_norm_in", axis=-1, epsilon=z.eps))
    add(node("Add", [x, mixer(p, wp, p + "_u")], [p + "_mid"],
             name=p + "_res_mix"))
    add(node("RMSNormalization", [p + "_mid", wp + "_norm_post_w"],
             [p + "_u2"], name=p + "_norm_post", axis=-1, epsilon=z.eps))
    ffn = _gated_ffn(add, p + "_ffn", wp + "_ffn", p + "_u2")
    add(node("Add", [p + "_mid", ffn], [p + "_out"], name=p + "_res_ffn"))
    return p + "_out"


def _tied_head(add, c: str, final: str):
    """The embedding's transpose as the head over ``final [N, 1, hidden]``,
    then float32 logits and the greedy choice."""
    add(node("Einsum", [final, "tok_emb"], [c + "_logits"], name=c + "_head",
             equation="nsh,vh->nsv"))
    return _greedy(add, c)


def jamba(layers: int = 28, hidden: int = 2560, vocab: int = 65536,
          heads: int = 20, kv_heads: int = 1, head_dim: int = 128,
          attn_period: int = 14, attn_offset: int = 7, expand: int = 2,
          state: int = 16, dt_rank: int = 160, conv_kernel: int = 4,
          width: int = 8192, eps: float = 1e-6, dt_min: float = 1e-3,
          dt_max: float = 1e-1, generate: int = 128, seed: int = 0
          ) -> ModelProto:
    """The ``jamba`` decoder (module docstring); the defaults are
    AI21-Jamba2-3B as published, whole: 28 layers with attention at layers 7
    and 21, every width, the whole vocabulary, generating ``generate`` ids."""
    if generate < 2 or layers < 1:
        raise ValueError(f"generate {generate} is at least 2, layers "
                         f"{layers} at least 1")
    z = _Sizes(hidden=hidden, heads=heads, kv_heads=kv_heads,
               head_dim=head_dim, attn_period=attn_period,
               attn_offset=attn_offset, d=expand * hidden, state=state,
               dt_rank=dt_rank, conv=conv_kernel, eps=eps, dt_min=dt_min,
               dt_max=dt_max)
    w = _Weights(seed)
    w.normal("tok_emb", (vocab, hidden), hidden ** -0.5)
    for i in range(layers):
        p = f"l{i}"
        w.full(p + "_norm_in_w", (hidden,), 1.0)
        if _is_attention(z, i):
            _attention_weights(w, z, p)
        else:
            _mamba_weights(w, z, p)
        w.full(p + "_norm_post_w", (hidden,), 1.0)
        _gated_weights(w, p + "_ffn", hidden, width)
    w.full("norm_f_w", (hidden,), 1.0)
    for name, values in (
            ("zero", 0), ("one", 1), ("index0", [0]), ("index1", [1]),
            ("axes_0", [0]), ("axes_1", [1]), ("axes_last", [-1]),
            ("one_1d", [1]), ("generate_1d", [generate]),
            ("trips", generate - 1), ("huge_1d", [np.iinfo(np.int64).max]),
            ("conv_keep_from", [-(conv_kernel - 1)]),
            ("dt_b_c", [dt_rank, state, state]),
            ("cache_pad", [0, 0, 0, 0, generate, 0])):
        w.ints(name, values)

    nodes: List = []
    add = nodes.append
    mamba = [i for i in range(layers) if not _is_attention(z, i)]
    attention = [i for i in range(layers) if _is_attention(z, i)]
    # what both passes read of a Mamba layer, made once: A = -exp(A_log), D
    # and the step's bias in float32
    for i in mamba:
        p = f"l{i}"
        add(node("Cast", [p + "_a_log"], [p + "_a_log_f"],
                 name=p + "_a_log_f", to=_FLOAT))
        add(node("Exp", [p + "_a_log_f"], [p + "_a_exp"], name=p + "_a_exp"))
        add(node("Neg", [p + "_a_exp"], [p + "_a"], name=p + "_a"))
        add(node("Cast", [p + "_d"], [p + "_d_f"], name=p + "_d_f",
                 to=_FLOAT))
        add(node("Cast", [p + "_dt_b"], [p + "_dt_b_f"], name=p + "_dt_b_f",
                 to=_FLOAT))
        # the decode pass's convolution taps [conv, 1, d] and bias, float32
        add(node("Transpose", [p + "_conv_w"], [p + "_conv_taps_t"],
                 name=p + "_conv_taps_t", perm=[2, 1, 0]))
        add(node("Cast", [p + "_conv_taps_t"], [p + "_conv_taps"],
                 name=p + "_conv_taps", to=_FLOAT))
        add(node("Cast", [p + "_conv_b"], [p + "_conv_b_f"],
                 name=p + "_conv_b_f", to=_FLOAT))
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
        def mixer(p, wp, u):
            if not _is_attention(z, i):
                mix, *carried[i] = _mamba(add, z, p, wp, u)
                return mix
            q, k, v = _projections(add, z, p, wp, u)
            add(node("Attention", [q, k, v], [p + "_ctx"], name=p + "_att",
                     q_num_heads=z.heads, kv_num_heads=z.kv_heads,
                     is_causal=1))
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
    first_id, first_logprob = _tied_head(add, "p", "p_final")
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
    # a Mamba layer carries (state, convolution rows), replaced every pass;
    # an attention layer (keys, values), written in place
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
        def mixer(p, wp, u):
            if not _is_attention(z, i):
                state_in, conv_rows = d_carried[i]
                mix, *d_left[i] = _mamba(d_add, z, p, wp, u, conv_rows,
                                         state_in)
                return mix
            q, k, v = _projections(d_add, z, p, wp, u)
            d_left[i] = []
            for rows, cache in zip((k, v), d_carried[i]):
                d_add(node("TensorScatter", [cache, rows, "d_position_1d"],
                           [rows + "_cache"], name=rows + "_cache", axis=1))
                d_left[i].append(rows + "_cache")
            d_add(node("Attention", [q, *d_left[i], "d_visible"],
                       [p + "_ctx"], name=p + "_att", q_num_heads=z.heads,
                       kv_num_heads=z.kv_heads))
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
    new_id, new_logprob = _tied_head(d_add, "d", "d_final")
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
        nodes, f"jamba_{layers}l_h{hidden}_g{generate}",
        [value_info("input_ids", np.int64, ["N", "S"])],
        [value_info("tokens", np.int64, ["N", generate]),
         value_info("chosen_logprob", np.float32, ["N", generate]),
         value_info("pooled", np.float32, ["N", hidden])], w.store)
    return make_model(graph, opset=24, domains={EXPERT_DOMAIN: 1})
