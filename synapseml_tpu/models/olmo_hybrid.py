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

The graph generates greedily, as ``decoder.hybrid_decoder`` builds it:
``input_ids [N, S]`` in; ``tokens``, ``chosen_logprob`` and ``pooled`` out.
In the prompt pass (nodes ``p_l#_...``) a delta rule layer leaves the state
after the last position (``GatedDeltaRule``'s second output, ``[N, dk, H x
dv]`` float32) and the last ``conv_kernel - 1`` rows of step 1's
projection, positions major (``[conv_kernel - 1, N, 2 H dk + H dv]``); an
attention layer its normed keys and its values, padded to a cache ``[N, S +
generate, heads x head_dim]``. In ``Loop`` ``decode`` (nodes ``d_l#_...``)
the convolution is the published single step over the window
``[conv_kernel, N, channels]`` of the kept rows and the new one, in
float32, and ``GatedDeltaRule`` runs one position from the carried state.

Everything else is a standard operator of opset 24. Weights are seeded
draws as ``jamba``'s: matrices ``N(0, 1/fan_in)`` rounded to BFLOAT16, norm
weights 1, the convolution ``N(0, 1/conv_kernel)``, ``b_dt`` the inverse
softplus of a log-uniform step in ``[dt_min, dt_max]``; ``A_log`` the log of
a uniform draw in ``(0, 16]`` a head (the family's own initialisation), a
FLOAT initializer of numbers a bfloat16 holds, since the checkpoint is one.
"""

from __future__ import annotations

from types import SimpleNamespace as _Sizes
from typing import List

import numpy as np

from ..onnx.builder import node
from ..onnx.wire import DataType, ModelProto
from .decoder import EXPERT_DOMAIN, Weights, cast_float, causal_conv, \
    choose, gated_ffn, gated_weights, hybrid_decoder, step_bias

__all__ = ["olmo_hybrid"]

_FLOAT = DataType.FLOAT


def _delta_weights(w: Weights, z: _Sizes, p: str) -> None:
    h, lh = z.hidden, z.linear_heads
    w.normal(p + "_qkv_w", (h, z.channels), h ** -0.5)
    w.normal(p + "_conv_w", (z.channels, 1, z.conv), z.conv ** -0.5)
    w.normal(p + "_ab_w", (h, 2 * lh), h ** -0.5)

    w.draw(p + "_dt_b", (lh,), step_bias(z.dt_min, z.dt_max))
    # a float32 initializer of numbers a bfloat16 holds (module docstring)
    a = np.random.default_rng((w.seed, len(w.store))).uniform(0, 16, lh)
    w.store[p + "_a_log"] = np.log(np.maximum(a, 1e-4)).astype(
        w.bfloat16).astype(np.float32)
    w.normal(p + "_gate_w", (h, lh * z.value_dim), h ** -0.5)
    w.full(p + "_o_norm_w", (z.value_dim,), 1.0)
    w.normal(p + "_out_w", (lh * z.value_dim, h), (lh * z.value_dim) ** -0.5)


def _attention_weights(w: Weights, z: _Sizes, p: str) -> None:
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
    conv = causal_conv(add, p, wp, "qkv", z.channels, z.conv,
                       conv_rows=conv_rows)
    qkv = _silu(add, p + "_qkv", conv)
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
    ffn = gated_ffn(add, p + "_ffn", wp + "_ffn", p + "_mid")
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
               channels=linear_heads * (2 * key_dim + value_dim), eps=eps,
               dt_min=dt_min, dt_max=dt_max)
    attention = [i % attn_period == attn_offset for i in range(layers)]
    w = Weights(seed)
    w.normal("tok_emb", (vocab, hidden), hidden ** -0.5)
    for i in range(layers):
        p = f"l{i}"
        if attention[i]:
            _attention_weights(w, z, p)
        else:
            _delta_weights(w, z, p)
        w.full(p + "_norm_mix_w", (hidden,), 1.0)
        gated_weights(w, p + "_ffn", hidden, width)
        w.full(p + "_norm_ffn_w", (hidden,), 1.0)
    w.full("norm_f_w", (hidden,), 1.0)
    w.normal("lm_head", (hidden, vocab), hidden ** -0.5)
    w.store["two"] = np.asarray(2.0, np.float32)

    nodes: List = []
    add = nodes.append
    cast_float(nodes, "two", "two_f")
    # what both passes read of a delta rule layer, made once: -exp(A_log)
    # and the step's bias in float32, the decode pass's taps [conv, 1, C]
    for p in (f"l{i}" for i in range(layers) if not attention[i]):
        cast_float(nodes, p + "_a_log", p + "_a_log_f")
        add(node("Exp", [p + "_a_log_f"], [p + "_a_exp"], name=p + "_a_exp"))
        add(node("Neg", [p + "_a_exp"], [p + "_a"], name=p + "_a"))
        cast_float(nodes, p + "_dt_b", p + "_dt_b_f")
        add(node("Transpose", [p + "_conv_w"], [p + "_conv_taps_t"],
                 name=p + "_conv_taps_t", perm=[2, 1, 0]))
        cast_float(nodes, p + "_conv_taps_t", p + "_conv_taps")
    lh, dk, dv = linear_heads, key_dim, value_dim
    return hybrid_decoder(
        w, nodes, z, name=f"olmo_hybrid_{layers}l_h{hidden}_g{generate}",
        attention=attention, heads=heads, kv_heads=heads, hidden=hidden,
        generate=generate, eps=eps,
        ints=(("huge_1d", [np.iinfo(np.int64).max]),
              ("conv_keep_from", [-(conv_kernel - 1)]),
              ("qkv_split", [lh * dk, lh * dk, lh * dv]),
              ("ab_split", [lh, lh]), ("qk_heads", [0, 0, lh, dk]),
              ("v_heads", [0, 0, lh, dv]), ("v_merged", [0, 0, lh * dv])),
        mixer=_delta, projections=_projections, block=_block, head=choose)
