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

The graph generates greedily, as ``decoder.hybrid_decoder`` builds it:
``input_ids [N, S]`` in; ``tokens``, ``chosen_logprob`` and ``pooled`` out.
In the prompt pass (nodes ``p_l#_...``) a Mamba layer leaves the state after
the last position (``SelectiveScan``'s second output, ``[N, state, d]``
float32, channels minor) and the last ``conv_kernel - 1`` rows of step 1's
``x``, positions major (``[conv_kernel - 1, N, d]``: one ``Transpose`` a
call); an attention layer its keys and values, padded to a cache ``[N, S +
generate, kv_heads x head_dim]``. In ``Loop`` ``decode`` (nodes
``d_l#_...``) the convolution is the published single step (the window
``[conv_kernel, N, d]`` of the kept rows and the new one times the taps,
summed over its leading axis, in float32; the window's last ``conv_kernel -
1`` rows are the next pass's) and ``SelectiveScan`` runs one position from
the carried state.

Everything else is a standard operator of opset 24. Weights are seeded
draws as ``nemotron_h``'s: matrices ``N(0, 1/fan_in)`` rounded to BFLOAT16
(the embedding too: it is the head's matrix), norm weights 1, the
convolution ``N(0, 1/conv_kernel)`` with a bias ``N(0, 0.02^2)``, ``b_dt``
the inverse softplus of a log-uniform step in ``[dt_min, dt_max]``;
``A_log[c, j] = log(j + 1)`` and ``D = 1`` (the family's own
initialisation) are FLOAT initializers whose numbers a bfloat16 holds
exactly, since the checkpoint is one.
"""

from __future__ import annotations

from functools import partial
from types import SimpleNamespace as _Sizes
from typing import List

import numpy as np

from ..onnx.builder import node
from ..onnx.wire import DataType, ModelProto
from .decoder import EXPERT_DOMAIN, Weights, cast_float, causal_conv, \
    gated_ffn, gated_weights, greedy, grouped_attention, hybrid_decoder, \
    step_bias

__all__ = ["jamba"]

_FLOAT = DataType.FLOAT


def _mamba_weights(w: Weights, z: _Sizes, p: str) -> None:
    h, d, n, r = z.hidden, z.d, z.state, z.dt_rank
    w.normal(p + "_in_w", (h, 2 * d), h ** -0.5)
    w.normal(p + "_conv_w", (d, 1, z.conv), z.conv ** -0.5)
    w.normal(p + "_conv_b", (d,), 0.02)
    w.normal(p + "_x_w", (d, r + 2 * n), d ** -0.5)
    w.full(p + "_dt_norm_w", (r,), 1.0)
    w.full(p + "_b_norm_w", (n,), 1.0)
    w.full(p + "_c_norm_w", (n,), 1.0)
    w.normal(p + "_dt_w", (r, d), r ** -0.5)

    w.draw(p + "_dt_b", (d,), step_bias(z.dt_min, z.dt_max))
    # float32 initializers of numbers a bfloat16 holds (module docstring)
    a_log = np.log(np.arange(1, n + 1, dtype=np.float32))
    w.store[p + "_a_log"] = np.tile(
        a_log.astype(w.bfloat16).astype(np.float32), (d, 1))
    w.store[p + "_d"] = np.ones((d,), np.float32)
    w.normal(p + "_out_w", (d, h), d ** -0.5)


def _attention_weights(w: Weights, z: _Sizes, p: str) -> None:
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
    conv = causal_conv(add, p, wp, "x", z.d, z.conv, bias=True,
                       conv_rows=conv_rows)
    add(node("Sigmoid", [conv], [p + "_conv_s"], name=p + "_silu_s"))
    add(node("Mul", [conv, p + "_conv_s"], [p + "_x"], name=p + "_silu"))
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
    ffn = gated_ffn(add, p + "_ffn", wp + "_ffn", p + "_u2")
    add(node("Add", [p + "_mid", ffn], [p + "_out"], name=p + "_res_ffn"))
    return p + "_out"


def _tied_head(add, c: str, final: str):
    """The embedding's transpose as the head over ``final [N, 1, hidden]``,
    then float32 logits and the greedy choice."""
    add(node("Einsum", [final, "tok_emb"], [c + "_logits"], name=c + "_head",
             equation="nsh,vh->nsv"))
    return greedy(add, c)


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
               head_dim=head_dim, d=expand * hidden, state=state,
               dt_rank=dt_rank, conv=conv_kernel, eps=eps, dt_min=dt_min,
               dt_max=dt_max)
    attention = [i % attn_period == attn_offset for i in range(layers)]
    w = Weights(seed)
    w.normal("tok_emb", (vocab, hidden), hidden ** -0.5)
    for i in range(layers):
        p = f"l{i}"
        w.full(p + "_norm_in_w", (hidden,), 1.0)
        if attention[i]:
            _attention_weights(w, z, p)
        else:
            _mamba_weights(w, z, p)
        w.full(p + "_norm_post_w", (hidden,), 1.0)
        gated_weights(w, p + "_ffn", hidden, width)
    w.full("norm_f_w", (hidden,), 1.0)

    nodes: List = []
    add = nodes.append
    # what both passes read of a Mamba layer, made once: A = -exp(A_log), D
    # and the step's bias in float32
    for p in (f"l{i}" for i in range(layers) if not attention[i]):
        cast_float(nodes, p + "_a_log", p + "_a_log_f")
        add(node("Exp", [p + "_a_log_f"], [p + "_a_exp"], name=p + "_a_exp"))
        add(node("Neg", [p + "_a_exp"], [p + "_a"], name=p + "_a"))
        cast_float(nodes, p + "_d", p + "_d_f")
        cast_float(nodes, p + "_dt_b", p + "_dt_b_f")
        # the decode pass's convolution taps [conv, 1, d] and bias, float32
        add(node("Transpose", [p + "_conv_w"], [p + "_conv_taps_t"],
                 name=p + "_conv_taps_t", perm=[2, 1, 0]))
        cast_float(nodes, p + "_conv_taps_t", p + "_conv_taps")
        cast_float(nodes, p + "_conv_b", p + "_conv_b_f")
    return hybrid_decoder(
        w, nodes, z, name=f"jamba_{layers}l_h{hidden}_g{generate}",
        attention=attention, hidden=hidden, generate=generate, eps=eps,
        ints=(("huge_1d", [np.iinfo(np.int64).max]),
              ("conv_keep_from", [-(conv_kernel - 1)]),
              ("dt_b_c", [dt_rank, state, state])),
        mixer=_mamba, projections=_projections, block=_block, head=_tied_head,
        attend=partial(grouped_attention, heads=heads, kv_heads=kv_heads))
