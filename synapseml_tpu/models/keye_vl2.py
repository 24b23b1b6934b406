"""``keye_vl2``: the language model of Keye-VL-2.0-30B-A3B, the Qwen3-MoE
block with keys chosen by a learned indexer, generation included, as ONE
ONNX graph for the zoo.

Layer ``i`` over ``h [N, s, hidden]``, ``x = RMSNorm(h)``:

- attention: ``q = x W_q`` (``heads`` of ``head_dim``), ``k = x W_k``, ``v
  = x W_v`` (``kv_heads`` of ``head_dim``); a per-head RMSNorm on ``q`` and
  ``k``, then rotate-half rotary over the whole head at the position
  (M-RoPE's three sections take one position with text alone, so it is
  1-D rotary, ``rope_theta``);
- the indexer: ``qI = x W_qI`` (``index_heads`` of ``index_dim``), ``kI =
  LayerNorm(x W_kI)`` (one head of ``index_dim``), both rotated the same way
  over all ``index_dim``; ``w = x W_w`` times ``index_heads^-1/2
  index_dim^-1/2``;
- ``synapseml_tpu::SparseAttention``: ``I[t, s] = sum_j w[t, j] relu(qI[t,
  j] . kI[s])``, the ``topk`` keys of largest ``I`` (ties to the lower
  position, every key where fewer are visible) are one set for every head,
  and attention runs over those alone; ``h' = h + o W_o``;
- experts: ``y = RMSNorm(h')``, a softmax router over ``experts``, the
  ``top_k`` largest renormalised, gated experts ``(silu(y G_e) * (y U_e))
  D_e`` of ``expert_width`` (``decoder.softmax_experts``), no shared
  expert; ``h'' = h' + MoE(y)``.

After the last layer an RMSNorm and an untied head. The graph generates
greedily, as ``decoder.hybrid_decoder`` builds it with every layer this
attention: ``input_ids [N, S]`` in; ``tokens``, ``chosen_logprob``,
``pooled`` and ``prompt_pooled`` (the final norm's output over the
prompt's first ``topk`` positions, averaged: where every key is kept) out.
The prompt pass (nodes ``p_l#_...``) leaves three caches a layer, padded to
``S + generate`` positions: keys and values ``[N, L, kv_heads, head_dim]``
(a position a leading index, so the decode kernel's DMA takes one row) and
the index keys ``[N, L, index_dim]``. In ``Loop``
``decode`` (nodes ``d_l#_...``) ``TensorScatter`` writes a position's three
rows, and ``SparseAttention`` gets the caches and the position.

Weights are seeded draws as ``sdar_moe``'s: matrices ``N(0, 1/fan_in)``
rounded to BFLOAT16, the embedding ``N(0, 1)``, norm weights 1, the index
keys' LayerNorm bias 0.
"""

from __future__ import annotations

from functools import partial
from types import SimpleNamespace as _Sizes
from typing import List

import numpy as np

from ..onnx.builder import node, value_info
from ..onnx.wire import DataType, ModelProto
from .decoder import EXPERT_DOMAIN, Weights, cast_float, choose, \
    head_norm_rotary, hybrid_decoder, rotary_tables, softmax_experts

__all__ = ["keye_vl2"]

_FLOAT = DataType.FLOAT


def _layer_weights(w: Weights, z: _Sizes, p: str) -> None:
    h, hd, std = z.hidden, z.head_dim, z.hidden ** -0.5
    w.full(p + "_norm_in_w", (h,), 1.0)
    w.normal(p + "_q_w", (h, z.heads * hd), std)
    w.normal(p + "_k_w", (h, z.kv_heads * hd), std)
    w.normal(p + "_v_w", (h, z.kv_heads * hd), std)
    w.full(p + "_q_norm_w", (hd,), 1.0)
    w.full(p + "_k_norm_w", (hd,), 1.0)
    w.normal(p + "_o_w", (z.heads * hd, h), (z.heads * hd) ** -0.5)
    w.normal(p + "_index_q_w", (h, z.index_heads * z.index_dim), std)
    w.normal(p + "_index_k_w", (h, z.index_dim), std)
    w.full(p + "_index_k_norm_w", (z.index_dim,), 1.0)
    w.full(p + "_index_k_norm_b", (z.index_dim,), 0.0)
    w.normal(p + "_index_w_w", (h, z.index_heads), std)
    w.full(p + "_norm_post_w", (h,), 1.0)
    w.normal(p + "_router_w", (h, z.experts), std)
    w.normal(p + "_experts_gate", (z.experts, h, z.expert_width), std)
    w.normal(p + "_experts_up", (z.experts, h, z.expert_width), std)
    w.normal(p + "_experts_down", (z.experts, z.expert_width, h),
             z.expert_width ** -0.5)


def _setup(add, c: str, *, theta: float, head_dim: int,
           index_dim: int) -> None:
    """The rotary tables of the heads and of the index heads and the
    prompt's positions (``c`` ``p``), the decode pass's position ``[N, 1]``
    (``d``)."""
    if c == "d":
        add(node("Unsqueeze", ["d_position_1d", "axes_1"], ["d_positions"],
                 name="d_positions"))
        return
    rotary_tables(add, theta, head_dim)
    rotary_tables(add, theta, index_dim, "index_")
    add(node("Range", ["zero", "prompt_len", "one"], ["prompt_range"],
             name="prompt_range"))
    add(node("Unsqueeze", ["prompt_range", "axes_0"], ["prompt_row"],
             name="prompt_row"))
    add(node("Expand", ["prompt_row", "ids_shape"], ["prompt_positions"],
             name="prompt_positions"))


def _projections(add, z: _Sizes, p: str, wp: str, u: str):
    """Queries ``[N, s, heads x head_dim]``, then what a layer keeps: keys
    and values ``[N, s, kv_heads, head_dim]``, index keys ``[N, s,
    index_dim]``."""
    positions = "d_positions" if p.startswith("d_") else "prompt_positions"
    for part in ("q", "k", "v"):
        add(node("MatMul", [u, f"{wp}_{part}_w"], [f"{p}_{part}"],
                 name=f"{p}_att_{part}"))
    q = head_norm_rotary(add, p, wp, "q", z.heads, positions, z.eps)
    k = head_norm_rotary(add, p, wp, "k", z.kv_heads, positions, z.eps)
    for part, flat in (("k", k), ("v", p + "_v")):
        add(node("Reshape", [flat, "heads_k_shape"], [f"{p}_{part}_rows"],
                 name=f"{p}_{part}_rows"))
    add(node("MatMul", [u, wp + "_index_k_w"], [p + "_index_k_raw"],
             name=p + "_index_k"))
    add(node("LayerNormalization", [p + "_index_k_raw",
                                    wp + "_index_k_norm_w",
                                    wp + "_index_k_norm_b"],
             [p + "_index_k_n"], name=p + "_index_k_norm", axis=-1,
             epsilon=z.index_eps))
    add(node("RotaryEmbedding", [p + "_index_k_n", "index_rope_cos",
                                 "index_rope_sin", positions],
             [p + "_index_k_rows"], name=p + "_index_rope_k", num_heads=1))
    return q, p + "_k_rows", p + "_v_rows", p + "_index_k_rows"


def _attend(add, z: _Sizes, p: str, wp: str, u: str, q: str, kept: List[str],
            start: str) -> str:
    """The index queries and weights over ``u``, then ``SparseAttention``
    over ``kept`` (the layer's rows, or its caches and ``start``)."""
    positions = "d_positions" if start else "prompt_positions"
    add(node("MatMul", [u, wp + "_index_q_w"], [p + "_index_q_raw"],
             name=p + "_index_q"))
    add(node("RotaryEmbedding", [p + "_index_q_raw", "index_rope_cos",
                                 "index_rope_sin", positions],
             [p + "_index_q_r"], name=p + "_index_rope_q",
             num_heads=z.index_heads))
    add(node("MatMul", [u, wp + "_index_w_w"], [p + "_index_w_raw"],
             name=p + "_index_w"))
    add(node("Cast", [p + "_index_w_raw"], [p + "_index_w_f"],
             name=p + "_index_w_f", to=_FLOAT))
    add(node("Mul", [p + "_index_w_f", "index_scale_f"],
             [p + "_index_weights"], name=p + "_index_weights"))
    add(node("SparseAttention",
             [q, kept[0], kept[1], p + "_index_q_r", kept[2],
              p + "_index_weights"] + ([start] if start else []),
             [p + "_ctx"], name=p + "_att", domain=EXPERT_DOMAIN,
             q_num_heads=z.heads, kv_num_heads=z.kv_heads, topk=z.topk))
    return p + "_ctx"


def _block(nodes: List, z: _Sizes, c: str, i: int, x: str, mixer) -> str:
    """Layer ``i`` in pass ``c`` (``p`` or ``d``) over ``x``: the Qwen3-MoE
    block, ``mixer(p, wp, u)`` its attention."""
    p, wp = f"{c}_l{i}", f"l{i}"
    add = nodes.append
    add(node("RMSNormalization", [x, wp + "_norm_in_w"], [p + "_u"],
             name=p + "_norm_in", axis=-1, epsilon=z.eps))
    add(node("Add", [x, mixer(p, wp, p + "_u")], [p + "_mid"],
             name=p + "_res_att"))
    add(node("RMSNormalization", [p + "_mid", wp + "_norm_post_w"],
             [p + "_v2"], name=p + "_norm_post", axis=-1, epsilon=z.eps))
    moe = softmax_experts(add, p, wp, p + "_v2", z.experts)
    add(node("Add", [p + "_mid", moe], [p + "_out"], name=p + "_res_moe"))
    return p + "_out"


def _prompt_pooled(add, x: str, *, hidden: int, eps: float) -> List:
    """``prompt_pooled [N, hidden]``: the mean of the final norm's output
    over the prompt's first ``topk`` positions (all of them in a shorter
    prompt), where a query keeps every key it sees, so that no rounding of
    the index scores moves it."""
    add(node("Slice", [x, "index0", "topk_1d", "axes_1"], ["p_dense"],
             name="p_dense"))
    add(node("RMSNormalization", ["p_dense", "norm_f_w"], ["p_dense_final"],
             name="p_dense_norm_f", axis=-1, epsilon=eps))
    add(node("Cast", ["p_dense_final"], ["p_dense_final_f"],
             name="p_dense_final_f", to=_FLOAT))
    add(node("ReduceMean", ["p_dense_final_f", "axes_1"], ["prompt_pooled"],
             name="prompt_pooled", keepdims=0))
    return [value_info("prompt_pooled", np.float32, ["N", hidden])]


def keye_vl2(layers: int = 48, hidden: int = 2048, vocab: int = 151936,
             heads: int = 32, kv_heads: int = 4, head_dim: int = 128,
             experts: int = 128, top_k: int = 8, expert_width: int = 768,
             index_heads: int = 16, index_dim: int = 64, topk: int = 2048,
             rope_theta: float = 1e7, eps: float = 1e-6,
             index_eps: float = 1e-6, generate: int = 128, seed: int = 0
             ) -> ModelProto:
    """The ``keye_vl2`` language model (module docstring); the defaults are
    Keye-VL-2.0-30B-A3B's as published, generating ``generate`` ids."""
    if generate < 2 or layers < 1 or head_dim % 2 or index_dim % 2:
        raise ValueError(
            f"generate {generate} is at least 2, layers {layers} at least 1, "
            f"head_dim {head_dim} and index_dim {index_dim} even")
    z = _Sizes(hidden=hidden, heads=heads, kv_heads=kv_heads,
               head_dim=head_dim, experts=experts, expert_width=expert_width,
               index_heads=index_heads, index_dim=index_dim, topk=topk,
               eps=eps, index_eps=index_eps)
    w = Weights(seed)
    w.normal("tok_emb", (vocab, hidden), 1.0)
    for i in range(layers):
        _layer_weights(w, z, f"l{i}")
    w.full("norm_f_w", (hidden,), 1.0)
    w.normal("lm_head", (hidden, vocab), hidden ** -0.5)
    w.store["index_scale"] = np.asarray(
        (index_heads * index_dim) ** -0.5, np.float32)

    nodes: List = []
    cast_float(nodes, "index_scale", "index_scale_f")
    return hybrid_decoder(
        w, nodes, z, name=f"keye_vl2_{layers}l_h{hidden}_g{generate}",
        attention=[True] * layers, hidden=hidden, generate=generate,
        eps=eps,
        ints=(("topk_1d", [topk]), ("top_k", [top_k]),
              ("heads_q_shape", [0, 0, heads, head_dim]),
              ("heads_k_shape", [0, 0, kv_heads, head_dim]),
              ("flat_shape", [0, 0, -1]),
              ("cache_pad_heads", [0, 0, 0, 0, 0, generate, 0, 0])),
        mixer=None, projections=_projections, block=_block, head=choose,
        setup=partial(_setup, theta=rope_theta, head_dim=head_dim,
                      index_dim=index_dim),
        attend=_attend,
        prompt_outputs=partial(_prompt_pooled, hidden=hidden, eps=eps),
        cache_pads=("cache_pad_heads", "cache_pad_heads", "cache_pad"))
