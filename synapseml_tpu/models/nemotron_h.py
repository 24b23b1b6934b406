"""``nemotron_h``: the hybrid Mamba-2 / sparse-expert / grouped-query decoder of
NVIDIA-Nemotron-3-Nano-30B-A3B, as an ONNX graph for the zoo.

Every block is ONE mixer behind a pre-norm and a residual, ``x <- x +
mixer(RMSNorm(x))``, chosen by a letter of the pattern string: ``M`` Mamba-2,
``E`` routed experts plus a shared one, ``*`` causal grouped-query attention
(no positional term: this family's attention carries none). After the last
block ``norm_f``; the graph returns ``logits`` of the LAST position and
``pooled``, the mean of ``norm_f``'s output over positions.

The graph is what an exporter of the published modelling code would write, in
standard operators of opset 23 (``Attention``, ``RMSNormalization``,
``Einsum``, ``CumSum``, ``Trilu``, grouped ``Conv``, ``TopK``), plus one node
of a custom domain, ``synapseml_tpu::ExpertFFN``, for the routed products. The
Mamba recurrence is in its chunked form (state-space duality): inside a chunk
the lower-triangular decay ``L = exp(segsum(dt A))`` weighs ``C Bᵀ``; each
chunk leaves a state; the recurrence BETWEEN chunks is one product with the
chunks' strictly-lower decay matrix (the state entering the first chunk is
zero, so the published padded ``(chunks+1)²`` matrix loses its first column
and last row); the entering state reaches a chunk's outputs through ``C``. No
``Loop``, no scan. ``dt``, ``A``, the decays and the states are float32
through the graph's own ``Cast(to=FLOAT)``, as the published code keeps them,
whatever the executor's policy; ``CastLike`` returns to the stream's type.

A chip of a deployment holds a SHARE of the model: ``experts_held`` of the
router's ``experts`` starting at ``first_expert`` (a pick of an expert held
elsewhere adds nothing here), and ``vocab`` rows of the embedding and head.
The router is always as wide as published.

Weights are seeded draws, a tensor at a time in float32 on a thread pool and
rounded to BFLOAT16 initializers (the source is a bfloat16 checkpoint).
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ..onnx.builder import make_graph, make_model, node, value_info
from ..onnx.wire import ModelProto
from .decoder import EXPERT_DOMAIN, Weights, cast_float, router

__all__ = ["nemotron_h"]


def _rms_norm(nodes, w: Weights, name: str, x: str, size: int, eps: float):
    weight = w.full(name + "_w", (size,), 1.0)
    nodes.append(node("RMSNormalization", [x, weight], [name], name=name,
                      axis=-1, epsilon=eps))
    return name


def _mamba(nodes, w: Weights, p: str, u: str, hidden: int, heads: int,
           head_dim: int, groups: int, state: int, conv_kernel: int,
           chunk: int, eps: float, dt_limits: Tuple[float, float, float]):
    """Mamba-2 mixer; ``p`` prefixes every name, ``u`` is the normed input
    ``[N, S, hidden]``. Heads are written ``(g, r)``: group and head within
    it, since head ``h`` reads ``B``/``C`` of group ``h // (heads/groups)``."""
    inner, per = heads * head_dim, heads // groups
    bc = groups * state
    conv_dim = inner + 2 * bc
    add = nodes.append

    add(node("MatMul", [u, w.normal(p + "_in_w", (hidden, inner + conv_dim
                                                  + heads), hidden ** -0.5)],
             [p + "_in"], name=p + "_in_proj"))
    add(node("Split", [p + "_in", w.ints(p + "_in_split",
                                         [inner, conv_dim, heads])],
             [p + "_z", p + "_xbc", p + "_dt_raw"], name=p + "_in_split",
             axis=-1))
    # depthwise causal convolution over positions, then silu
    add(node("Transpose", [p + "_xbc"], [p + "_xbc_t"], name=p + "_conv_in",
             perm=[0, 2, 1]))
    add(node("Conv", [p + "_xbc_t",
                      w.normal(p + "_conv_w", (conv_dim, 1, conv_kernel),
                               conv_kernel ** -0.5),
                      w.normal(p + "_conv_b", (conv_dim,), 0.02)],
             [p + "_conv_t"], name=p + "_conv", group=conv_dim,
             kernel_shape=[conv_kernel], pads=[conv_kernel - 1, 0]))
    add(node("Transpose", [p + "_conv_t"], [p + "_conv"],
             name=p + "_conv_out", perm=[0, 2, 1]))
    add(node("Sigmoid", [p + "_conv"], [p + "_conv_sig"], name=p + "_silu_s"))
    add(node("Mul", [p + "_conv", p + "_conv_sig"], [p + "_act"],
             name=p + "_silu"))
    add(node("Split", [p + "_act", w.ints(p + "_act_split", [inner, bc, bc])],
             [p + "_x", p + "_b", p + "_c"], name=p + "_act_split", axis=-1))

    # float32 from here to the gate: the step, the decays, the states
    def dt_bias(rng, scratch):
        # the inverse softplus of a log-uniform step, floored
        lo, hi, floor = dt_limits
        dt = np.exp(rng.uniform(np.log(lo), np.log(hi), scratch.size))
        dt = np.maximum(dt, floor)
        scratch[...] = dt + np.log(-np.expm1(-dt))

    def a_log(rng, scratch):
        scratch[...] = np.log(rng.uniform(1.0, 16.0, scratch.size))

    w.draw(p + "_dt_bias", (heads,), dt_bias)
    w.draw(p + "_a_log", (heads,), a_log)
    w.full(p + "_d", (heads,), 1.0)
    add(node("Add", [cast_float(nodes, p + "_dt_raw", p + "_dt_f"),
                     cast_float(nodes, p + "_dt_bias", p + "_dt_bias_f")],
             [p + "_dt_b"], name=p + "_dt_add"))
    add(node("Softplus", [p + "_dt_b"], [p + "_dt"], name=p + "_dt"))
    add(node("Exp", [cast_float(nodes, p + "_a_log", p + "_a_log_f")],
             [p + "_a_exp"], name=p + "_a_exp"))
    add(node("Neg", [p + "_a_exp"], [p + "_a"], name=p + "_a"))
    add(node("Mul", [p + "_dt", p + "_a"], [p + "_da"], name=p + "_da"))

    # chunks: positions S -> (c, l), heads -> (g, r)
    x_shape = w.ints(p + "_x_shape", [0, -1, chunk, groups, per, head_dim])
    h_shape = w.ints(p + "_h_shape", [0, -1, chunk, groups, per])
    bc_shape = w.ints(p + "_bc_shape", [0, -1, chunk, groups, state])
    add(node("Reshape", [cast_float(nodes, p + "_x", p + "_x_f"), x_shape],
             [p + "_xc"], name=p + "_x_chunks"))
    add(node("Reshape", [cast_float(nodes, p + "_b", p + "_b_f"), bc_shape],
             [p + "_bchunks"], name=p + "_b_chunks"))
    add(node("Reshape", [cast_float(nodes, p + "_c", p + "_c_f"), bc_shape],
             [p + "_cchunks"], name=p + "_c_chunks"))
    add(node("Reshape", [p + "_dt", h_shape], [p + "_dtc"],
             name=p + "_dt_chunks"))
    add(node("Reshape", [p + "_da", h_shape], [p + "_dac"],
             name=p + "_da_chunks"))
    add(node("Mul", [p + "_xc", _unsqueeze(nodes, w, p + "_dtc", -1)],
             [p + "_xdt"], name=p + "_x_dt"))
    # cs[b,c,l,g,r]: dt A summed from the chunk's start through l
    add(node("CumSum", [p + "_dac", w.ints(p + "_axis2", 2)], [p + "_cs"],
             name=p + "_cs"))
    add(node("Transpose", [p + "_cs"], [p + "_cs_t"], name=p + "_cs_t",
             perm=[0, 1, 3, 4, 2]))  # [b,c,g,r,l]

    # inside a chunk: Y_diag = (L o C Bᵀ) (dt x), L[l,s] = exp(cs_l - cs_s)
    add(node("Sub", [_unsqueeze(nodes, w, p + "_cs_t", -1),
                     _unsqueeze(nodes, w, p + "_cs_t", -2)],
             [p + "_seg"], name=p + "_ssd_seg"))
    add(node("Exp", [p + "_seg"], [p + "_seg_exp"], name=p + "_ssd_exp"))
    add(node("Trilu", [p + "_seg_exp"], [p + "_l"], name=p + "_ssd_l",
             upper=0))
    add(node("Einsum", [p + "_cchunks", p + "_bchunks"], [p + "_cb"],
             name=p + "_ssd_cb", equation="bclgn,bcsgn->bcgls"))
    add(node("Mul", [p + "_l", _unsqueeze(nodes, w, p + "_cb", 3)],
             [p + "_m"], name=p + "_ssd_m"))
    add(node("Einsum", [p + "_m", p + "_xdt"], [p + "_y_diag"],
             name=p + "_ssd_diag", equation="bcgrls,bcsgrp->bclgrp"))

    # the state a chunk leaves: sum over l of exp(cs_last - cs_l) B_l (dt x)_l
    last = w.ints(p + "_last", [chunk - 1])
    add(node("Gather", [p + "_cs", last], [p + "_cs_last"],
             name=p + "_cs_last", axis=2))  # [b,c,1,g,r]
    add(node("Sub", [p + "_cs_last", p + "_cs"], [p + "_to_end"],
             name=p + "_to_end"))
    add(node("Exp", [p + "_to_end"], [p + "_decay_end"],
             name=p + "_decay_end"))
    add(node("Mul", [p + "_xdt", _unsqueeze(nodes, w, p + "_decay_end", -1)],
             [p + "_xdt_end"], name=p + "_x_dt_end"))
    add(node("Einsum", [p + "_bchunks", p + "_xdt_end"], [p + "_states"],
             name=p + "_ssd_state", equation="bclgn,bclgrp->bcgrpn"))

    # between chunks: the state entering chunk z is the sum over c < z of
    # exp(a_{c+1} + .. + a_{z-1}) states_c, with a_c the chunk's summed dt A
    add(node("Squeeze", [p + "_cs_last", w.ints(p + "_axes2", [2])],
             [p + "_a_chunk"], name=p + "_a_chunk"))  # [b,c,g,r]
    add(node("CumSum", [p + "_a_chunk", w.ints(p + "_axis1", 1)],
             [p + "_a_incl"], name=p + "_a_incl"))
    add(node("Sub", [p + "_a_incl", p + "_a_chunk"], [p + "_a_excl"],
             name=p + "_a_excl"))
    add(node("Transpose", [p + "_a_incl"], [p + "_a_incl_t"],
             name=p + "_a_incl_t", perm=[0, 2, 3, 1]))  # [b,g,r,c]
    add(node("Transpose", [p + "_a_excl"], [p + "_a_excl_t"],
             name=p + "_a_excl_t", perm=[0, 2, 3, 1]))
    add(node("Sub", [_unsqueeze(nodes, w, p + "_a_excl_t", -1),
                     _unsqueeze(nodes, w, p + "_a_incl_t", -2)],
             [p + "_chunk_seg"], name=p + "_chunk_seg"))  # [b,g,r,z,c]
    add(node("Exp", [p + "_chunk_seg"], [p + "_chunk_exp"],
             name=p + "_chunk_exp"))
    add(node("Trilu", [p + "_chunk_exp", w.ints(p + "_minus1", -1)],
             [p + "_chunk_decay"], name=p + "_chunk_decay", upper=0))
    add(node("Einsum", [p + "_chunk_decay", p + "_states"], [p + "_prev"],
             name=p + "_ssd_carry", equation="bgrzc,bcgrpn->bzgrpn"))

    # the entering state read through C, decayed to each position
    add(node("Einsum", [p + "_cchunks", p + "_prev"], [p + "_y_prev"],
             name=p + "_ssd_off", equation="bclgn,bcgrpn->bclgrp"))
    add(node("Exp", [p + "_cs"], [p + "_decay_in"], name=p + "_decay_in"))
    add(node("Mul", [p + "_y_prev",
                     _unsqueeze(nodes, w, p + "_decay_in", -1)],
             [p + "_y_off"], name=p + "_ssd_off_decay"))
    add(node("Add", [p + "_y_diag", p + "_y_off"], [p + "_y_ssd"],
             name=p + "_ssd_sum"))
    d_shape = w.ints(p + "_d_shape", [groups, per, 1])
    add(node("Reshape", [cast_float(nodes, p + "_d", p + "_d_f"), d_shape],
             [p + "_d_r"], name=p + "_d_r"))
    add(node("Mul", [p + "_xc", p + "_d_r"], [p + "_skip"], name=p + "_skip"))
    add(node("Add", [p + "_y_ssd", p + "_skip"], [p + "_yc"], name=p + "_y"))

    # gate BEFORE the norm, the norm over groups of inner / groups
    grouped = w.ints(p + "_grouped", [0, -1, groups, inner // groups])
    flat = w.ints(p + "_flat", [0, -1, inner])
    add(node("Reshape", [p + "_yc", flat], [p + "_y_flat"],
             name=p + "_y_flat"))
    add(node("Sigmoid", [p + "_z"], [p + "_z_sig"], name=p + "_gate_s"))
    add(node("Mul", [p + "_z", p + "_z_sig"], [p + "_z_silu"],
             name=p + "_gate_silu"))
    add(node("Mul", [p + "_y_flat", p + "_z_silu"], [p + "_gated"],
             name=p + "_gate"))
    add(node("Reshape", [p + "_gated", grouped], [p + "_gated_g"],
             name=p + "_gate_groups"))
    w.store[p + "_one"] = np.ones((), np.float32)
    add(node("RMSNormalization", [p + "_gated_g", p + "_one"],
             [p + "_normed_g"], name=p + "_gate_norm", axis=-1, epsilon=eps))
    add(node("Reshape", [p + "_normed_g", flat], [p + "_normed"],
             name=p + "_gate_flat"))
    add(node("Mul", [p + "_normed", w.full(p + "_gate_norm_w", (inner,), 1.0)],
             [p + "_scaled"], name=p + "_gate_scale"))
    add(node("CastLike", [p + "_scaled", u], [p + "_out_in"],
             name=p + "_out_cast"))
    add(node("MatMul", [p + "_out_in", w.normal(p + "_out_w", (inner, hidden),
                                                inner ** -0.5)],
             [p + "_mix"], name=p + "_out_proj"))
    return p + "_mix"


def _unsqueeze(nodes, w: Weights, src: str, axis: int) -> str:
    name = f"{src}_u{axis % 10}"
    axes = w.ints(f"axes_{axis % 10}", [axis])
    nodes.append(node("Unsqueeze", [src, axes], [name], name=name))
    return name


def _attention(nodes, w: Weights, p: str, u: str, hidden: int, heads: int,
               kv_heads: int, head_dim: int):
    std = hidden ** -0.5
    for proj, n in (("q", heads), ("k", kv_heads), ("v", kv_heads)):
        nodes.append(node("MatMul", [u, w.normal(f"{p}_{proj}_w",
                                                 (hidden, n * head_dim), std)],
                          [f"{p}_{proj}"], name=f"{p}_att_{proj}"))
    nodes.append(node("Attention", [p + "_q", p + "_k", p + "_v"],
                      [p + "_ctx"], name=p + "_att", q_num_heads=heads,
                      kv_num_heads=kv_heads, is_causal=1))
    nodes.append(node("MatMul", [p + "_ctx",
                                 w.normal(p + "_o_w", (heads * head_dim,
                                                       hidden),
                                          (heads * head_dim) ** -0.5)],
                      [p + "_mix"], name=p + "_att_o"))
    return p + "_mix"


def _relu2_ffn(nodes, w: Weights, p: str, u: str, hidden: int, width: int):
    nodes.append(node("MatMul", [u, w.normal(p + "_up_w", (hidden, width),
                                             hidden ** -0.5)],
                      [p + "_up"], name=p + "_up"))
    nodes.append(node("Relu", [p + "_up"], [p + "_relu"], name=p + "_relu"))
    nodes.append(node("Mul", [p + "_relu", p + "_relu"], [p + "_sq"],
                      name=p + "_relu2"))
    nodes.append(node("MatMul", [p + "_sq", w.normal(p + "_down_w",
                                                     (width, hidden),
                                                     width ** -0.5)],
                      [p + "_down"], name=p + "_down"))
    return p + "_down"


def _experts(nodes, w: Weights, p: str, u: str, hidden: int, experts: int,
             top_k: int, width: int, shared_width: int, scaling: float,
             first_expert: int, experts_held: int):
    add = nodes.append
    top_i, top_w = router(nodes, w, p, u, hidden, experts, top_k, scaling)
    std_up, std_down = hidden ** -0.5, width ** -0.5
    add(node("ExpertFFN",
             [u, top_i, top_w,
              w.normal(p + "_experts_up", (experts_held, hidden, width),
                       std_up),
              w.normal(p + "_experts_down", (experts_held, width, hidden),
                       std_down)],
             [p + "_routed"], name=p + "_moe_experts", domain=EXPERT_DOMAIN,
             first_expert=first_expert, num_experts=experts,
             activation="relu2"))
    shared = _relu2_ffn(nodes, w, p + "_moe_shared", u, hidden, shared_width)
    add(node("Add", [p + "_routed", shared], [p + "_mix"],
             name=p + "_moe_sum_shared"))
    return p + "_mix"


def nemotron_h(pattern: str = "MEMEM*EME", hidden: int = 2688,
               vocab: int = 32768, mamba_heads: int = 64,
               mamba_head_dim: int = 64, groups: int = 8, state: int = 128,
               conv_kernel: int = 4, chunk: int = 128, heads: int = 32,
               kv_heads: int = 2, head_dim: int = 128, experts: int = 128,
               top_k: int = 6, expert_width: int = 1856,
               shared_width: int = 3712, routed_scaling: float = 2.5,
               first_expert: int = 0, experts_held: int = 32,
               eps: float = 1e-5, time_step_min: float = 1e-3,
               time_step_max: float = 0.1, time_step_floor: float = 1e-4,
               seed: int = 0) -> ModelProto:
    """One chip's share of a ``nemotron_h`` decoder (module docstring); the
    defaults are the published widths of NVIDIA-Nemotron-3-Nano-30B-A3B with
    the pattern's first nine blocks, 32 of 128 experts and a quarter of the
    vocabulary. Input ``input_ids`` ``[N, S]`` int64 with ``S`` a multiple of
    ``chunk``; outputs ``logits`` ``[N, vocab]`` (last position), ``pooled``
    ``[N, hidden]``."""
    unknown = set(pattern) - set("ME*")
    if unknown or not pattern:
        raise ValueError(f"pattern {pattern!r}: letters M, E and * only")
    w = Weights(seed)
    nodes: List = []
    nodes.append(node("Gather", [w.normal("tok_emb", (vocab, hidden), 1.0),
                                 "input_ids"], ["tok"], name="tok", axis=0))
    x = "tok"
    for i, kind in enumerate(pattern):
        p = f"l{i}"
        u = _rms_norm(nodes, w, p + "_norm", x, hidden, eps)
        if kind == "M":
            mix = _mamba(nodes, w, p, u, hidden, mamba_heads, mamba_head_dim,
                         groups, state, conv_kernel, chunk, eps,
                         (time_step_min, time_step_max, time_step_floor))
        elif kind == "*":
            mix = _attention(nodes, w, p, u, hidden, heads, kv_heads,
                             head_dim)
        else:
            mix = _experts(nodes, w, p, u, hidden, experts, top_k,
                           expert_width, shared_width, routed_scaling,
                           first_expert, experts_held)
        nodes.append(node("Add", [x, mix], [p + "_res"], name=p + "_res"))
        x = p + "_res"
    final = _rms_norm(nodes, w, "norm_f", x, hidden, eps)
    nodes.append(node("ReduceMean", [final, w.ints("axes_1", [1])],
                      ["pooled"], name="pooled", keepdims=0))
    nodes.append(node("Gather", [final, w.ints("last_position", -1)],
                      ["last"], name="last", axis=1))
    nodes.append(node("MatMul", ["last", w.normal("lm_head", (hidden, vocab),
                                                  hidden ** -0.5)],
                      ["logits"], name="lm_head"))
    w.fill_all()
    graph = make_graph(
        nodes, f"nemotron_h_{len(pattern)}l_h{hidden}",
        [value_info("input_ids", np.int64, ["N", "S"])],
        [value_info("logits", np.float32, ["N", vocab]),
         value_info("pooled", np.float32, ["N", hidden])],
        w.store)
    return make_model(graph, opset=23, domains={EXPERT_DOMAIN: 1})
