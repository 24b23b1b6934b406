"""Work one chip's share of a ``nemotron_h`` decoder needs, counted from its
shapes (the published modelling code's algorithm, ``chunk_size`` and all).

The yardstick for ``step_mfu`` and ``matmul_roofline``, as ``work/encoder.py``
is for the encoders: operations the ARCHITECTURE requires, a multiply-add as
two, never a compiler's count. Per token and block:

- ``M``: ``in_proj`` and ``out_proj``; the chunked scan's five products (``C
  Bᵀ`` a group, the masked product with ``dt x`` a head, the chunk states, the
  carry between chunks, the entering state read through ``C``); the depthwise
  convolution (four taps a channel).
- ``*``: the four projections; scores and context over the causal HALF of the
  ``S²`` pairs.
- ``E``: the router, the shared expert, and the routed experts THIS share
  holds at their expected load: ``num_experts_per_tok * held / router_width``
  evaluations a token (6 x 32 / 128 = 1.5 for ``nemotron3_nano``; uniform ids
  and seeded weights spread the picks evenly, and the gauge
  ``smt_onnx_expert_pairs`` has what the program was sized for).
- the head, once a row (``num_logits_to_keep`` 1).

``matmul_least_seconds`` counts exactly the products that run in operations of
``trace_reduce.is_matmul``'s class on the chip (PERF.md section 5 has the
trace reading): XLA's ``convolution``/``dot`` fusions. Two sets of products
run in Pallas kernels, which that class does not hold, and are left out of it
while they stay in ``flops_per_row``: attention's scores and context (the
flash kernel) and the routed experts (the grouped-product kernel).
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple

_BYTES = {"bfloat16": 2, "float32": 4}


class Product(NamedTuple):
    what: str
    flops: float        # a row
    activations: float  # elements read and written a row
    weights: float      # elements read once a bucket
    in_matmul_class: bool = True


def _dense(what: str, tokens: int, k: int, n: int, count: int = 1) -> Product:
    return Product(what, 2.0 * tokens * k * n * count,
                   float(tokens * (k + n) * count), float(k * n * count))


def products_per_row(config: dict, dims: Dict[str, int]) -> List[Product]:
    """Every matrix product one row (one sequence of ``S`` tokens) needs."""
    s, h = int(dims["S"]), config["hidden_size"]
    pattern = config["hybrid_override_pattern"][: config["num_hidden_layers"]]
    n_m, n_e, n_a = (pattern.count(c) for c in "ME*")
    out: List[Product] = []

    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n = config["n_groups"], config["ssm_state_size"]
    inner, q = heads * p, config["chunk_size"]
    conv_dim, chunks = inner + 2 * g * n, s // q
    out += [
        _dense("mamba_in_proj", s, h, inner + conv_dim + heads, n_m),
        _dense("mamba_out_proj", s, inner, h, n_m),
        Product("mamba_conv", 2.0 * s * conv_dim * config["conv_kernel"] * n_m,
                2.0 * s * conv_dim * n_m,
                conv_dim * config["conv_kernel"] * n_m),
        # per chunk: C Bᵀ a group [q,n]x[n,q]; (L o CBᵀ)(dt x) a head
        # [q,q]x[q,p]; states a head [p,q]x[q,n]; C H_prev a head [q,n]x[n,p]
        Product("ssd_cb", 2.0 * s * q * n * g * n_m,
                (2.0 * s * g * n + s * q * g) * n_m, 0.0),
        Product("ssd_diag", 2.0 * s * q * p * heads * n_m,
                (s * q * heads + 2.0 * s * inner) * n_m, 0.0),
        Product("ssd_state", 2.0 * s * p * n * heads * n_m,
                (s * inner + s * g * n + chunks * inner * n) * n_m, 0.0),
        Product("ssd_off", 2.0 * s * n * p * heads * n_m,
                (s * g * n + chunks * inner * n + s * inner) * n_m, 0.0),
        # between chunks, a head: [chunks,chunks]x[chunks,p*n]
        Product("ssd_carry", 2.0 * chunks * chunks * inner * n * n_m,
                (chunks * chunks * heads + 2.0 * chunks * inner * n) * n_m,
                0.0),
    ]

    a_heads, kv, d = (config["num_attention_heads"],
                      config["num_key_value_heads"], config["head_dim"])
    out += [
        _dense("attention_q", s, h, a_heads * d, n_a),
        _dense("attention_kv", s, h, kv * d, 2 * n_a),
        _dense("attention_o", s, a_heads * d, h, n_a),
        # causal: half of the S² pairs, two products of 2 d a pair a head
        Product("attention_scores_context", 4.0 * s * s * d * a_heads / 2 * n_a,
                (2.0 * s * a_heads * d + 2.0 * s * kv * d) * n_a, 0.0,
                in_matmul_class=False),
    ]

    f, shared = (config["moe_intermediate_size"],
                 config["moe_shared_expert_intermediate_size"])
    held, wide = config["n_routed_experts"], config["router_width"]
    evaluations = config["num_experts_per_tok"] * held / wide  # a token
    out += [
        _dense("moe_router", s, h, wide, n_e),
        _dense("moe_shared_up", s, h, shared, n_e),
        _dense("moe_shared_down", s, shared, h, n_e),
        Product("moe_routed", 2.0 * 2 * s * evaluations * h * f * n_e,
                2.0 * s * evaluations * (h + f) * n_e, 2.0 * held * h * f * n_e,
                in_matmul_class=False),
    ]
    out.append(_dense("lm_head", 1, h, config["vocab_size"]))
    return out


def flops_per_row(config: dict, dims: Dict[str, int]) -> float:
    """Model FLOPs of one row: the matrix products only (norms, gates, the
    decays' exponentials and the routing's sort are left out, so the share of
    the peak this gives is a floor)."""
    return float(sum(p.flops for p in products_per_row(config, dims)))


def matmul_least_seconds(config: dict, dims: Dict[str, int], rows: int,
                         peak_flops: float, peak_bytes_per_s: float
                         ) -> Dict[str, float]:
    """Least time the chip could take over the products of one bucket of
    ``rows`` rows that run in ``trace_reduce.is_matmul``'s class: for each the
    larger of operations over the peak rate and bytes over the peak bandwidth,
    summed. A weight is read once a bucket, activations once a row, all at the
    width of the type the configuration is served in (the scan's float32
    tensors too: the smaller count, so the share is a floor)."""
    width = _BYTES[config["policy"]]
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0}
    for p in products_per_row(config, dims):
        if not p.in_matmul_class:
            continue
        fl = p.flops * rows
        by = (p.activations * rows + p.weights) * width
        t_c, t_b = fl / peak_flops, by / peak_bytes_per_s
        out["seconds"] += max(t_c, t_b)
        out["compute_bound_s" if t_c >= t_b else "bandwidth_bound_s"] += \
            max(t_c, t_b)
        out["flops"] += fl
        out["bytes"] += by
    return out
