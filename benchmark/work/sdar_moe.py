"""Work one pipeline stage's layers of an ``sdar_moe`` decoder need to
generate, counted from shapes: the yardstick for ``step_mfu``,
``matmul_roofline`` and ``generation_roofline``.

As in ``work/encoder.py``: operations the ARCHITECTURE requires, a
multiply-add as two, never a compiler's count. A call is a prompt pass over
``S`` positions a row, then for each of ``G / B`` blocks ``T`` denoising
passes and one commit pass over the block's ``B`` positions: ``(T + 1) G / B``
passes, ``T G / B`` of them with the head. A token and layer: the four
attention projections, the router, ``num_experts_per_tok`` gated experts
(three products each); scores and context over the pairs the mask ``M`` makes
visible (a query sees every earlier block and all of its own). Norms, rotary,
softmax, routing's sort and the choice of ids are left out, so the share of
the peak this gives is a floor.

``matmul_least_seconds`` counts the products that run in operations of
``trace_reduce.is_matmul``'s class on the chip: XLA's ``convolution``/``dot``
fusions, which hold the projections, the router, the head and the cached
passes' scores and context (the grouped dense form). The prompt pass's
attention (the flash kernel) and every pass's routed experts (the grouped-
product kernel) are custom calls: in ``flops_per_row``, not in that class.
A weight is read once a PASS, not once a bucket.

``generation_least_seconds`` is the whole call's roofline: phase by phase
(the prompt pass, then each pass) the larger of FLOPs over the peak and bytes
over the bandwidth, bytes being the weights the phase touches read once, the
cache's filled part, and the activations' one write and read.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple

_BYTES = {"bfloat16": 2, "float32": 4}


class Product(NamedTuple):
    what: str
    flops: float        # a row and pass
    activations: float  # elements read and written, a row and pass
    weights: float      # elements read once a pass, whatever the bucket
    in_matmul_class: bool = True


class Phase(NamedTuple):
    what: str
    times: int          # a call
    tokens: int         # a row
    products: List[Product]
    state: float        # elements of cache read or written, a row


def _generation(config: dict) -> Dict[str, int]:
    kw = config["builder_kwargs"]
    return {"G": int(kw["generate"]), "B": int(kw["block"]),
            "T": int(kw["passes"])}


def _layer_products(config: dict, tokens: int, visible_pairs: float,
                    cached: bool) -> List[Product]:
    """One layer's products for ``tokens`` positions of one row that see
    ``visible_pairs`` (query, key) pairs in all."""
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    f, k = config["moe_intermediate_size"], config["num_experts_per_tok"]
    experts = config["num_experts"]

    def dense(what, n_in, n_out, count=1):
        return Product(what, 2.0 * tokens * n_in * n_out * count,
                       float(tokens * (n_in + n_out) * count),
                       float(n_in * n_out * count))

    return [
        dense("attention_q", h, heads * d),
        dense("attention_kv", h, kv * d, 2),
        dense("attention_o", heads * d, h),
        # two products of 2 d a visible pair and query head
        Product("attention_scores_context", 4.0 * visible_pairs * d * heads,
                2.0 * tokens * heads * d, 0.0, in_matmul_class=cached),
        dense("moe_router", h, experts),
        # weights: every expert's, as if each were touched (a bucket's pairs
        # reach them all; generation_least_seconds reckons how many)
        Product("moe_routed", 2.0 * 3 * tokens * k * h * f,
                float(tokens * k * (2 * h + 3 * f)), 3.0 * experts * h * f,
                in_matmul_class=False),
    ]


def phases(config: dict, dims: Dict[str, int]) -> List[Phase]:
    """The prompt pass and every later pass of one call, a row."""
    g = _generation(config)
    s, block, n_layers = int(dims["S"]), g["B"], config["num_hidden_layers"]
    h, v = config["hidden_size"], config["vocab_size"]
    kv_row = 2 * config["num_key_value_heads"] * config["head_dim"]

    def layers(products):
        return [p._replace(flops=p.flops * n_layers,
                           activations=p.activations * n_layers,
                           weights=p.weights * n_layers) for p in products]

    # the prompt: query i sees the (i // B + 1) B positions through its block
    prompt_pairs = sum((i // block + 1) * block for i in range(s))
    out = [Phase("prompt", 1, s,
                 layers(_layer_products(config, s, prompt_pairs, False)),
                 float(s * kv_row * n_layers))]
    head = Product("lm_head", 2.0 * block * h * v, float(block * (h + v)),
                   float(h * v))
    for b in range(g["G"] // block):
        seen = s + (b + 1) * block  # positions a block's queries see
        body = layers(_layer_products(config, block, block * seen, True))
        out.append(Phase(f"denoise_{b}", g["T"], block, body + [head],
                         float(seen * kv_row * n_layers)))
        out.append(Phase(f"commit_{b}", 1, block, body,
                         float(seen * kv_row * n_layers)))
    return out


def passes_per_call(config: dict) -> int:
    g = _generation(config)
    return (g["T"] + 1) * g["G"] // g["B"]


def flops_per_row(config: dict, dims: Dict[str, int]) -> float:
    """Model FLOPs of one row's call: the matrix products only."""
    return float(sum(p.flops * phase.times for phase in phases(config, dims)
                     for p in phase.products))


def matmul_least_seconds(config: dict, dims: Dict[str, int], rows: int,
                         peak_flops: float, peak_bytes_per_s: float
                         ) -> Dict[str, float]:
    """Least time the chip could take over the products of one call of
    ``rows`` rows that run in ``trace_reduce.is_matmul``'s class: for each
    product of each pass the larger of operations over the peak rate and
    bytes over the peak bandwidth, summed."""
    width = _BYTES[config["policy"]]
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0}
    for phase in phases(config, dims):
        for p in phase.products:
            if not p.in_matmul_class:
                continue
            fl = p.flops * rows * phase.times
            by = (p.activations * rows + p.weights) * width * phase.times
            t_c, t_b = fl / peak_flops, by / peak_bytes_per_s
            out["seconds"] += max(t_c, t_b)
            out["compute_bound_s" if t_c >= t_b else "bandwidth_bound_s"] \
                += max(t_c, t_b)
            out["flops"] += fl
            out["bytes"] += by
    return out


def experts_touched(config: dict, pairs: float) -> float:
    """Experts a pass of ``pairs`` (token, pick) pairs is expected to reach
    under a router that spreads them evenly."""
    experts = config["num_experts"]
    return experts * (1.0 - (1.0 - 1.0 / experts) ** pairs)


def generation_least_seconds(config: dict, dims: Dict[str, int], rows: int,
                             peaks: dict) -> Dict[str, float]:
    """Least time the chip could take over one call of ``rows`` rows, phase
    by phase: the larger of a phase's FLOPs over the peak and its bytes over
    the bandwidth. Bytes: the weights it touches read once (the experts'
    as many as its pairs are expected to reach), the embedding rows it
    gathers, the cache's filled part read (written, in the prompt pass) and
    the activations' one write and read."""
    width = _BYTES[config["policy"]]
    h, k = config["hidden_size"], config["num_experts_per_tok"]
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0,
           "prompt_s": 0.0, "loop_s": 0.0}
    for phase in phases(config, dims):
        fl = sum(p.flops for p in phase.products) * rows
        reached = experts_touched(config, phase.tokens * rows * k) \
            / config["num_experts"]
        elements = phase.state * rows + phase.tokens * rows * h
        for p in phase.products:
            elements += p.activations * rows + p.weights * (
                reached if p.what == "moe_routed" else 1.0)
        t_c = fl / peaks["bf16_flops_per_s"]
        t_b = elements * width / peaks["hbm_bytes_per_s"]
        least = max(t_c, t_b) * phase.times
        out["seconds"] += least
        out["compute_bound_s" if t_c >= t_b else "bandwidth_bound_s"] += least
        out["prompt_s" if phase.what == "prompt" else "loop_s"] += least
        out["flops"] += fl * phase.times
        out["bytes"] += elements * width * phase.times
    return out
