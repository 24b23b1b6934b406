"""Work a ``keye_vl2`` decoder needs to generate, counted from shapes: the
yardstick for ``step_mfu``, ``matmul_roofline`` and
``indexed_generation_roofline``.

As in ``work/sdar_moe.py``: operations the ARCHITECTURE requires, a
multiply-add as two, never a compiler's count. A call is a prompt pass over
``S`` positions a row, then ``G - 1`` decode passes of ONE position a row. A
token and layer: ``W_q``, ``W_k``, ``W_v``, ``W_o``, the indexer's ``W_qI``,
``W_kI`` and ``W_w``, the router, ``num_experts_per_tok`` gated experts
(three products each); the indexer's scores over the keys a query sees
(``2 dI + 2`` an index head and pair: the dot, the weight and the sum) and
attention's scores and context over the keys it CHOSE (``min(t + 1,
topk)``), not over every key. The untied head at the prompt's last position
and in every decode pass. Norms, rotary, the ReLU, the softmax, the
selection's counts and the choice of ids count no operation.

``matmul_least_seconds`` counts ONLY the products that run as XLA's dot
fusions (``trace_reduce.is_matmul``'s class): the projections, the router
and the head. The indexer's two Pallas kernels, the two attention kernels
and the experts' grouped product are custom calls, outside that class, so
their work stays out of it (counting it would read over 100 %); a weight is
read once a PASS.

``generation_least_seconds`` is the whole call's roofline, phase by phase
the larger of FLOPs over the peak and bytes over the bandwidth, bytes being
the weights the phase touches read once (of the experts' as many as its
pairs are expected to reach), the embedding rows gathered, the activations'
one write and read, the index keys a query's scores read, the chosen rows of
the key-value caches, and the three caches' rows written.

``indexer_work`` and ``selected_attention_work`` are each kernel's
operations and bytes, a pass's kind at a time: the yardsticks of the
kernels' roofline shares read by hand from ``tools/trace_by_node.py``
(``PERF.md`` section 5).
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.work.sdar_moe import _BYTES, Phase, Product

_FLOAT32 = 2  # a float32 number in elements of the policy's bfloat16


def _sizes(config: dict) -> Dict[str, int]:
    c, sa = config, config["sa_config"]
    return dict(
        h=c["hidden_size"], heads=c["num_attention_heads"],
        kv=c["num_key_value_heads"], hd=c["head_dim"],
        f=c["moe_intermediate_size"], k=c["num_experts_per_tok"],
        experts=c["num_experts"], vocab=c["vocab_size"],
        layers=c["num_hidden_layers"], index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], topk=sa["topk"],
        G=int(c["builder_kwargs"]["generate"]))


def _dense(what: str, tokens: int, n_in: int, n_out: int,
           count: int = 1) -> Product:
    return Product(what, 2.0 * tokens * n_in * n_out * count,
                   float(tokens * (n_in + n_out) * count),
                   float(n_in * n_out * count))


def chosen_pairs(topk: int, first: int, last: int) -> float:
    """Keys chosen by the queries at positions ``first .. last - 1``: a
    query at ``t`` keeps ``min(t + 1, topk)``."""
    below = max(0, min(last, topk) - first)  # queries that keep t + 1
    lo = first + 1
    return below * (2 * lo + below - 1) / 2.0 \
        + max(0, last - max(first, topk)) * float(topk)


def _indexer(z: Dict[str, int], tokens: int, scored: float,
             keys_read: float) -> Product:
    """The index scores of ``tokens`` queries over ``scored`` (query, key)
    pairs, the index keys read once (``keys_read`` of them)."""
    hi, di = z["index_heads"], z["index_dim"]
    return Product("index_scores", float(scored * hi * (2 * di + 2)),
                   float(tokens * (hi * di + hi * _FLOAT32)
                         + keys_read * di), 0.0, in_matmul_class=False)


def _attention(z: Dict[str, int], tokens: int, chosen: float,
               rows_read: float) -> Product:
    """Attention of ``tokens`` queries over ``chosen`` (query, key) pairs,
    two products of ``2 d`` a pair and query head; ``rows_read`` key and
    value rows read."""
    heads, hd = z["heads"], z["hd"]
    return Product("selected_attention", 4.0 * chosen * hd * heads,
                   float(2 * tokens * heads * hd
                         + rows_read * 2 * z["kv"] * hd), 0.0,
                   in_matmul_class=False)


def _layers(z: Dict[str, int], tokens: int, scored: float, chosen: float,
            keys_read: float, rows_read: float) -> List[Product]:
    h, hi, di = z["h"], z["index_heads"], z["index_dim"]
    one = [
        _dense("attention_q", tokens, h, z["heads"] * z["hd"]),
        _dense("attention_kv", tokens, h, z["kv"] * z["hd"], 2),
        _dense("attention_o", tokens, z["heads"] * z["hd"], h),
        _dense("index_q", tokens, h, hi * di),
        _dense("index_k", tokens, h, di),
        _dense("index_w", tokens, h, hi),
        _indexer(z, tokens, scored, keys_read),
        _attention(z, tokens, chosen, rows_read),
        _dense("moe_router", tokens, h, z["experts"]),
        # weights: every expert's, as if each were touched
        # (generation_least_seconds reckons how many are)
        Product("moe_routed", 2.0 * 3 * tokens * z["k"] * h * z["f"],
                float(tokens * z["k"] * (2 * h + 3 * z["f"])),
                3.0 * z["experts"] * h * z["f"], in_matmul_class=False),
    ]
    return one * z["layers"]


def cache_row_elements(config: dict) -> float:
    """A position's three cache rows in all layers, in elements: keys,
    values and the index key."""
    z = _sizes(config)
    return float(z["layers"] * (2 * z["kv"] * z["hd"] + z["index_dim"]))


def phases(config: dict, dims: Dict[str, int]) -> List[Phase]:
    """The prompt pass and every decode pass of one call, a row. A phase's
    ``state``: the cache rows it writes (the prompt pass: ``S`` of each,
    a decode pass one); what the scores and the attention read of the caches
    is in their products."""
    z = _sizes(config)
    s = int(dims["S"])
    head = _dense("lm_head", 1, z["h"], z["vocab"])
    row = cache_row_elements(config)
    out = [Phase("prompt", 1, s,
                 _layers(z, s, s * (s + 1) / 2.0,
                         chosen_pairs(z["topk"], 0, s), s, s) + [head],
                 s * row)]
    for t in range(1, z["G"]):
        seen = s + t  # positions pass t's query sees, its own among them
        chosen = float(min(seen, z["topk"]))
        out.append(Phase(f"decode_{t}", 1, 1,
                         _layers(z, 1, seen, chosen, seen, chosen) + [head],
                         row))
    return out


def passes_per_call(config: dict) -> int:
    return _sizes(config)["G"] - 1


def parameters(config: dict) -> int:
    """The model's parameters from the configuration's widths, the embedding
    and the untied head each counted."""
    z = _sizes(config)
    h, hd, di = z["h"], z["hd"], z["index_dim"]
    attention = (2 * h * z["heads"] * hd + 2 * h * z["kv"] * hd + 2 * h
                 + 2 * hd)
    indexer = h * z["index_heads"] * di + h * di + h * z["index_heads"] \
        + 2 * di
    experts = h * z["experts"] + 3 * z["experts"] * h * z["f"]
    return z["layers"] * (attention + indexer + experts) \
        + 2 * z["vocab"] * h + h


def flops_per_row(config: dict, dims: Dict[str, int]) -> float:
    """Model FLOPs of one row's call: the products, the index scores and
    attention over the chosen keys."""
    return float(sum(p.flops * phase.times for phase in phases(config, dims)
                     for p in phase.products))


def matmul_least_seconds(config: dict, dims: Dict[str, int], rows: int,
                         peak_flops: float, peak_bytes_per_s: float
                         ) -> Dict[str, float]:
    """Least time the chip could take over the products of one call of
    ``rows`` rows that run as XLA's dot fusions: for each such product of
    each pass the larger of operations over the peak rate and bytes (its
    activations and its weights once a pass) over the peak bandwidth,
    summed. A product of ONE row (a decode pass's, and the head, at one
    row a call) is a vector against a matrix, which XLA runs as a
    multiply-reduce loop fusion, outside ``is_matmul``'s class: left out."""
    width = _BYTES[config["policy"]]
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0}
    for phase in phases(config, dims):
        for p in phase.products:
            one_row = rows == 1 and (phase.tokens == 1 or p.what == "lm_head")
            if not p.in_matmul_class or one_row:
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
    the bandwidth (module docstring)."""
    width = _BYTES[config["policy"]]
    z = _sizes(config)
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0,
           "prompt_s": 0.0, "loop_s": 0.0}
    for phase in phases(config, dims):
        fl = sum(p.flops for p in phase.products) * rows
        reached = experts_touched(config, phase.tokens * rows * z["k"]) \
            / z["experts"]
        elements = phase.state * rows + phase.tokens * rows * z["h"]
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


def _kernel_work(config: dict, dims: Dict[str, int], rows: int, peaks: dict,
                 what: str, kind: str) -> Dict[str, float]:
    width = _BYTES[config["policy"]]
    out = {"flops": 0.0, "bytes": 0.0}
    for phase in phases(config, dims):
        if (phase.what == "prompt") != (kind == "prompt"):
            continue
        for p in phase.products:
            if p.what == what:
                out["flops"] += p.flops * rows * phase.times
                out["bytes"] += p.activations * rows * width * phase.times
    out["seconds"] = max(out["flops"] / peaks["bf16_flops_per_s"],
                         out["bytes"] / peaks["hbm_bytes_per_s"])
    return out


def indexer_work(config: dict, dims: Dict[str, int], rows: int,
                 peaks: dict, kind: str = "prompt") -> Dict[str, float]:
    """The indexer kernel's work over one call's ``kind`` of passes
    (``prompt`` or ``decode``, every layer): the index scores' operations,
    the index queries, weights and keys read once, and the least seconds."""
    return _kernel_work(config, dims, rows, peaks, "index_scores", kind)


def selected_attention_work(config: dict, dims: Dict[str, int], rows: int,
                            peaks: dict, kind: str = "prompt"
                            ) -> Dict[str, float]:
    """The attention kernel's work over one call's ``kind`` of passes: the
    chosen pairs' operations, the queries and results and the chosen rows
    (the prompt pass: every row once) read, and the least seconds; for the
    prompt pass also ``computed_flops``, what its blockwise form computes
    (every causal pair of every head)."""
    out = _kernel_work(config, dims, rows, peaks, "selected_attention", kind)
    if kind == "prompt":
        z, s = _sizes(config), int(dims["S"])
        out["computed_flops"] = (4.0 * s * (s + 1) / 2.0 * z["hd"]
                                 * z["heads"] * z["layers"] * rows)
    return out
