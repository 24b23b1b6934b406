"""Work one chip's share of a ``joyai_llm_flash`` decoder needs to generate,
counted from shapes: the yardstick for ``step_mfu``, ``matmul_roofline`` and
``latent_generation_roofline``.

As in ``work/sdar_moe.py``: operations the ARCHITECTURE requires, a
multiply-add as two, never a compiler's count. A call is a prompt pass over
``S`` positions a row in the EXPANDED form of latent attention (keys and
values of every head from the latents: ``W_uk`` and ``W_uv`` a position), then
``G - 1`` decode passes of ONE position a row in the ABSORBED form (``W_uk``
and ``W_uv`` a query, scores and context against the filled latents, ``2 x
heads x (kv_rank + rope + kv_rank)`` operations a visible position). A token
and layer: the query and latent projections, ``W_o``, then the dense layer's
gated feed-forward or the router, the shared expert and the routed experts
HELD here: ``top_k x held / experts`` expected picks a token, a pick of an
expert held elsewhere being no work of this chip. The head: at the prompt's
last position and in every decode pass. Norms, rotary, softmax, routing's
sort and the argmax are left out, so the share of the peak this gives is a
floor.

``matmul_least_seconds`` counts the products that run in operations of
``trace_reduce.is_matmul``'s class on the chip: XLA's ``convolution``/``dot``
fusions, which hold the projections, the dense and shared feed-forwards, the
router, the head and the decode passes' scores and context (the grouped dense
form). The prompt pass's attention (the flash kernel) and every pass's routed
experts (the grouped-product kernel) are custom calls: in ``flops_per_row``,
not in that class. A weight is read once a PASS, not once a bucket.

``generation_least_seconds`` is the whole call's roofline: phase by phase the
larger of FLOPs over the peak and bytes over the bandwidth, bytes being the
weights the phase touches read once (the routed experts' as many as the
phase's pairs are EXPECTED to reach of the held ones), the filled latents read
ONCE (written, in the prompt pass), the activations' one write and read.

``attention_kernel_work`` and ``latent_read_work`` are the operations and
bytes of the two attention shapes this family brought, for the day
``trace_reduce`` sums device time by scope.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.work.sdar_moe import _BYTES, Phase, Product


def _sizes(config: dict) -> Dict[str, int]:
    c = config
    return dict(
        h=c["hidden_size"], heads=c["num_attention_heads"],
        q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
        nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
        v=c["v_head_dim"], dense=c["intermediate_size"],
        f=c["moe_intermediate_size"], k=c["num_experts_per_tok"],
        experts=int(c.get("published", {}).get("n_routed_experts",
                                               c["n_routed_experts"])),
        held=c["n_routed_experts"], shared=c["n_shared_experts"],
        layers=c["num_hidden_layers"],
        dense_layers=c.get("first_k_dense_replace", 1), vocab=c["vocab_size"],
        G=int(config["builder_kwargs"]["generate"]))


def _dense(what: str, tokens: int, n_in: int, n_out: int, count: int = 1,
           in_class: bool = True) -> Product:
    return Product(what, 2.0 * tokens * n_in * n_out * count,
                   float(tokens * (n_in + n_out) * count),
                   float(n_in * n_out * count), in_class)


def _attention_products(z: Dict[str, int], tokens: int, visible_pairs: float,
                        absorbed: bool) -> List[Product]:
    """One layer's attention for ``tokens`` positions of one row that see
    ``visible_pairs`` (query, key) pairs in all."""
    h, heads, qk = z["h"], z["heads"], z["nope"] + z["rope"]
    out = [_dense("attention_dq", tokens, h, z["q_rank"]),
           _dense("attention_uq", tokens, z["q_rank"], heads * qk),
           _dense("attention_dkv", tokens, h, z["kv_rank"] + z["rope"]),
           # W_uk and W_uv: a position's keys and values in the expanded
           # form, a query's absorbed products in the other: the same count
           _dense("attention_uk_uv", tokens, z["kv_rank"],
                  heads * (z["nope"] + z["v"])),
           _dense("attention_o", tokens, heads * z["v"], h)]
    if absorbed:
        # scores over kv_rank + rope, context over kv_rank, every head
        # against ONE head of latents (state counts their read)
        wide = z["kv_rank"] + z["rope"] + z["kv_rank"]
        out.append(Product("attention_scores_context",
                           2.0 * visible_pairs * heads * wide,
                           float(tokens * heads * wide), 0.0))
    else:
        out.append(Product("attention_scores_context",
                           2.0 * visible_pairs * heads * (qk + z["v"]),
                           float(tokens * heads * (2 * qk + 2 * z["v"])), 0.0,
                           in_matmul_class=False))
    return out


def _ffn_products(z: Dict[str, int], tokens: int, dense: bool
                  ) -> List[Product]:
    h, f = z["h"], z["f"]
    if dense:
        return [_dense("ffn_dense", tokens, h, z["dense"], 3)]
    picks_here = z["k"] * z["held"] / z["experts"]  # expected, a token
    return [_dense("moe_router", tokens, h, z["experts"]),
            _dense("moe_shared", tokens, h, f * z["shared"], 3),
            # weights: every held expert's, as if each were touched
            # (generation_least_seconds reckons how many are)
            Product("moe_routed", 2.0 * 3 * tokens * picks_here * h * f,
                    float(tokens * picks_here * (2 * h + 3 * f)),
                    3.0 * z["held"] * h * f, in_matmul_class=False)]


def _layers(z: Dict[str, int], tokens: int, visible_pairs: float,
            absorbed: bool) -> List[Product]:
    out: List[Product] = []
    for i in range(z["layers"]):
        out += _attention_products(z, tokens, visible_pairs, absorbed)
        out += _ffn_products(z, tokens, dense=i < z["dense_layers"])
    return out


def phases(config: dict, dims: Dict[str, int]) -> List[Phase]:
    """The prompt pass and every decode pass of one call, a row."""
    z = _sizes(config)
    s, latent = int(dims["S"]), z["kv_rank"] + z["rope"]
    head = _dense("lm_head", 1, z["h"], z["vocab"])
    out = [Phase("prompt", 1, s,
                 _layers(z, s, s * (s + 1) / 2.0, False) + [head],
                 float(s * latent * z["layers"]))]
    for t in range(1, z["G"]):
        seen = s + t  # positions pass t's query sees, its own among them
        out.append(Phase(f"decode_{t}", 1, 1,
                         _layers(z, 1, float(seen), True) + [head],
                         float(seen * latent * z["layers"])))
    return out


def passes_per_call(config: dict) -> int:
    return _sizes(config)["G"] - 1


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
    bytes over the peak bandwidth, summed. The decode passes' scores and
    context also read the filled latents, once a pass and layer."""
    width = _BYTES[config["policy"]]
    n_layers = _sizes(config)["layers"]
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0}
    for phase in phases(config, dims):
        for p in phase.products:
            if not p.in_matmul_class:
                continue
            fl = p.flops * rows * phase.times
            elements = p.activations * rows + p.weights
            if p.what == "attention_scores_context":
                elements += phase.state * rows / n_layers
            by = elements * width * phase.times
            t_c, t_b = fl / peak_flops, by / peak_bytes_per_s
            out["seconds"] += max(t_c, t_b)
            out["compute_bound_s" if t_c >= t_b else "bandwidth_bound_s"] \
                += max(t_c, t_b)
            out["flops"] += fl
            out["bytes"] += by
    return out


def held_experts_reached(config: dict, pairs: float) -> float:
    """The share of the HELD experts a pass of ``pairs`` (token, pick) pairs
    over the whole router is expected to reach under a router that spreads
    them evenly: each pair lands on a given expert with one chance in
    ``experts``."""
    experts = _sizes(config)["experts"]
    return 1.0 - (1.0 - 1.0 / experts) ** pairs


def generation_least_seconds(config: dict, dims: Dict[str, int], rows: int,
                             peaks: dict) -> Dict[str, float]:
    """Least time the chip could take over one call of ``rows`` rows, phase
    by phase: the larger of a phase's FLOPs over the peak and its bytes over
    the bandwidth. Bytes: the weights it touches read once (of the held
    experts' as many as its pairs are expected to reach), the embedding rows
    it gathers, the filled latents read once (written, in the prompt pass)
    and the activations' one write and read."""
    width = _BYTES[config["policy"]]
    z = _sizes(config)
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0,
           "prompt_s": 0.0, "loop_s": 0.0}
    for phase in phases(config, dims):
        fl = sum(p.flops for p in phase.products) * rows
        reached = held_experts_reached(config, phase.tokens * rows * z["k"])
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


def attention_kernel_work(config: dict, dims: Dict[str, int], rows: int
                          ) -> Dict[str, float]:
    """One layer's causal attention of the prompt pass, the kernel whose
    queries and keys are ``nope + rope`` wide and whose values are ``v``:
    operations (the visible half of ``S x S`` a head) and the bytes of
    queries, keys, values and the result crossing HBM once."""
    z, s = _sizes(config), int(dims["S"])
    qk, width = z["nope"] + z["rope"], _BYTES[config["policy"]]
    flops = 2.0 * rows * z["heads"] * (s * (s + 1) / 2.0) * (qk + z["v"])
    return {"flops": flops,
            "bytes": float(rows * s * z["heads"] * (2 * qk + 2 * z["v"])
                           * width)}


def latent_read_work(config: dict, dims: Dict[str, int], rows: int,
                     filled: int = None) -> Dict[str, float]:
    """One layer's decode attention of one pass: every head's query against
    ``filled`` cached latents (default: the mean over a call's passes)
    shared by all heads; the bytes are the latents read ONCE."""
    z, s = _sizes(config), int(dims["S"])
    if filled is None:
        filled = s + z["G"] / 2.0
    latent, width = z["kv_rank"] + z["rope"], _BYTES[config["policy"]]
    return {"flops": 2.0 * rows * z["heads"] * filled
            * (latent + z["kv_rank"]),
            "bytes": float(rows * filled * latent * width)}
