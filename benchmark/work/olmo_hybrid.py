"""Work an ``olmo_hybrid`` decoder needs to generate, counted from shapes:
the yardstick for ``step_mfu``, ``matmul_roofline`` and
``delta_rule_generation_roofline``.

As in ``work/jamba.py``: operations the ARCHITECTURE requires, a
multiply-add as two, never a compiler's count. A call is a prompt pass over
``S`` positions a row, then ``G - 1`` decode passes of ONE position a row.
A token and delta rule layer: ``W_qkv`` (hidden to ``2 H dk + H dv``),
``W_a`` and ``W_b`` (hidden to ``H`` each), ``W_gate`` (hidden to ``H dv``),
``W_out`` and the gated feed-forward, and the rule itself: ``S^T k``, ``k
u^T`` and ``S^T q`` a head, ``2 dk dv`` each; a token and attention layer:
``W_q``, ``W_k``, ``W_v``, ``W_o``, scores and context over the positions it
sees, the feed-forward. The untied head: at the prompt's last position and
in every decode pass. The convolution, norms, softplus, the decay, softmax
and the argmax count no operation; their BYTES count where a roofline is
reckoned.

``matmul_least_seconds`` counts the products that run in operations of
``trace_reduce.is_matmul``'s class on the chip: XLA's ``convolution``/``dot``
fusions, which hold the projections, the feed-forwards, the head and the
decode passes' scores and context. The rule is out of the class in both
passes: a decode pass's is the Pallas kernel, and the prompt pass's chunked
form, whose products are XLA dots at float32 (``highest``), is counted out
so that the share cannot pass 100 (its device time counts in the share's
denominator: a floor). A product's bytes are its ACTIVATIONS alone, as in
``work/jamba.py``; ``generation_least_seconds`` counts the weights, once a
PASS.

``generation_least_seconds`` is the whole call's roofline: phase by phase the
larger of FLOPs over the peak and bytes over the bandwidth, bytes being the
weights read once, the embedding rows gathered, the activations' one write
and read, the rule's operands, and the state: every delta rule layer's
float32 state and convolution rows IN AND OUT every decode pass (written
once by the prompt pass), the filled part of the key-value caches read and
the position's row written.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.work.jamba import _attention_products
from benchmark.work.joyai_llm_flash import _dense
from benchmark.work.sdar_moe import _BYTES, Phase, Product

_FLOAT32 = 2  # a float32 number in elements of the policy's bfloat16


def _sizes(config: dict) -> Dict[str, int]:
    c = config
    types = c["layer_types"]
    heads = c["num_attention_heads"]
    return dict(
        h=c["hidden_size"], f=c["intermediate_size"], heads=heads,
        kv=c["num_key_value_heads"],
        hd=c.get("head_dim") or c["hidden_size"] // heads,
        lh=c["linear_num_value_heads"], dk=c["linear_key_head_dim"],
        dv=c["linear_value_head_dim"], conv=c["linear_conv_kernel_dim"],
        attention=types.count("full_attention"),
        delta=types.count("linear_attention"), vocab=c["vocab_size"],
        G=int(config["builder_kwargs"]["generate"]))


def _channels(z: Dict[str, int]) -> int:
    return z["lh"] * (2 * z["dk"] + z["dv"])


def _delta_products(z: Dict[str, int], tokens: int) -> List[Product]:
    h, lh, dk, dv = z["h"], z["lh"], z["dk"], z["dv"]
    c = _channels(z)
    return [
        _dense("delta_qkv_proj", tokens, h, c),
        Product("delta_conv", 0.0, float(tokens * 2 * c),
                float(c * z["conv"]), in_matmul_class=False),
        _dense("delta_ab_proj", tokens, h, 2 * lh),
        # q, k, v read, g and beta float32, the result written
        Product("gated_delta_rule", float(tokens * lh * 3 * 2 * dk * dv),
                float(tokens * (lh * (2 * dk + 2 * dv) + 2 * lh * _FLOAT32)),
                0.0, in_matmul_class=False),
        _dense("delta_gate_proj", tokens, h, lh * dv),
        _dense("delta_out_proj", tokens, lh * dv, h)]


def _layers(z: Dict[str, int], tokens: int, visible_pairs: float,
            cached: bool) -> List[Product]:
    ffn = _dense("ffn", tokens, z["h"], z["f"], 3)
    out: List[Product] = []
    for _ in range(z["delta"]):
        out += _delta_products(z, tokens) + [ffn]
    for _ in range(z["attention"]):
        out += _attention_products(z, tokens, visible_pairs, cached) + [ffn]
    return out


def recurrent_state_elements(config: dict) -> float:
    """A row's recurrent state in elements of the policy's type: every delta
    rule layer's float32 ``[dk, H dv]`` state and its ``conv - 1`` rows of
    the projection's channels."""
    z = _sizes(config)
    return float(z["delta"] * (z["dk"] * z["lh"] * z["dv"] * _FLOAT32
                               + (z["conv"] - 1) * _channels(z)))


def phases(config: dict, dims: Dict[str, int]) -> List[Phase]:
    """The prompt pass and every decode pass of one call, a row. A phase's
    ``state``: what it writes (the prompt pass: every state, the convolution
    rows, ``S`` rows of each cache) or reads AND writes (a decode pass: the
    state in and out, one row of each cache; the caches' read is the
    scores' product's)."""
    z = _sizes(config)
    s = int(dims["S"])
    head = _dense("lm_head", 1, z["h"], z["vocab"])
    recurrent = recurrent_state_elements(config)
    cache_row = float(z["attention"] * 2 * z["kv"] * z["hd"])
    out = [Phase("prompt", 1, s,
                 _layers(z, s, s * (s + 1) / 2.0, False) + [head],
                 recurrent + s * cache_row)]
    for t in range(1, z["G"]):
        seen = s + t  # positions pass t's query sees, its own among them
        out.append(Phase(f"decode_{t}", 1, 1,
                         _layers(z, 1, float(seen), True) + [head],
                         2.0 * recurrent + cache_row))
    return out


def passes_per_call(config: dict) -> int:
    return _sizes(config)["G"] - 1


def parameters(config: dict) -> int:
    """The model's parameters from the configuration's widths, the embedding
    and the untied head each counted."""
    z = _sizes(config)
    h, lh, dk, dv = z["h"], z["lh"], z["dk"], z["dv"]
    c = _channels(z)
    ffn_and_norms = 3 * h * z["f"] + 2 * h
    delta = (h * c + c * z["conv"] + h * 2 * lh + 2 * lh + h * lh * dv + dv
             + lh * dv * h)
    wide = z["heads"] * z["hd"]
    attention = 2 * h * wide + 2 * h * z["kv"] * z["hd"] + wide \
        + z["kv"] * z["hd"]
    return (z["delta"] * (delta + ffn_and_norms)
            + z["attention"] * (attention + ffn_and_norms)
            + 2 * z["vocab"] * h + h)


def flops_per_row(config: dict, dims: Dict[str, int]) -> float:
    """Model FLOPs of one row's call: the products and the rule."""
    return float(sum(p.flops * phase.times for phase in phases(config, dims)
                     for p in phase.products))


def matmul_least_seconds(config: dict, dims: Dict[str, int], rows: int,
                         peak_flops: float, peak_bytes_per_s: float
                         ) -> Dict[str, float]:
    """Least time the chip could take over the products of one call of
    ``rows`` rows that run in ``trace_reduce.is_matmul``'s class: for each
    product of each pass the larger of operations over the peak rate and its
    activations' bytes over the peak bandwidth (module docstring), summed."""
    width = _BYTES[config["policy"]]
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0}
    for phase in phases(config, dims):
        for p in phase.products:
            if not p.in_matmul_class:
                continue
            fl = p.flops * rows * phase.times
            by = p.activations * rows * width * phase.times
            t_c, t_b = fl / peak_flops, by / peak_bytes_per_s
            out["seconds"] += max(t_c, t_b)
            out["compute_bound_s" if t_c >= t_b else "bandwidth_bound_s"] \
                += max(t_c, t_b)
            out["flops"] += fl
            out["bytes"] += by
    return out


def generation_least_seconds(config: dict, dims: Dict[str, int], rows: int,
                             peaks: dict) -> Dict[str, float]:
    """Least time the chip could take over one call of ``rows`` rows, phase
    by phase: the larger of a phase's FLOPs over the peak and its bytes over
    the bandwidth (module docstring)."""
    width = _BYTES[config["policy"]]
    z = _sizes(config)
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0,
           "prompt_s": 0.0, "loop_s": 0.0, "state_bytes": 0.0}
    for phase in phases(config, dims):
        fl = sum(p.flops for p in phase.products) * rows
        elements = phase.state * rows + phase.tokens * rows * z["h"]
        for p in phase.products:
            elements += p.activations * rows + p.weights
        t_c = fl / peaks["bf16_flops_per_s"]
        t_b = elements * width / peaks["hbm_bytes_per_s"]
        least = max(t_c, t_b) * phase.times
        out["seconds"] += least
        out["compute_bound_s" if t_c >= t_b else "bandwidth_bound_s"] += least
        out["prompt_s" if phase.what == "prompt" else "loop_s"] += least
        out["flops"] += fl * phase.times
        out["bytes"] += elements * width * phase.times
        out["state_bytes"] += phase.state * rows * width * phase.times
    return out


def decode_step_work(config: dict, rows: int, peaks: dict) -> Dict[str, float]:
    """The decode pass's rule, every delta rule layer's, for ``rows`` rows:
    the bytes of the state read and written once and of the operands, over
    the bandwidth: the yardstick of the step kernel's share, read by hand
    from ``tools/trace_by_node.py`` (``PERF.md`` section 5)."""
    z = _sizes(config)
    width = _BYTES[config["policy"]]
    rule = next(p for p in _delta_products(z, 1)
                if p.what == "gated_delta_rule")
    state = z["dk"] * z["lh"] * z["dv"] * _FLOAT32
    elements = z["delta"] * rows * (2 * state + rule.activations)
    return {"bytes": elements * width,
            "seconds": elements * width / peaks["hbm_bytes_per_s"]}
