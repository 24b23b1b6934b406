"""Work a ``jamba`` decoder needs to generate, counted from shapes: the
yardstick for ``step_mfu``, ``matmul_roofline``,
``recurrent_generation_roofline`` and, by hand until ``trace_reduce`` sums
by scope, the scan kernel's share of its roofline.

As in ``work/joyai_llm_flash.py``: operations the ARCHITECTURE requires, a
multiply-add as two, never a compiler's count, and the MATRIX PRODUCTS only:
the peak they are held against is the matrix unit's. A call is a prompt pass
over ``S`` positions a row, then ``G - 1`` decode passes of ONE position a
row. A token and Mamba layer: ``W_in`` (hidden to ``2d``), ``W_x`` (``d`` to
``dt_rank + 2n``), ``W_dt``, ``W_out`` and the gated feed-forward; a token and
attention layer: ``W_q``, ``W_k``, ``W_v``, ``W_o``, scores and context over
the positions it sees, the feed-forward. The tied head: at the prompt's last
position and in every decode pass. The selective scan (``d x n`` decays,
multiply-adds and one exponential a position, on the vector unit), the
convolution, norms, softplus, softmax and the argmax count no operation here,
so the share of the peak this gives is a floor; their BYTES count where a
roofline is reckoned.

``matmul_least_seconds`` counts the products that run in operations of
``trace_reduce.is_matmul``'s class on the chip: XLA's ``convolution``/``dot``
fusions, which hold the projections, the feed-forwards, the head and the
decode passes' scores and context (the grouped dense form). The scan's kernel
and single step, the prompt pass's attention (the flash kernel) and the
depthwise convolution are in no such fusion for certain: out of the class,
whatever the compiler makes of the last. A product's bytes are its
ACTIVATIONS alone: in a decode pass the compiler stages the weights into its
other memory space through asynchronous slice copies (``slice-done
bf16[640,8192]``, ``bf16[640,10240]``: 347 ms a call; my chip run, PR 39),
which are outside the class, so the product's own operation does not read
them from HBM, and with the weights counted the share read 125 %;
``generation_least_seconds`` counts them, once a PASS.

``generation_least_seconds`` is the whole call's roofline: phase by phase the
larger of FLOPs over the peak and bytes over the bandwidth, bytes being the
weights read once, the embedding rows gathered, the activations' one write
and read, the scan's operands, and the state: every Mamba layer's recurrent
state (float32) and convolution rows IN AND OUT every decode pass (written
once by the prompt pass), the filled part of the two key-value caches read
and the position's row written.

``selective_scan_work``: the 26 prompt-pass scans' updates, the bytes of their
operands and result crossing HBM once, and those bytes over the bandwidth:
the vector unit's peak is in no published table, so bytes are the yardstick
and the share it gives reads low.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.work.joyai_llm_flash import _dense
from benchmark.work.sdar_moe import _BYTES, Phase, Product

_FLOAT32 = 2  # a float32 number in elements of the policy's bfloat16


def _sizes(config: dict) -> Dict[str, int]:
    c = config
    layers, period = c["num_hidden_layers"], c["attn_layer_period"]
    attention = [i for i in range(layers)
                 if i % period == c["attn_layer_offset"]]
    return dict(
        h=c["hidden_size"], d=c["mamba_expand"] * c["hidden_size"],
        n=c["mamba_d_state"], r=c["mamba_dt_rank"], conv=c["mamba_d_conv"],
        f=c["intermediate_size"], heads=c["num_attention_heads"],
        kv=c["num_key_value_heads"],
        hd=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        layers=layers, attention=len(attention),
        mamba=layers - len(attention), vocab=c["vocab_size"],
        G=int(config["builder_kwargs"]["generate"]))


def _mamba_products(z: Dict[str, int], tokens: int) -> List[Product]:
    h, d, n, r = z["h"], z["d"], z["n"], z["r"]
    return [
        _dense("mamba_in_proj", tokens, h, 2 * d),
        Product("mamba_conv", 0.0, float(tokens * 2 * d),
                float(d * (z["conv"] + 1)), in_matmul_class=False),
        _dense("mamba_x_proj", tokens, d, r + 2 * n),
        _dense("mamba_dt_proj", tokens, r, d),
        # u, delta, z read and the gated result written; B and C float32;
        # A float32, D and the step's bias
        Product("selective_scan", 0.0,
                float(tokens * (4 * d + 2 * n * _FLOAT32)),
                float(d * n * _FLOAT32 + 2 * d), in_matmul_class=False),
        _dense("mamba_out_proj", tokens, d, h)]


def _attention_products(z: Dict[str, int], tokens: int, visible_pairs: float,
                        cached: bool) -> List[Product]:
    """One attention layer for ``tokens`` positions of one row that see
    ``visible_pairs`` (query, key) pairs in all."""
    h, heads, hd, kv = z["h"], z["heads"], z["hd"], z["kv"]
    wide = heads * hd
    out = [_dense("attention_q", tokens, h, wide),
           _dense("attention_kv", tokens, h, 2 * kv * hd),
           _dense("attention_o", tokens, wide, h)]
    flops = 2.0 * visible_pairs * heads * 2 * hd
    if cached:  # the queries and the result, and the filled caches read
        out.append(Product("attention_scores_context", flops,
                           float(tokens * 2 * wide
                                 + visible_pairs * 2 * kv * hd), 0.0))
    else:
        out.append(Product("attention_scores_context", flops,
                           float(tokens * (2 * wide + 2 * kv * hd)), 0.0,
                           in_matmul_class=False))
    return out


def _layers(z: Dict[str, int], tokens: int, visible_pairs: float,
            cached: bool) -> List[Product]:
    ffn = _dense("ffn", tokens, z["h"], z["f"], 3)
    out: List[Product] = []
    for _ in range(z["mamba"]):
        out += _mamba_products(z, tokens) + [ffn]
    for _ in range(z["attention"]):
        out += _attention_products(z, tokens, visible_pairs, cached) + [ffn]
    return out


def recurrent_state_elements(config: dict) -> float:
    """A row's recurrent state in elements of the policy's type: every Mamba
    layer's float32 ``[n, d]`` state and its ``conv - 1`` rows of ``d``."""
    z = _sizes(config)
    return float(z["mamba"] * (z["n"] * z["d"] * _FLOAT32
                               + (z["conv"] - 1) * z["d"]))


def phases(config: dict, dims: Dict[str, int]) -> List[Phase]:
    """The prompt pass and every decode pass of one call, a row. A phase's
    ``state``: what it writes (the prompt pass: every state, the convolution
    rows, ``S`` rows of each cache) or reads AND writes (a decode pass: the
    recurrent state in and out, one row of each cache; the caches' read is
    the scores' product's)."""
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
    counted once (the head is its transpose)."""
    z = _sizes(config)
    h, d, n, r = z["h"], z["d"], z["n"], z["r"]
    ffn_and_norms = 3 * h * z["f"] + 2 * h
    mamba = (h * 2 * d + d * z["conv"] + d + d * (r + 2 * n) + r + 2 * n
             + r * d + d + d * n + d + d * h)
    attention = 2 * h * z["heads"] * z["hd"] + 2 * h * z["kv"] * z["hd"]
    return (z["mamba"] * (mamba + ffn_and_norms)
            + z["attention"] * (attention + ffn_and_norms)
            + z["vocab"] * h + h)


def flops_per_row(config: dict, dims: Dict[str, int]) -> float:
    """Model FLOPs of one row's call: the matrix products only."""
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


def selective_scan_work(config: dict, dims: Dict[str, int], rows: int,
                        peaks: dict) -> Dict[str, float]:
    """The prompt pass's scans of one call of ``rows`` rows, every Mamba
    layer's: state updates (one decay, one multiply-add a channel, state and
    position), the bytes of ``u``, ``delta``, ``z``, ``B``, ``C`` read, the
    result and the leaving state written, once each, and those bytes over
    the bandwidth."""
    z = _sizes(config)
    s, width = int(dims["S"]), _BYTES[config["policy"]]
    scan = next(p for p in _mamba_products(z, s)
                if p.what == "selective_scan")
    elements = z["mamba"] * (rows * (scan.activations
                                     + z["n"] * z["d"] * _FLOAT32)
                             + scan.weights)
    return {"updates": float(z["mamba"] * rows * s * z["d"] * z["n"]),
            "bytes": elements * width,
            "seconds": elements * width / peaks["hbm_bytes_per_s"]}
