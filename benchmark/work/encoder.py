"""Work a transformer encoder needs, counted from its shapes.

The yardstick for ``step_mfu`` and ``matmul_roofline``: floating-point
operations and bytes that the ARCHITECTURE requires (Devlin et al. 2018,
Dosovitskiy et al. 2020), never XLA's ``cost_analysis`` of whatever program
the executor happened to emit. A multiply-add is two operations. Bytes are
each operand and each result of a matrix product once, at the width of the
type the configuration is served in.

``config`` is a file under ``benchmark/configs/`` (the published key names);
``dims`` the symbolic sizes a cell's traffic fixes (``S`` for token ids; a
patch embedding fixes its own sequence length).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

_BYTES = {"bfloat16": 2, "float32": 4}

# (what, m, k, n, how many per row): ``count`` products [m,k] x [k,n]
Matmul = Tuple[str, int, int, int, int]


def seq_len(config: dict, dims: Dict[str, int]) -> int:
    if "patch_size" in config:  # + class token
        return (config["image_size"] // config["patch_size"]) ** 2 + 1
    return int(dims["S"])


def matmuls_per_row(config: dict, dims: Dict[str, int]) -> List[Matmul]:
    """Every matrix product one row (one sequence, one image) needs."""
    s, h = seq_len(config, dims), config["hidden_size"]
    f, heads = config["intermediate_size"], config["num_attention_heads"]
    layers, hd = config["num_hidden_layers"], h // heads
    out: List[Matmul] = []
    if "patch_size" in config:
        k = config["num_channels"] * config["patch_size"] ** 2
        out.append(("patch_projection", s - 1, k, h, 1))
    out += [
        ("qkvo_projection", s, h, h, 4 * layers),
        ("attention_scores", s, hd, s, heads * layers),
        ("attention_context", s, s, hd, heads * layers),
        ("ffn_up", s, h, f, layers),
        ("ffn_down", s, f, h, layers),
    ]
    if "vocab_size" in config:  # BERT's pooler over the first token
        out.append(("pooler", 1, h, h, 1))
    out.append(("classifier", 1, h, config["num_labels"], 1))
    return out


def flops_per_row(config: dict, dims: Dict[str, int]) -> float:
    """Model FLOPs of one row: the matrix products only (layer norms,
    softmax, GELU and the residuals are under 1% and are left out, so the
    share of the peak this gives is a floor, not a flattering count)."""
    return float(sum(2 * m * k * n * c
                     for _, m, k, n, c in matmuls_per_row(config, dims)))


def matmul_least_seconds(config: dict, dims: Dict[str, int], rows: int,
                         peak_flops: float, peak_bytes_per_s: float
                         ) -> Dict[str, float]:
    """Least time the chip could take over the matrix products of one bucket
    of ``rows`` rows: for each product the larger of operations over the
    peak rate and bytes over the peak bandwidth, summed, since the products
    of one forward pass depend on each other. A weight is read once per
    bucket, activations once per row."""
    width = _BYTES[config["policy"]]
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0,
           "compute_bound_s": 0.0, "bandwidth_bound_s": 0.0}
    for what, m, k, n, count in matmuls_per_row(config, dims):
        weight_is_shared = not what.startswith("attention")
        fl = 2.0 * m * k * n * count * rows
        act = (m * k + m * n) * count * rows
        rhs = k * n * (count if weight_is_shared else count * rows)
        by = float(act + rhs) * width
        t_c, t_b = fl / peak_flops, by / peak_bytes_per_s
        out["seconds"] += max(t_c, t_b)
        out["compute_bound_s" if t_c >= t_b else "bandwidth_bound_s"] += \
            max(t_c, t_b)
        out["flops"] += fl
        out["bytes"] += by
    return out
