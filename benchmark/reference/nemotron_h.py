"""Plain ``nemotron_h`` decoder, the reference of ``nemotron3_nano``.

The published modelling code of NVIDIA-Nemotron-3-Nano-30B-A3B (Hugging Face
``modeling_nemotron_h.py``) in straightforward ``jax.numpy`` float32, every
matrix product at ``highest`` precision, nothing imported from the program.
Where the program's graph is clever this is not, so that the cleverness is
under test:

- the Mamba-2 recurrence is the SEQUENTIAL scan over positions, ``H_t =
  exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t``, ``y_t = H_t C_t + D x_t``: no
  chunks, no decay matrices;
- attention writes its scores, a row block at a time;
- the routed experts are a loop over the experts this share holds, each over
  every token, weighted by a mask of the picks that chose it.

Block i is ``x <- x + mixer_i(RMSNorm(x))`` with the mixer its letter of
``hybrid_override_pattern`` names (``M``, ``E``, ``*``); ``norm_f`` after the
last; ``logits`` of the last position, ``pooled`` the mean of ``norm_f``'s
output over positions. One chip's share: the experts whose weights the model
file holds, from ``builder_kwargs.first_expert`` on, under a router as wide as
published; a pick of an expert held elsewhere adds nothing. Weights come out
of the model file by name and are ARGUMENTS of the jitted functions.

``precision`` is the arithmetic of the matrix products and nothing else
(``encoder._mm``: ``float32`` the reference proper, ``bfloat16``, and the
``float8`` control); the scan, the norms and the router's sigmoid stay float32.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from benchmark.reference import onnx_initializers
from benchmark.reference.encoder import PRECISIONS, _mm

# the model file's weights are BFLOAT16 tensors (ONNX data type 16), which the
# reader's table of types does not list yet (PERF.md section 7)
onnx_initializers._DTYPES.setdefault(16, ml_dtypes.bfloat16)

_HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _silu(x):
    return x * jax.nn.sigmoid(x)


@partial(jax.jit, static_argnames=("heads", "groups", "eps", "precision"))
def mamba_mixer(u, w: Dict[str, jax.Array], heads: int, groups: int,
                eps: float, precision: str):
    """``u [n, s, hidden]`` -> the mixer's output, by the sequential scan."""
    n, s, _ = u.shape
    inner = w["out_w"].shape[0]
    head_dim = inner // heads
    kernel = w["conv_w"].shape[-1]
    state = (w["conv_w"].shape[0] - inner) // (2 * groups)
    zxbcdt = _mm("nsh,hk->nsk", u, w["in_w"], precision)
    z, xbc, dt = jnp.split(zxbcdt, [inner, zxbcdt.shape[-1] - heads], -1)
    # depthwise, causal: position t sees t-3 .. t
    padded = jnp.pad(xbc, ((0, 0), (kernel - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + s] * w["conv_w"][:, 0, j]
               for j in range(kernel)) + w["conv_b"]
    x, b, c = jnp.split(_silu(conv), [inner, inner + groups * state], -1)
    x = x.reshape(n, s, heads, head_dim)
    per = heads // groups  # head h reads group h // per
    b = jnp.repeat(b.reshape(n, s, groups, state), per, axis=2)
    c = jnp.repeat(c.reshape(n, s, groups, state), per, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["a_log"])

    def step(h, at_t):
        x_t, b_t, c_t, dt_t = at_t
        h = (h * jnp.exp(dt_t * a)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, jnp.einsum("nhps,nhs->nhp", h, c_t, precision=_HIGHEST)

    time_major = [jnp.moveaxis(t, 1, 0) for t in (x, b, c, dt)]
    _, y = jax.lax.scan(step, jnp.zeros((n, heads, head_dim, state),
                                        jnp.float32), time_major)
    y = jnp.moveaxis(y, 0, 1) + w["d"][:, None] * x
    y = y.reshape(n, s, inner) * _silu(z)  # the gate BEFORE the norm
    y = rms_norm(y.reshape(n, s, groups, inner // groups), 1.0, eps)
    y = y.reshape(n, s, inner) * w["gate_norm_w"]
    return _mm("nsk,kh->nsh", y, w["out_w"], precision)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "precision"))
def attention_mixer(u, w: Dict[str, jax.Array], heads: int, kv_heads: int,
                    precision: str):
    """Causal grouped-query attention with materialised scores; no
    positional term."""
    n, s, _ = u.shape
    q = _mm("nsh,hk->nsk", u, w["q_w"], precision).reshape(n, s, heads, -1)
    k = _mm("nsh,hk->nsk", u, w["k_w"], precision).reshape(n, s, kv_heads, -1)
    v = _mm("nsh,hk->nsk", u, w["v_w"], precision).reshape(n, s, kv_heads, -1)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    scores = _mm("nqhd,nkhd->nhqk", q, k, precision) / np.sqrt(q.shape[-1])
    visible = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    ctx = _mm("nhqk,nkhd->nqhd", probs, v, precision).reshape(n, s, -1)
    return _mm("nsk,kh->nsh", ctx, w["o_w"], precision)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(u, w: Dict[str, jax.Array], top_k: int, scaling: float,
          precision: str):
    """Picks ``[n, s, k]`` and their weights: sigmoid scores, the top-k of
    score + correction bias, the scores at the picks (without the bias)
    over their sum, times the routed scaling factor."""
    scores = jax.nn.sigmoid(_mm("nsh,he->nse", u, w["router_w"], precision))
    _, picks = jax.lax.top_k(scores + w["router_bias"], top_k)
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20) * scaling
    return picks, weights


@partial(jax.jit, static_argnames=("top_k", "scaling", "first_expert",
                                   "precision", "shared"))
def expert_mixer(u, w: Dict[str, jax.Array], top_k: int, scaling: float,
                 first_expert: int, precision: str, shared: bool = True):
    """The routed experts this share holds (``experts_up [held, h, f]``),
    one after another over every token, plus the shared expert (``shared``
    False leaves it out: for adding shares up)."""
    picks, weights = route(u, w, top_k, scaling, precision)

    def one_expert(total, expert):
        index, up, down = expert
        share = jnp.sum(jnp.where(picks == index, weights, 0.0), axis=-1)
        out = _mm("nsf,fh->nsh",
                  _relu2(_mm("nsh,hf->nsf", u, up, precision)), down,
                  precision)
        return total + share[..., None] * out, None

    held = w["experts_up"].shape[0]
    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (first_expert + jnp.arange(held), w["experts_up"], w["experts_down"]))
    if shared:
        total = total + _mm(
            "nsf,fh->nsh", _relu2(_mm("nsh,hf->nsf", u,
                                      w["moe_shared_up_w"], precision)),
            w["moe_shared_down_w"], precision)
    return total


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm_w, lm_head, eps: float, precision: str):
    final = rms_norm(x, norm_w, eps)
    logits = _mm("nh,hv->nv", final[:, -1], lm_head, precision)
    return logits, jnp.mean(final, axis=1)


@partial(jax.jit, static_argnames=("eps",))
def _pre_norm(x, weight, eps: float):
    return rms_norm(x, weight, eps)


_KEYS = {
    "M": ("in_w", "conv_w", "conv_b", "dt_bias", "a_log", "d", "gate_norm_w",
          "out_w"),
    "*": ("q_w", "k_w", "v_w", "o_w"),
    "E": ("router_w", "router_bias", "experts_up", "experts_down",
          "moe_shared_up_w", "moe_shared_down_w"),
}


class Reference:
    """The forward pass of one configuration over blocks of rows; returns the
    graph's own output names, ``logits`` and ``pooled``."""

    def __init__(self, config: dict, initializers: Dict[str, np.ndarray]):
        self.config = config
        self.pattern = config["hybrid_override_pattern"][
            : config["num_hidden_layers"]]
        self.first_expert = int(config.get("builder_kwargs", {})
                                .get("first_expert", 0))

        def put(name):  # a bfloat16 tensor is widened on the device
            return jnp.asarray(np.ascontiguousarray(initializers[name])
                               ).astype(jnp.float32)

        self.layers: List[Dict[str, jax.Array]] = [
            dict({k: put(f"l{i}_{k}") for k in _KEYS[kind]},
                 norm=put(f"l{i}_norm_w"))
            for i, kind in enumerate(self.pattern)]
        self.embed, self.norm_f, self.lm_head = (
            put("tok_emb"), put("norm_f_w"), put("lm_head"))

    def forward(self, feeds: Dict[str, np.ndarray],
                precision: str = "float32") -> Dict[str, np.ndarray]:
        """One block of rows, layer by layer; ``feeds`` by graph input name."""
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        c = self.config
        eps = float(c["norm_eps"])
        x = self.embed[jnp.asarray(feeds["input_ids"], jnp.int32)]
        for kind, w in zip(self.pattern, self.layers):
            u = _pre_norm(x, w["norm"], eps=eps)
            w = {k: v for k, v in w.items() if k != "norm"}
            if kind == "M":
                mix = mamba_mixer(u, w, heads=c["mamba_num_heads"],
                                  groups=c["n_groups"], eps=eps,
                                  precision=precision)
            elif kind == "*":
                mix = attention_mixer(u, w, heads=c["num_attention_heads"],
                                      kv_heads=c["num_key_value_heads"],
                                      precision=precision)
            else:
                mix = expert_mixer(u, w, top_k=c["num_experts_per_tok"],
                                   scaling=float(c["routed_scaling_factor"]),
                                   first_expert=self.first_expert,
                                   precision=precision)
            x = x + mix
        logits, pooled = _head(x, self.norm_f, self.lm_head, eps=eps,
                               precision=precision)
        return {"logits": np.asarray(logits), "pooled": np.asarray(pooled)}

    def forward_blocks(self, feeds: Dict[str, np.ndarray], block_rows: int,
                       precision: str = "float32") -> Dict[str, np.ndarray]:
        """All rows of ``feeds`` in blocks of ``block_rows`` (the scores of
        more than a row of 4,096 positions would not fit beside the float32
        weights)."""
        n = len(next(iter(feeds.values())))
        parts = [self.forward({k: v[lo:lo + block_rows]
                               for k, v in feeds.items()}, precision)
                 for lo in range(0, n, block_rows)]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
