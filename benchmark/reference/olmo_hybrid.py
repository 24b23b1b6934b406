"""Plain ``olmo_hybrid`` decoder, the reference of ``olmo_hybrid_7b``: a greedy
generator judged, teacher-forced, at the ids the program itself chose.

The layer of Olmo-Hybrid-7B in straightforward ``jax.numpy`` float32, every
matrix product at ``highest`` precision, nothing imported from the program.
Layer ``i`` of ``num_hidden_layers`` is the Olmo 2/3 block, norms after each
sublayer: ``x <- x + RMSNorm(mixer_i(x))``, ``x <- x + RMSNorm(W_down(silu(W_gate
x) * (W_up x)))``. ``mixer_i`` is ``layer_types[i]``: ``full_attention``
(``num_attention_heads`` heads on ``num_key_value_heads``, queries and keys
through an RMSNorm over the whole projection, causal softmax of ``q k^T /
sqrt(head size)``, no bias, no positional term: ``rope_theta`` is null) or
``linear_attention``, the gated delta rule::

    [q, k, v] = silu(conv(x W_qkv))      depthwise, causal, no bias
    g    = -exp(A_log) softplus(x W_a + b_dt);  beta = 2 sigmoid(x W_b)
    q, k = q / |q| / sqrt(dk), k / |k|   a head;  |x| = sqrt(sum x^2 + 1e-6)
    S   <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T
    o_t  = S^T q_t
    out  = (RMSNorm(o) * silu(x W_gate)) W_out     the norm a head over dv

(``2 sigmoid`` is ``linear_allow_neg_eigval``.) After the last layer an
RMSNorm and the untied head. Where the program is clever this is not:

- the rule is a sequential ``lax.scan`` over positions, one position at a
  time, with the state in the PUBLISHED layout ``[rows, H, dk, dv]``; no
  chunks, no kernel, no single-step form, no carried state;
- no cache, no loop: whatever is asked, the answer is ONE full causal forward
  over a row's ``S + G - 1`` ids (the prompt, then the program's own ids but
  the last), the convolution as ``linear_conv_kernel_dim`` shifted products
  over the whole length, the scores materialised;
- a generating program is not re-run: its logits at positions ``S - 1 .. S +
  G - 2`` of that forward are what each of the program's ``G`` choices is
  judged by, so the prompt pass, the states and convolution rows it left,
  the caches and every decode pass are all held to the full forward. The mean
  of the final norm's output over those positions is ``pooled``.

One layer's float32 weights are on the device at a time. Departures from the
published modelling code: seeded weights; no cache object, no fused kernels,
no sampling (the program's own choices are forced). ``precision`` is the
arithmetic of the matrix products and nothing else (``encoder._mm``:
``float32`` the reference proper, ``bfloat16``, and the ``float8``
control); the norms, the convolution, the softplus, the recurrence and the
softmax are float32 as there.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.encoder import PRECISIONS, _mm
# importing it also teaches onnx_initializers BFLOAT16, the file's type
from benchmark.reference.nemotron_h import rms_norm


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


@partial(jax.jit, static_argnames=("heads", "dk", "dv", "neg_eigval", "eps",
                                   "precision"))
def delta_rule(x, w: Dict[str, jax.Array], heads: int, dk: int, dv: int,
               neg_eigval: bool, eps: float, precision: str):
    """The gated delta rule mixer over ``x [n, s, hidden]`` from a zero
    state."""
    rows, s, _ = x.shape
    qkv = _mm("nsh,hk->nsk", x, w["qkv_w"], precision)
    taps = w["conv_w"].shape[-1]  # [C, 1, taps]: tap j weighs position t-taps+1+j
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * w["conv_w"][:, 0, j]
                          for j in range(taps)))
    q = _unit(qkv[..., :heads * dk].reshape(rows, s, heads, dk)) * dk ** -0.5
    k = _unit(qkv[..., heads * dk:2 * heads * dk].reshape(rows, s, heads, dk))
    v = qkv[..., 2 * heads * dk:].reshape(rows, s, heads, dv)
    ab = _mm("nsh,hk->nsk", x, w["ab_w"], precision)
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(ab[..., :heads] + w["dt_b"])
    beta = jax.nn.sigmoid(ab[..., heads:]) * (2.0 if neg_eigval else 1.0)

    def one_position(state, at):  # state [rows, H, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        kv = jnp.sum(state * k_t[..., None], axis=2)
        u = beta_t[..., None] * (v_t - kv)
        state = state + k_t[..., None] * u[:, :, None, :]
        return state, jnp.sum(state * q_t[..., None], axis=2)

    _, o = jax.lax.scan(one_position,
                        jnp.zeros((rows, heads, dk, dv), jnp.float32),
                        [jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)])
    o = rms_norm(jnp.moveaxis(o, 0, 1), w["o_norm_w"], eps)
    gate = _mm("nsh,hk->nsk", x, w["gate_w"], precision)
    o = o * jax.nn.silu(gate.reshape(rows, s, heads, dv))
    return _mm("nsk,kh->nsh", o.reshape(rows, s, -1), w["out_w"], precision)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "precision"))
def attention(x, w: Dict[str, jax.Array], heads: int, kv_heads: int,
              eps: float, precision: str):
    n, s, _ = x.shape
    q = rms_norm(_mm("nsh,hk->nsk", x, w["q_w"], precision), w["q_norm_w"],
                 eps).reshape(n, s, heads, -1)
    k = rms_norm(_mm("nsh,hk->nsk", x, w["k_w"], precision), w["k_norm_w"],
                 eps).reshape(n, s, kv_heads, -1)
    v = _mm("nsh,hk->nsk", x, w["v_w"], precision).reshape(n, s, kv_heads, -1)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    scores = _mm("nqhd,nkhd->nhqk", q, k, precision) / np.sqrt(q.shape[-1])
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    ctx = _mm("nhqk,nkhd->nqhd", probs, v, precision).reshape(n, s, -1)
    return _mm("nsk,kh->nsh", ctx, w["o_w"], precision)


@partial(jax.jit, static_argnames=("precision",))
def feed_forward(x, w: Dict[str, jax.Array], precision: str):
    hidden = jax.nn.silu(_mm("nsh,hf->nsf", x, w["ffn_gate_w"], precision)) \
        * _mm("nsh,hf->nsf", x, w["ffn_up_w"], precision)
    return _mm("nsf,fh->nsh", hidden, w["ffn_down_w"], precision)


@partial(jax.jit, static_argnames=("precision",))
def _head(rows, lm_head, precision: str):
    return _mm("rh,hv->rv", rows, lm_head, precision)


_DELTA = ("qkv_w", "conv_w", "ab_w", "a_log", "dt_b", "gate_w", "o_norm_w",
          "out_w")
_ATTENTION = ("q_w", "k_w", "v_w", "q_norm_w", "k_norm_w", "o_w")
_FFN = ("ffn_gate_w", "ffn_up_w", "ffn_down_w")


class Reference:
    """The forward pass of one configuration; ``final_norm`` is the whole of
    it, ``replay`` asks it the check's questions."""

    def __init__(self, config: dict, initializers: Dict[str, np.ndarray]):
        self.config, self.weights = config, initializers
        self.layers = int(config["num_hidden_layers"])
        if len(config["layer_types"]) != self.layers:
            raise ValueError(f"{len(config['layer_types'])} layer types for "
                             f"{self.layers} layers")

    def _put(self, name: str):  # a bfloat16 tensor is widened on the device
        return jnp.asarray(np.ascontiguousarray(self.weights[name])
                           ).astype(jnp.float32)

    def _layer(self, i: int, x, precision: str):
        c = self.config
        eps = float(c["rms_norm_eps"])
        if c["layer_types"][i] == "full_attention":
            w = {k: self._put(f"l{i}_{k}") for k in _ATTENTION}
            mix = attention(x, w, heads=c["num_attention_heads"],
                            kv_heads=c["num_key_value_heads"], eps=eps,
                            precision=precision)
        else:
            w = {k: self._put(f"l{i}_{k}") for k in _DELTA}
            mix = delta_rule(x, w, heads=c["linear_num_value_heads"],
                             dk=c["linear_key_head_dim"],
                             dv=c["linear_value_head_dim"],
                             neg_eigval=bool(c["linear_allow_neg_eigval"]),
                             eps=eps, precision=precision)
        x = x + rms_norm(mix, self._put(f"l{i}_norm_mix_w"), eps)
        w = {k: self._put(f"l{i}_{k}") for k in _FFN}
        return x + rms_norm(feed_forward(x, w, precision=precision),
                            self._put(f"l{i}_norm_ffn_w"), eps)

    def final_norm(self, ids: np.ndarray, precision: str = "float32"):
        """``ids [n, length]`` -> the final norm's output ``[n, length,
        hidden]``, on the device: one causal forward."""
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        x = jnp.asarray(np.asarray(self.weights["tok_emb"])[ids]
                        ).astype(jnp.float32)
        for i in range(self.layers):
            x = self._layer(i, x, precision)
        return rms_norm(x, self._put("norm_f_w"),
                        float(self.config["rms_norm_eps"]))

    def replay(self, prompts: np.ndarray, tokens: np.ndarray,
               precision: str = "float32", block_rows: int = 0
               ) -> Dict[str, np.ndarray]:
        """What the reference gives where the program chose, ``block_rows``
        rows a forward (0: all in one): ``logits [row, G, vocab]`` float32 at
        positions ``S - 1 .. S + G - 2`` of one causal forward over the
        prompt and the program's ids but the last, and ``pooled [row,
        hidden]``, the mean of the final norm's output over those
        positions."""
        prompts = np.asarray(prompts, np.int64)
        tokens = np.asarray(tokens, np.int64)
        s, g = prompts.shape[1], tokens.shape[1]
        lm_head = self._put("lm_head")
        out = {"logits": [], "pooled": []}
        for lo in range(0, len(prompts), block_rows or len(prompts)):
            rows = slice(lo, lo + (block_rows or len(prompts)))
            ids = np.concatenate([prompts[rows], tokens[rows, :-1]], axis=1)
            chosen_from = self.final_norm(ids, precision)[:, s - 1:]
            out["pooled"].append(np.asarray(jnp.mean(chosen_from, axis=1)))
            out["logits"].append(np.asarray(_head(
                chosen_from.reshape(-1, chosen_from.shape[-1]), lm_head,
                precision=precision)).reshape(len(ids), g, -1))
        return {k: np.concatenate(v) for k, v in out.items()}
