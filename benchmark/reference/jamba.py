"""Plain ``jamba`` decoder, the reference of ``jamba2_3b``: a greedy generator
judged, teacher-forced, at the ids the program itself chose.

The layer of AI21-Jamba2-3B in straightforward ``jax.numpy`` float32, every
matrix product at ``highest`` precision, nothing imported from the program.
Layer ``i`` of ``num_hidden_layers``: ``x <- x + mixer_i(RMSNorm(x))``, ``x
<- x + W_down(silu(W_gate u) * (W_up u))``, ``u = RMSNorm(x)`` (``num_experts``
1: no router). ``mixer_i`` is attention where ``i % attn_layer_period ==
attn_layer_offset`` (``num_attention_heads`` query heads on
``num_key_value_heads`` key-value heads, causal softmax of ``q k^T /
sqrt(head size)``, no bias, no positional term) and Mamba-1 elsewhere::

    [x, z] = u W_in
    x      = silu(conv(x))        depthwise, causal, mamba_d_conv wide, + bias
    [dt_r, B, C] = x W_x          each through an RMSNorm of its own
    delta  = softplus(dt_r W_dt + b_dt);   A = -exp(A_log)
    s[c, j] <- exp(delta[t, c] A[c, j]) s[c, j] + delta[t, c] B[t, j] x[t, c]
    y[t, c] = sum_j C[t, j] s[c, j] + D[c] x[t, c]
    out    = (y * silu(z)) W_out

After the last layer an RMSNorm; the logits are its output times the
embedding's transpose (``tie_word_embeddings``). Where the program is clever
this is not:

- the recurrence is a sequential ``lax.scan`` over positions with the state in
  the PUBLISHED layout ``[rows, d, n]``, one position at a time; no kernel, no
  single-step form, no carried state;
- no cache, no loop: whatever is asked, the answer is ONE full causal forward
  over a row's ``S + G - 1`` ids (the prompt, then the program's own ids but
  the last), the convolution as ``mamba_d_conv`` shifted products over the
  whole length, the scores materialised;
- a generating program is not re-run: its logits at positions ``S - 1 .. S +
  G - 2`` of that forward are what each of the program's ``G`` choices is
  judged by, so the prompt pass, the state and the convolution rows it left,
  the caches and every decode pass are all held to the full forward. The mean
  of the final norm's output over those positions is ``pooled``.

One layer's float32 weights are on the device at a time. Departures from the
published modelling code (``modeling_jamba.py``): seeded weights; no cache
object, no fused kernels, no sampling (the program's own choices are forced).
``precision`` is the arithmetic of the matrix products and nothing else
(``encoder._mm``: ``float32`` the reference proper, ``bfloat16``, and the
``float8`` control); the norms, the convolution, the softplus, the recurrence
and the softmax are float32 as there.
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


@partial(jax.jit, static_argnames=("eps", "precision"))
def mamba(u, w: Dict[str, jax.Array], eps: float, precision: str):
    """The Mamba-1 mixer over ``u [n, s, hidden]`` from a zero state."""
    rows, s, _ = u.shape
    xz = _mm("nsh,hk->nsk", u, w["in_w"], precision)
    d = xz.shape[-1] // 2
    x, z = xz[..., :d], xz[..., d:]
    taps = w["conv_w"].shape[-1]  # [d, 1, taps]: tap k weighs position t-taps+1+k
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = sum(padded[:, k:k + s] * w["conv_w"][:, 0, k] for k in range(taps)) \
        + w["conv_b"]
    x = jax.nn.silu(x)
    dbc = _mm("nsd,dk->nsk", x, w["x_w"], precision)
    rank, n = w["dt_norm_w"].shape[0], w["b_norm_w"].shape[0]
    dt_r = rms_norm(dbc[..., :rank], w["dt_norm_w"], eps)
    b = rms_norm(dbc[..., rank:rank + n], w["b_norm_w"], eps)
    c = rms_norm(dbc[..., rank + n:], w["c_norm_w"], eps)
    delta = jax.nn.softplus(_mm("nsr,rd->nsd", dt_r, w["dt_w"], precision)
                            + w["dt_b"])
    a = -jnp.exp(w["a_log"])  # [d, n]

    def one_position(state, at):  # state [rows, d, n], the published layout
        delta_t, x_t, b_t, c_t = at
        state = jnp.exp(delta_t[:, :, None] * a) * state \
            + (delta_t * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(one_position, jnp.zeros((rows, d, n), jnp.float32),
                        [jnp.moveaxis(v, 1, 0) for v in (delta, x, b, c)])
    y = jnp.moveaxis(y, 0, 1) + w["d"] * x
    return _mm("nsd,dh->nsh", y * jax.nn.silu(z), w["out_w"], precision)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "precision"))
def attention(u, w: Dict[str, jax.Array], heads: int, kv_heads: int,
              precision: str):
    n, s, _ = u.shape
    q = _mm("nsh,hk->nsk", u, w["q_w"], precision).reshape(n, s, heads, -1)
    k = _mm("nsh,hk->nsk", u, w["k_w"], precision).reshape(n, s, kv_heads, -1)
    v = _mm("nsh,hk->nsk", u, w["v_w"], precision).reshape(n, s, kv_heads, -1)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    scores = _mm("nqhd,nkhd->nhqk", q, k, precision) / np.sqrt(q.shape[-1])
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    ctx = _mm("nhqk,nkhd->nqhd", probs, v, precision).reshape(n, s, -1)
    return _mm("nsk,kh->nsh", ctx, w["o_w"], precision)


@partial(jax.jit, static_argnames=("precision",))
def feed_forward(u, w: Dict[str, jax.Array], precision: str):
    hidden = jax.nn.silu(_mm("nsh,hf->nsf", u, w["ffn_gate_w"], precision)) \
        * _mm("nsh,hf->nsf", u, w["ffn_up_w"], precision)
    return _mm("nsf,fh->nsh", hidden, w["ffn_down_w"], precision)


@partial(jax.jit, static_argnames=("precision",))
def _tied_head(rows, tok_emb, precision: str):
    return _mm("rh,vh->rv", rows, tok_emb, precision)


_MAMBA = ("in_w", "conv_w", "conv_b", "x_w", "dt_norm_w", "b_norm_w",
          "c_norm_w", "dt_w", "dt_b", "a_log", "d", "out_w")
_ATTENTION = ("q_w", "k_w", "v_w", "o_w")
_FFN = ("ffn_gate_w", "ffn_up_w", "ffn_down_w")


class Reference:
    """The forward pass of one configuration; ``final_norm`` is the whole of
    it, ``replay`` asks it the check's questions."""

    def __init__(self, config: dict, initializers: Dict[str, np.ndarray]):
        self.config, self.weights = config, initializers
        self.layers = int(config["num_hidden_layers"])

    def _put(self, name: str):  # a bfloat16 tensor is widened on the device
        return jnp.asarray(np.ascontiguousarray(self.weights[name])
                           ).astype(jnp.float32)

    def _layer(self, i: int, x, precision: str):
        c = self.config
        eps = float(c["rms_norm_eps"])
        u = rms_norm(x, self._put(f"l{i}_norm_in_w"), eps)
        if i % int(c["attn_layer_period"]) == int(c["attn_layer_offset"]):
            w = {k: self._put(f"l{i}_{k}") for k in _ATTENTION}
            x = x + attention(u, w, heads=c["num_attention_heads"],
                              kv_heads=c["num_key_value_heads"],
                              precision=precision)
        else:
            w = {k: self._put(f"l{i}_{k}") for k in _MAMBA}
            x = x + mamba(u, w, eps=eps, precision=precision)
        u = rms_norm(x, self._put(f"l{i}_norm_post_w"), eps)
        w = {k: self._put(f"l{i}_{k}") for k in _FFN}
        return x + feed_forward(u, w, precision=precision)

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
        tok_emb = self._put("tok_emb")
        out = {"logits": [], "pooled": []}
        for lo in range(0, len(prompts), block_rows or len(prompts)):
            rows = slice(lo, lo + (block_rows or len(prompts)))
            ids = np.concatenate([prompts[rows], tokens[rows, :-1]], axis=1)
            chosen_from = self.final_norm(ids, precision)[:, s - 1:]
            out["pooled"].append(np.asarray(jnp.mean(chosen_from, axis=1)))
            out["logits"].append(np.asarray(_tied_head(
                chosen_from.reshape(-1, chosen_from.shape[-1]), tok_emb,
                precision=precision)).reshape(len(ids), g, -1))
        return {k: np.concatenate(v) for k, v in out.items()}
