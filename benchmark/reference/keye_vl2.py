"""Plain ``keye_vl2`` decoder, the reference of ``keye_vl2_30b_a3b``: a greedy
generator judged, teacher-forced, at the ids the program itself chose.

The language model of Keye-VL-2.0-30B-A3B in straightforward ``jax.numpy``
float32, every matrix product at ``highest`` precision, nothing imported from
the program. Layer ``i`` over ``h``, ``x = RMSNorm(h)``::

    q = RoPE(RMSNorm_head(x W_q)),  k = RoPE(RMSNorm_head(x W_k)),  v = x W_v
    qI = RoPE(x W_qI),  kI = RoPE(LayerNorm(x W_kI)),  w = x W_w / sqrt(H_I dI)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])          s <= t
    S_t = lax.top_k(I[t, :t + 1], min(t + 1, topk))           ties: lower s
    o[t, h] = softmax over S_t of q[t, h] . k[s, g(h)] / sqrt(d), times v
    h' = h + o W_o;  y = RMSNorm(h')
    h'' = h' + sum over the top_k of softmax(y W_r), renormalised, of
          p_e (silu(y G_e) * (y U_e)) D_e

``RoPE`` is M-RoPE as the Qwen2-VL family writes it: the ``d / 2``
frequencies ``theta^(-2j / d)`` in ``mrope_section`` groups, each group
turned by its own position (time, height, width), rotate-half. With text
alone the three positions are the token's index, so this is 1-D rotary; the
index heads are turned the same way over their own ``dI``. After the last
layer an RMSNorm and the untied head. Where the program is clever this is
not:

- the indexer's scores are every (query, key) score of a block of queries,
  written out, and the set is ``lax.top_k`` of them; attention is the block's
  queries against EVERY key under the set's mask, the scores written out: no
  bisection, no kernel, no gather of rows;
- the experts are a loop over experts, each over every token
  (``sdar_moe.experts``);
- no cache, no loop: whatever is asked, the answer is ONE full causal
  forward over a row's ``S + G - 1`` ids (the prompt, then the program's own
  ids but the last); its logits at positions ``S - 1 .. S + G - 2`` judge
  each of the program's ``G`` choices, so the prompt pass, the three caches
  and every decode pass are held to the full forward. The mean of the final
  norm's output over those positions is ``pooled``, over the first ``topk``
  positions (where a query keeps every key) ``prompt_pooled``. The last
  layer's queries and experts run only at the blocks of positions read.

One layer's float32 weights are on the device at a time. Departures from the
published description: seeded weights; the indexer's keys are float32 (the
published indexer keeps them in FP8 behind a Hadamard rotation, which is
orthogonal and changes no score in exact arithmetic); the vision tower is
not built (the traffic is text). ``precision`` is the arithmetic of
the matrix products and nothing else (``encoder._mm``: ``float32`` the
reference proper, ``bfloat16``, and the ``float8`` control); the norms, the
rotation, the ReLU, the selection and the softmax are float32.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.encoder import PRECISIONS, _mm
# importing it also teaches onnx_initializers BFLOAT16, the file's type
from benchmark.reference.sdar_moe import _head, experts, rms_norm

QUERY_BLOCK = 64  # queries whose scores against every key exist at a time


def mrope_angles(positions: np.ndarray, theta: float, dim: int,
                 sections: Sequence[int]) -> np.ndarray:
    """M-RoPE's angles ``[length, dim / 2]`` for ``positions [3, length]``
    (time, height, width): frequency ``j`` of section ``c`` turns by
    ``positions[c]``."""
    freq = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    which = np.repeat(np.arange(len(sections)), sections)
    if len(which) != dim // 2:
        raise ValueError(f"mrope_section {list(sections)} does not sum to "
                         f"{dim // 2}")
    return positions[which].T.astype(np.float64) * freq[None, :]


def rotate_half(x, cos, sin):
    """``x [n, s, heads, d]`` turned by ``cos``/``sin [s, d / 2]``."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_norm(x, weight, bias, eps: float):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


@partial(jax.jit, static_argnames=("heads", "kv_heads", "index_heads",
                                   "topk", "eps", "precision"))
def sparse_attention(u, w: Dict[str, jax.Array], cos, sin, index_cos,
                     index_sin, starts, heads: int, kv_heads: int,
                     index_heads: int, topk: int, eps: float,
                     precision: str):
    """The attention of one layer over ``u [n, length, hidden]``, the
    length a multiple of ``QUERY_BLOCK``, for the queries of the blocks
    that begin at ``starts``: ``[n, len(starts) x QUERY_BLOCK, hidden]``."""
    n, length, _ = u.shape
    q = _mm("nsh,hk->nsk", u, w["q_w"], precision).reshape(n, length, heads,
                                                            -1)
    k = _mm("nsh,hk->nsk", u, w["k_w"], precision).reshape(n, length,
                                                            kv_heads, -1)
    v = _mm("nsh,hk->nsk", u, w["v_w"], precision).reshape(n, length,
                                                            kv_heads, -1)
    q = rotate_half(rms_norm(q, w["q_norm_w"], eps), cos, sin)
    k = rotate_half(rms_norm(k, w["k_norm_w"], eps), cos, sin)
    d, rep = q.shape[-1], heads // kv_heads
    q_index = rotate_half(_mm("nsh,hk->nsk", u, w["index_q_w"], precision
                              ).reshape(n, length, index_heads, -1),
                          index_cos, index_sin)
    k_index = rotate_half(_layer_norm(
        _mm("nsh,hk->nsk", u, w["index_k_w"], precision),
        w["index_k_norm_w"], w["index_k_norm_b"], 1e-6)[:, :, None, :],
        index_cos, index_sin)[:, :, 0]
    weights = _mm("nsh,hk->nsk", u, w["index_w_w"], precision) \
        * (index_heads * k_index.shape[-1]) ** -0.5
    keys = jnp.arange(length)
    kept = min(topk, length)

    def one_block(lo):
        rows = jnp.arange(n)[:, None, None]
        at = lo + jnp.arange(QUERY_BLOCK)
        qi = jax.lax.dynamic_slice_in_dim(q_index, lo, QUERY_BLOCK, 1)
        wi = jax.lax.dynamic_slice_in_dim(weights, lo, QUERY_BLOCK, 1)
        index = _mm("nqh,nqhk->nqk", wi, jax.nn.relu(
            _mm("nqhd,nkd->nqhk", qi, k_index, precision)), precision)
        seen = keys[None, :] <= at[:, None]                       # [q, L]
        _, picks = jax.lax.top_k(jnp.where(seen, index, -jnp.inf), kept)
        valid = jnp.arange(kept)[None, :] < jnp.minimum(at + 1, topk)[:, None]
        chosen = jnp.zeros((n, QUERY_BLOCK, length), bool).at[
            rows, jnp.arange(QUERY_BLOCK)[None, :, None], picks].set(
                jnp.broadcast_to(valid, picks.shape))
        qb = jax.lax.dynamic_slice_in_dim(q, lo, QUERY_BLOCK, 1).reshape(
            n, QUERY_BLOCK, kv_heads, rep, d)
        scores = _mm("nqgrd,nkgd->ngrqk", qb, k, precision) / np.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(chosen[:, None, None], scores, -jnp.inf), axis=-1)
        return _mm("ngrqk,nkgd->nqgrd", probs, v, precision).reshape(
            n, QUERY_BLOCK, heads * d)

    ctx = jax.lax.map(one_block, starts)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(n, -1, heads * d)
    return _mm("nsk,kh->nsh", ctx, w["o_w"], precision)


@partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, eps: float):
    return rms_norm(x, weight, eps)


_ATTENTION = ("q_w", "k_w", "v_w", "q_norm_w", "k_norm_w", "o_w",
              "index_q_w", "index_k_w", "index_k_norm_w", "index_k_norm_b",
              "index_w_w")
_EXPERTS = ("router_w", "experts_gate", "experts_up", "experts_down")


class Reference:
    """The forward pass of one configuration; ``final_norm`` is the whole of
    it, ``replay`` asks it the check's questions."""

    def __init__(self, config: dict, initializers: Dict[str, np.ndarray]):
        self.config, self.weights = config, initializers
        self.layers = int(config["num_hidden_layers"])

    def _put(self, name: str):  # a bfloat16 tensor is widened on the device
        return jnp.asarray(np.ascontiguousarray(self.weights[name])
                           ).astype(jnp.float32)

    def _angles(self, length: int, dim: int, sections: Sequence[int]):
        positions = np.broadcast_to(np.arange(length), (3, length))
        angles = mrope_angles(positions, float(self.config["rope_theta"]),
                              dim, sections)
        return (jnp.asarray(f(angles), jnp.float32) for f in (np.cos, np.sin))

    def final_norm(self, ids: np.ndarray, precision: str = "float32",
                   blocks=None):
        """``ids [n, length]`` -> the final norm's output ``[n, length,
        hidden]``, on the device: one causal forward. With ``blocks``, the
        first positions of blocks of ``QUERY_BLOCK``, the last layer's
        queries and experts run at those blocks' positions alone (every
        earlier layer's at every position, whose keys the last reads), and
        the output is theirs, ``[n, len(blocks) x QUERY_BLOCK, hidden]``."""
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        c, sa = self.config, self.config["sa_config"]
        eps = float(c["rms_norm_eps"])
        n, length = ids.shape
        padded = -(-length // QUERY_BLOCK) * QUERY_BLOCK  # masked, unread
        ids = np.pad(ids, ((0, 0), (0, padded - length)))
        sections = c["rope_scaling"]["mrope_section"]
        cos, sin = self._angles(padded, int(c["head_dim"]), sections)
        index_dim = int(sa["indexer_head_dim"])
        # the index heads' own frequencies, in one section
        index_cos, index_sin = self._angles(padded, index_dim,
                                            [index_dim // 2, 0, 0])
        x = jnp.asarray(np.asarray(self.weights["tok_emb"])[ids]
                        ).astype(jnp.float32)
        every = np.arange(0, padded, QUERY_BLOCK)
        for i in range(self.layers):
            starts = every if blocks is None or i < self.layers - 1 \
                else np.asarray(blocks)
            rows = (starts[:, None] + np.arange(QUERY_BLOCK)).reshape(-1)
            w = {k: self._put(f"l{i}_{k}") for k in _ATTENTION}
            u = _norm(x, self._put(f"l{i}_norm_in_w"), eps=eps)
            x = x[:, rows] + sparse_attention(
                u, w, cos, sin, index_cos, index_sin, jnp.asarray(starts),
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"],
                index_heads=int(sa["indexer_num_heads"]),
                topk=int(sa["topk"]), eps=eps, precision=precision)
            w = {k: self._put(f"l{i}_{k}") for k in _EXPERTS}
            u = _norm(x, self._put(f"l{i}_norm_post_w"), eps=eps)
            x = x + experts(u, w, top_k=c["num_experts_per_tok"],
                            precision=precision)
        out = _norm(x, self._put("norm_f_w"), eps=eps)
        return out[:, :length] if blocks is None else out

    def replay(self, prompts: np.ndarray, tokens: np.ndarray,
               precision: str = "float32", block_rows: int = 0
               ) -> Dict[str, np.ndarray]:
        """What the reference gives where the program chose, ``block_rows``
        rows a forward (0: all in one): ``logits [row, G, vocab]`` float32 at
        positions ``S - 1 .. S + G - 2`` of one causal forward over the
        prompt and the program's ids but the last, ``pooled [row,
        hidden]``, the mean of the final norm's output over those
        positions, and ``prompt_pooled [row, hidden]``, its mean over the
        first ``topk`` positions (where every key is kept)."""
        prompts = np.asarray(prompts, np.int64)
        tokens = np.asarray(tokens, np.int64)
        s, g = prompts.shape[1], tokens.shape[1]
        dense = min(s, int(self.config["sa_config"]["topk"]))
        # the blocks holding the positions read: the last layer runs there
        blocks = np.unique(np.concatenate([
            np.arange(0, dense), np.arange(s - 1, s + g - 1)]) // QUERY_BLOCK
        ) * QUERY_BLOCK
        at = {int(p): j for j, p in enumerate(
            (blocks[:, None] + np.arange(QUERY_BLOCK)).reshape(-1))}
        lm_head = self._put("lm_head")
        out = {"logits": [], "pooled": [], "prompt_pooled": []}
        for lo in range(0, len(prompts), block_rows or len(prompts)):
            rows = slice(lo, lo + (block_rows or len(prompts)))
            ids = np.concatenate([prompts[rows], tokens[rows, :-1]], axis=1)
            final = self.final_norm(ids, precision, blocks)
            chosen_from = final[:, [at[p] for p in range(s - 1, s + g - 1)]]
            out["prompt_pooled"].append(np.asarray(jnp.mean(
                final[:, [at[p] for p in range(dense)]], axis=1)))
            out["pooled"].append(np.asarray(jnp.mean(chosen_from, axis=1)))
            out["logits"].append(np.asarray(_head(
                chosen_from.reshape(-1, chosen_from.shape[-1]), lm_head,
                precision=precision)).reshape(len(ids), g, -1))
        return {k: np.concatenate(v) for k, v in out.items()}
