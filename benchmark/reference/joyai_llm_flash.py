"""Plain ``joyai_llm_flash`` decoder, the reference of ``joyai_llm_flash``: a
greedy generator judged, teacher-forced, at the ids the program itself chose.

The layer of JoyAI-LLM-Flash (the DeepSeek-V3 block: pre-norm multi-head
latent attention, a leading dense gated feed-forward, then pre-norm sigmoid
routers with a correction bias for the choice only, the chosen scores
renormalised and scaled, gated routed experts plus a gated shared expert) in
straightforward ``jax.numpy`` float32, every matrix product at ``highest``
precision, nothing imported from the program. The attention is the EXPANDED,
published form and no other:

    c_q = RMSNorm(u W_dq);  q = c_q W_uq = heads x [q_nope; q_rope]
    [c_kv; k_r] = u W_dkv;  c_kv <- RMSNorm(c_kv)
    q_rope <- R_t(q_rope);  k_rope = R_t(k_r), shared by every head
    k = [c_kv W_uk; k_rope];  v = c_kv W_uv
    softmax(q k^T / sqrt(nope + rope), causal) v, then W_o

with ``R_t`` turning the NEIGHBOURING pairs ``(2j, 2j + 1)`` of the ``rope``
numbers by ``t theta^(-2j / rope)``. Where the program is clever this is not:

- no cache, no loop, no absorbed product, no kernel: whatever is asked, the
  answer is ONE full causal forward over a row's ``S + G - 1`` ids (the
  prompt, then the program's own ids but the last), keys and values expanded
  for every head at every position, the scores materialised a block of
  queries at a time so that a row fits;
- the experts are a loop over the HELD experts, each over every token,
  weighted by a mask of the picks that chose it; a pick of an expert held
  elsewhere adds nothing, as in the program;
- a generating program is not re-run: its logits at positions ``S - 1 .. S +
  G - 2`` of that forward are what each of the program's ``G`` choices is
  judged by, so the prompt pass, the latents it cached and every decode pass
  through the absorbed form are all held to the published form. The mean of
  the final norm's output over those positions is ``pooled``.

One layer's float32 weights are on the device at a time, the head's after
them. With the prediction module in the file (``mtp_eh_w``), ``replay`` also
gives its logits: ``[RMSNorm(Emb(x_{t+1})); RMSNorm(h_t)] W_eh`` through one
more expert block, a norm and the main head.

Departures from the published modelling code (``modeling_deepseek.py`` of the
family): no key-value cache, no flash attention, no fused expert kernels, no
sampling (the program's own choices are forced), no permutation of the rotary
pairs into halves (it changes no score); ``n_group`` and ``topk_group`` are 1,
so no group of experts is excluded; the router's sigmoid, the norms, the
softmax and the rotation are float32 as there. ``precision`` is the arithmetic
of the matrix products and nothing else (``encoder._mm``: ``float32`` the
reference proper, ``bfloat16``, and the ``float8`` control).
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.encoder import PRECISIONS, _mm
# importing it also teaches onnx_initializers BFLOAT16, the file's type
from benchmark.reference.sdar_moe import _head, _norm, rms_norm

QUERY_BLOCK = 512  # queries whose scores against every key exist at a time


def rotate_pairs(x, cos, sin):
    """``x [n, s, heads, rope]`` turned by the angles ``cos``/``sin [s,
    rope / 2]``: number ``2j`` pairs with its neighbour ``2j + 1``."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("heads", "nope", "eps", "precision"))
def attention(u, w: Dict[str, jax.Array], cos, sin, heads: int, nope: int,
              eps: float, precision: str):
    n, s, _ = u.shape
    rank = w["kv_norm_w"].shape[0]
    c_q = rms_norm(_mm("nsh,hr->nsr", u, w["dq_w"], precision),
                   w["q_norm_w"], eps)
    q = _mm("nsr,rk->nsk", c_q, w["uq_w"], precision).reshape(n, s, heads, -1)
    kv = _mm("nsh,hr->nsr", u, w["dkv_w"], precision)
    c_kv = rms_norm(kv[..., :rank], w["kv_norm_w"], eps)
    k_rope = rotate_pairs(kv[..., None, rank:], cos, sin)  # one shared head
    q = jnp.concatenate([q[..., :nope],
                         rotate_pairs(q[..., nope:], cos, sin)], -1)
    k_nope = _mm("nsr,rk->nsk", c_kv, w["uk_w"], precision
                 ).reshape(n, s, heads, nope)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (n, s, heads, k_rope.shape[-1]))],
        -1)
    v = _mm("nsr,rk->nsk", c_kv, w["uv_w"], precision).reshape(n, s, heads, -1)
    keys = jnp.arange(s)
    ctx = []
    for lo in range(0, s, QUERY_BLOCK):  # a block of queries, every key
        hi = min(s, lo + QUERY_BLOCK)
        scores = _mm("nqhd,nkhd->nhqk", q[:, lo:hi], k, precision) \
            / np.sqrt(q.shape[-1])
        seen = keys[None, :] <= jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        ctx.append(_mm("nhqk,nkhd->nqhd", probs, v, precision))
    ctx = jnp.concatenate(ctx, axis=1).reshape(n, s, -1)
    return _mm("nsk,kh->nsh", ctx, w["o_w"], precision)


def _gated(u, gate, up, down, precision: str):
    hidden = jax.nn.silu(_mm("nsh,hf->nsf", u, gate, precision)) \
        * _mm("nsh,hf->nsf", u, up, precision)
    return _mm("nsf,fh->nsh", hidden, down, precision)


@partial(jax.jit, static_argnames=("precision",))
def dense_ffn(u, w: Dict[str, jax.Array], precision: str):
    return _gated(u, w["ffn_gate_w"], w["ffn_up_w"], w["ffn_down_w"],
                  precision)


@partial(jax.jit, static_argnames=("top_k", "scaling", "first_expert",
                                   "precision"))
def experts(u, w: Dict[str, jax.Array], top_k: int, scaling: float,
            first_expert: int, precision: str):
    """Sigmoid scores over every expert; the ``top_k`` largest of scores +
    bias are chosen, the chosen scores over their sum, scaled; each HELD
    expert in turn over every token; the shared expert once."""
    scores = jax.nn.sigmoid(_mm("nsh,he->nse", u, w["router_w"], precision))
    _, picks = jax.lax.top_k(scores + w["router_bias"], top_k)
    chosen = jnp.take_along_axis(scores, picks, axis=-1)
    weights = scaling * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)

    def one_expert(total, expert):
        index, gate, up, down = expert
        share = jnp.sum(jnp.where(picks == index, weights, 0.0), axis=-1)
        return total + share[..., None] * _gated(u, gate, up, down,
                                                 precision), None

    held = w["experts_up"].shape[0]
    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (first_expert + jnp.arange(held), w["experts_gate"],
         w["experts_up"], w["experts_down"]))
    return total + _gated(u, w["shared_gate_w"], w["shared_up_w"],
                          w["shared_down_w"], precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _draft_input(tok, final, w_e, w_h, w_eh, eps: float, precision: str):
    both = jnp.concatenate([rms_norm(tok, w_e, eps),
                            rms_norm(final, w_h, eps)], -1)
    return _mm("nsk,kh->nsh", both, w_eh, precision)


_ATTENTION = ("dq_w", "q_norm_w", "uq_w", "dkv_w", "kv_norm_w", "uk_w",
              "uv_w", "o_w")
_DENSE = ("ffn_gate_w", "ffn_up_w", "ffn_down_w")
_EXPERTS = ("router_w", "router_bias", "experts_gate", "experts_up",
            "experts_down", "shared_gate_w", "shared_up_w", "shared_down_w")


class Reference:
    """The forward pass of one configuration; ``final_norm`` is the whole of
    it, ``replay`` asks it the check's questions."""

    def __init__(self, config: dict, initializers: Dict[str, np.ndarray]):
        self.config, self.weights = config, initializers
        kwargs = config.get("builder_kwargs", {})
        self.generate = int(kwargs["generate"])
        self.first_expert = int(kwargs.get("first_expert", 0))
        self.layers = int(config["num_hidden_layers"])
        self.has_drafts = "mtp_eh_w" in initializers

    def _put(self, name: str):  # a bfloat16 tensor is widened on the device
        return jnp.asarray(np.ascontiguousarray(self.weights[name])
                           ).astype(jnp.float32)

    def _angles(self, length: int):
        c = self.config
        rope = int(c["qk_rope_head_dim"])
        angles = np.arange(length, dtype=np.float64)[:, None] * (
            float(c["rope_theta"]) ** (-np.arange(0, rope, 2,
                                                  dtype=np.float64) / rope)
        )[None, :]
        return (jnp.asarray(f(angles), jnp.float32) for f in (np.cos, np.sin))

    def _block(self, i: int, x, cos, sin, precision: str):
        """Block ``i`` (0 the dense one) over ``x [n, length, hidden]``."""
        c = self.config
        eps = float(c["rms_norm_eps"])
        w = {k: self._put(f"l{i}_{k}") for k in _ATTENTION}
        u = _norm(x, self._put(f"l{i}_norm_in_w"), eps=eps)
        x = x + attention(u, w, cos, sin, heads=c["num_attention_heads"],
                          nope=c["qk_nope_head_dim"], eps=eps,
                          precision=precision)
        u = _norm(x, self._put(f"l{i}_norm_post_w"), eps=eps)
        if i < int(c.get("first_k_dense_replace", 1)):
            w = {k: self._put(f"l{i}_{k}") for k in _DENSE}
            return x + dense_ffn(u, w, precision=precision)
        w = {k: self._put(f"l{i}_{k}") for k in _EXPERTS}
        return x + experts(u, w, top_k=c["num_experts_per_tok"],
                           scaling=float(c["routed_scaling_factor"]),
                           first_expert=self.first_expert,
                           precision=precision)

    def final_norm(self, ids: np.ndarray, precision: str = "float32"):
        """``ids [n, length]`` -> the final norm's output ``[n, length,
        hidden]``, on the device: one causal forward."""
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        cos, sin = self._angles(ids.shape[1])
        x = jnp.asarray(np.asarray(self.weights["tok_emb"])[ids]
                        ).astype(jnp.float32)
        for i in range(self.layers):
            x = self._block(i, x, cos, sin, precision)
        return _norm(x, self._put("norm_f_w"),
                     eps=float(self.config["rms_norm_eps"]))

    def draft_norm(self, final, next_ids: np.ndarray,
                   precision: str = "float32"):
        """The prediction module over ``final [n, length, hidden]`` (the main
        stack's final-norm output) and each position's next id: its own
        final norm's output, whose head gives the id after next."""
        eps = float(self.config["rms_norm_eps"])
        cos, sin = self._angles(next_ids.shape[1])
        tok = jnp.asarray(np.asarray(self.weights["tok_emb"])[next_ids]
                          ).astype(jnp.float32)
        x = _draft_input(tok, final, self._put("mtp_norm_e_w"),
                         self._put("mtp_norm_h_w"), self._put("mtp_eh_w"),
                         eps=eps, precision=precision)
        x = self._block(self.layers, x, cos, sin, precision)
        return _norm(x, self._put("mtp_norm_s_w"), eps=eps)

    def replay(self, prompts: np.ndarray, tokens: np.ndarray,
               precision: str = "float32", block_rows: int = 0
               ) -> Dict[str, np.ndarray]:
        """What the reference gives where the program chose, ``block_rows``
        rows a forward (0: all in one): ``logits [row, G, vocab]`` float32 at
        positions ``S - 1 .. S + G - 2`` of one causal forward over the
        prompt and the program's ids but the last, ``pooled [row, hidden]``
        the mean of the final norm's output over those positions and, with
        the prediction module in the file, ``draft_logits [row, G, vocab]``
        (slot ``j`` guesses id ``j + 1``)."""
        prompts = np.asarray(prompts, np.int64)
        tokens = np.asarray(tokens, np.int64)
        s, g = prompts.shape[1], tokens.shape[1]
        lm_head = self._put("lm_head")
        out = {"logits": [], "pooled": []}
        if self.has_drafts:
            out["draft_logits"] = []
        for lo in range(0, len(prompts), block_rows or len(prompts)):
            rows = slice(lo, lo + (block_rows or len(prompts)))
            ids = np.concatenate([prompts[rows], tokens[rows, :-1]], axis=1)
            final = self.final_norm(ids, precision)
            chosen_from = final[:, s - 1:]  # [rows, G, hidden]
            out["pooled"].append(np.asarray(jnp.mean(chosen_from, axis=1)))
            out["logits"].append(np.asarray(_head(
                chosen_from.reshape(-1, chosen_from.shape[-1]), lm_head,
                precision=precision)).reshape(len(ids), g, -1))
            if self.has_drafts:
                next_ids = np.concatenate([prompts[rows, 1:], tokens[rows]],
                                          axis=1)
                drafted = self.draft_norm(final, next_ids, precision)[:, s - 1:]
                out["draft_logits"].append(np.asarray(_head(
                    drafted.reshape(-1, drafted.shape[-1]), lm_head,
                    precision=precision)).reshape(len(ids), g, -1))
        return {k: np.concatenate(v) for k, v in out.items()}
