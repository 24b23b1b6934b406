"""Plain ``sdar_moe`` decoder, the reference of ``sdar_30b_a3b``: a block-
diffusion language model judged at the states the program itself went through.

The layer of SDAR-30B-A3B-Chat (the Qwen3-MoE block: pre-norm attention with
per-head query/key norms and rotate-half rotary positions, pre-norm softmax
router with the top-k renormalised, gated experts, no shared expert) in
straightforward ``jax.numpy`` float32, every matrix product at ``highest``
precision, nothing imported from the program. Where the program is clever this
is not:

- no cache and no loop-carried state: whatever is asked, the answer is ONE
  full forward over every position of a row, prompt included, with the whole
  mask ``M[i, j] = floor(j / B) <= floor(i / B)`` written out and the scores
  materialised;
- the experts are a loop over experts, each over every token, weighted by a
  mask of the picks that chose it;
- a generating program is not re-run: given a row's prompt, the ``tokens`` it
  produced and the pass at which each was fixed (``unmask_pass``), the ids the
  program saw at (block ``b``, pass ``t``) are rebuilt (the prompt, the final
  ids of earlier blocks and, in block ``b``, the final id where ``unmask_pass
  < t`` and the mask id elsewhere) and put through the forward. What follows
  block ``b`` is filled with the mask id and, under ``M``, seen by nothing
  that is read. One full forward over a row's final ids gives the final
  norm's output of every commit pass, hence ``pooled``.

One layer's float32 weights are on the device at a time (2.5 GB at the
published widths), the head's after them.

Departures from the published modelling code (``modeling_sdar_moe.py``): no
key-value cache, no flash or flex attention, no fused expert kernels, no
sampling (the program's own choices are forced); the router's softmax and the
norms are float32 as there. ``precision`` is the arithmetic of the matrix
products and nothing else (``encoder._mm``: ``float32`` the reference proper,
``bfloat16``, and the ``float8`` control).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from benchmark.reference import onnx_initializers
from benchmark.reference.encoder import PRECISIONS, _mm

# the model file's weights are BFLOAT16 tensors (ONNX data type 16)
onnx_initializers._DTYPES.setdefault(16, ml_dtypes.bfloat16)


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def rotate_half(x, cos, sin):
    """``x [n, s, heads, d]`` turned by the angles ``cos``/``sin [s, d/2]``:
    the first half of a head pairs with the second."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_mask(length: int, block: int) -> np.ndarray:
    """``M [length, length]``: key ``j`` is visible to query ``i`` where its
    block is ``i``'s or an earlier one."""
    blocks = np.arange(length) // block
    return blocks[None, :] <= blocks[:, None]


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "precision"))
def attention(u, w: Dict[str, jax.Array], cos, sin, visible, heads: int,
              kv_heads: int, eps: float, precision: str):
    n, s, _ = u.shape
    q = _mm("nsh,hk->nsk", u, w["q_w"], precision).reshape(n, s, heads, -1)
    k = _mm("nsh,hk->nsk", u, w["k_w"], precision).reshape(n, s, kv_heads, -1)
    v = _mm("nsh,hk->nsk", u, w["v_w"], precision).reshape(n, s, kv_heads, -1)
    q = rotate_half(rms_norm(q, w["q_norm_w"], eps), cos, sin)
    k = rotate_half(rms_norm(k, w["k_norm_w"], eps), cos, sin)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    scores = _mm("nqhd,nkhd->nhqk", q, k, precision) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    ctx = _mm("nhqk,nkhd->nqhd", probs, v, precision).reshape(n, s, -1)
    return _mm("nsk,kh->nsh", ctx, w["o_w"], precision)


@partial(jax.jit, static_argnames=("top_k", "precision"))
def experts(u, w: Dict[str, jax.Array], top_k: int, precision: str):
    """Softmax over every expert, the ``top_k`` largest over their sum; then
    each expert in turn over every token."""
    probs = jax.nn.softmax(_mm("nsh,he->nse", u, w["router_w"], precision),
                           axis=-1)
    top, picks = jax.lax.top_k(probs, top_k)
    weights = top / top.sum(-1, keepdims=True)

    def one_expert(total, expert):
        index, gate, up, down = expert
        share = jnp.sum(jnp.where(picks == index, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(_mm("nsh,hf->nsf", u, gate, precision)) \
            * _mm("nsh,hf->nsf", u, up, precision)
        out = _mm("nsf,fh->nsh", hidden, down, precision)
        return total + share[..., None] * out, None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (jnp.arange(w["experts_up"].shape[0]), w["experts_gate"],
         w["experts_up"], w["experts_down"]))
    return total


@partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, eps: float):
    return rms_norm(x, weight, eps)


@partial(jax.jit, static_argnames=("precision",))
def _head(rows, lm_head, precision: str):
    return _mm("rh,hv->rv", rows, lm_head, precision)


_ATTENTION = ("q_w", "k_w", "v_w", "q_norm_w", "k_norm_w", "o_w")
_EXPERTS = ("router_w", "experts_gate", "experts_up", "experts_down")


def ids_at(prompt: np.ndarray, tokens: np.ndarray, unmask_pass: np.ndarray,
           block_index: int, at_pass: int, block: int, mask_id: int
           ) -> np.ndarray:
    """The ids of one row as the program saw them at (``block_index``,
    ``at_pass``): every position of the row, what follows the block as the
    mask id (nothing that is read sees it)."""
    lo, hi = block_index * block, (block_index + 1) * block
    generated = np.full(len(tokens), mask_id, dtype=np.int64)
    generated[:lo] = tokens[:lo]
    generated[lo:hi] = np.where(unmask_pass[lo:hi] < at_pass, tokens[lo:hi],
                                mask_id)
    return np.concatenate([np.asarray(prompt, np.int64), generated])


class Reference:
    """The forward pass of one configuration; ``final_norm`` is the whole of
    it, ``replay`` and ``pooled`` ask it the check's questions."""

    def __init__(self, config: dict, initializers: Dict[str, np.ndarray]):
        self.config, self.weights = config, initializers
        kwargs = config.get("builder_kwargs", {})
        self.block = int(kwargs.get("block", 4))
        self.passes = int(kwargs.get("passes", 2))
        self.mask_id = int(config["mask_token_id"])

    def _put(self, name: str):  # a bfloat16 tensor is widened on the device
        return jnp.asarray(np.ascontiguousarray(self.weights[name])
                           ).astype(jnp.float32)

    def final_norm(self, ids: np.ndarray, precision: str = "float32"):
        """``ids [n, length]`` (``length`` a multiple of the block) -> the
        final norm's output ``[n, length, hidden]``, on the device."""
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        c = self.config
        eps, d = float(c["rms_norm_eps"]), int(c["head_dim"])
        length = ids.shape[1]
        angles = np.arange(length, dtype=np.float64)[:, None] * (
            float(c["rope_theta"]) ** (-np.arange(0, d, 2, dtype=np.float64)
                                       / d))[None, :]
        cos, sin = (jnp.asarray(f(angles), jnp.float32)
                    for f in (np.cos, np.sin))
        visible = jnp.asarray(block_mask(length, self.block))
        x = jnp.asarray(np.asarray(self.weights["tok_emb"])[ids]
                        ).astype(jnp.float32)
        for i in range(int(c["num_hidden_layers"])):
            w = {k: self._put(f"l{i}_{k}") for k in _ATTENTION}
            u = _norm(x, self._put(f"l{i}_norm_in_w"), eps=eps)
            x = x + attention(u, w, cos, sin, visible,
                              heads=c["num_attention_heads"],
                              kv_heads=c["num_key_value_heads"], eps=eps,
                              precision=precision)
            w = {k: self._put(f"l{i}_{k}") for k in _EXPERTS}
            u = _norm(x, self._put(f"l{i}_norm_post_w"), eps=eps)
            x = x + experts(u, w, top_k=c["num_experts_per_tok"],
                            precision=precision)
            del w
        return _norm(x, self._put("norm_f_w"), eps=eps)

    def replay(self, prompts: np.ndarray, tokens: np.ndarray,
               unmask_pass: np.ndarray, blocks: Sequence[Sequence[int]],
               precision: str = "float32", block_rows: int = 0
               ) -> Dict[str, np.ndarray]:
        """What the reference gives at the program's own states, ``block_rows``
        rows' states a forward (0: all in one): ``logits [row, which of the
        row's blocks, pass, position in block, vocab]`` float32 (the mask
        id's as computed, not yet at -inf) and ``pooled [row, hidden]``, the
        mean over the generated positions of the final norm's output in one
        full forward over the row's final ids (under ``M`` a block's
        positions see what its commit pass saw)."""
        block, passes, s = self.block, self.passes, prompts.shape[1]
        n_rows = len(blocks)
        lm_head = self._put("lm_head")
        logits = np.zeros((n_rows, max(len(b) for b in blocks), passes,
                           block, lm_head.shape[1]), np.float32)
        pooled = []
        for lo in range(0, n_rows, block_rows or n_rows):
            rows = range(lo, min(n_rows, lo + (block_rows or n_rows)))
            states = [np.concatenate([prompts[r], tokens[r]]) for r in rows]
            where = []
            for r in rows:
                for j, b in enumerate(blocks[r]):
                    for t in range(passes):
                        states.append(ids_at(prompts[r], tokens[r],
                                             unmask_pass[r], b, t, block,
                                             self.mask_id))
                        where.append((r, j, t, s + b * block))
            final = self.final_norm(np.stack(states).astype(np.int64),
                                    precision)
            pooled.append(np.asarray(jnp.mean(final[:len(rows), s:], axis=1)))
            at_blocks = jnp.concatenate(
                [final[len(rows) + k, first:first + block]
                 for k, (_, _, _, first) in enumerate(where)])
            out = np.asarray(_head(at_blocks, lm_head, precision=precision))
            for k, (r, j, t, _) in enumerate(where):
                logits[r, j, t] = out[k * block:(k + 1) * block]
        return {"logits": logits, "pooled": np.concatenate(pooled)}

    def pooled(self, prompts: np.ndarray, tokens: np.ndarray,
               precision: str = "float32") -> np.ndarray:
        """``replay``'s ``pooled`` alone."""
        ids = np.concatenate([np.asarray(prompts, np.int64),
                              np.asarray(tokens, np.int64)], axis=1)
        final = self.final_norm(ids, precision)
        return np.asarray(jnp.mean(final[:, prompts.shape[1]:], axis=1))
