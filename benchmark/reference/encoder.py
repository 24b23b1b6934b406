"""Plain transformer encoder, the reference of ``bert_base`` and ``vit_b16``.

Devlin et al. 2018 (BERT) and Dosovitskiy et al. 2020 (ViT) as the zoo's
graphs state them, in straightforward ``jax.numpy`` float32 with every matrix
product at ``highest`` precision: no kernels, no batching logic, nothing
imported from the program. Weights come out of the model file's initializers
by name (``onnx_initializers.read_initializers``) and are ARGUMENTS of the
jitted functions, never literals, so one compiled layer serves every layer
and every weight seed.

Departures of the zoo's graphs from the papers, followed here and listed
under ``assumed`` in the configuration files: both stacks are post-LN (the
published ViT is pre-LN with a final LayerNorm), BERT has no token-type
embedding and no attention mask, ViT no pre-logits layer.

``precision`` is the arithmetic of the matrix products and nothing else:

- ``float32``: the reference proper;
- ``bfloat16``: operands rounded to bfloat16, float32 accumulation (what the
  configurations state the program serves in);
- ``float8``: operands rounded to float8 e4m3 under one absmax scale per
  tensor, float32 accumulation: the control, the nearest precision below
  bfloat16, in the form a later PR would be tempted by (scaled, so that it is
  as accurate as float8 gets).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")
_F8_MAX = 448.0  # largest finite float8 e4m3fn


def _operand(x, precision: str):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, precision: str):
    # rounded operands are exact float32 numbers, so one product routine at
    # "highest" serves the three precisions
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


@partial(jax.jit, static_argnames=("heads", "eps", "precision"))
def _layer(x, w: Dict[str, jax.Array], heads: int, eps: float,
           precision: str):
    n, s, h = x.shape
    hd = h // heads

    def proj(name):
        y = _mm("nsh,hk->nsk", x, w[name + "w"], precision) + w[name + "b"]
        return y.reshape(n, s, heads, hd).transpose(0, 2, 1, 3)

    q, k, v = proj("att_q"), proj("att_k"), proj("att_v")
    scores = _mm("nhqd,nhkd->nhqk", q, k, precision) / np.sqrt(hd)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("nhqk,nhkd->nhqd", probs, v, precision)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(n, s, h)
    attn = _mm("nsh,hk->nsk", ctx, w["att_ow"], precision) + w["att_ob"]
    x = _layer_norm(x + attn, w["ln1_g"], w["ln1_b"], eps)
    f = _mm("nsh,hf->nsf", x, w["ffn1w"], precision) + w["ffn1b"]
    f = jax.nn.gelu(f, approximate=False)
    f = _mm("nsf,fh->nsh", f, w["ffn2w"], precision) + w["ffn2b"]
    return _layer_norm(x + f, w["ln2_g"], w["ln2_b"], eps)


@partial(jax.jit, static_argnames=("eps",))
def _embed_tokens(ids, tok, pos, g, b, eps: float):
    x = tok[ids] + pos[: ids.shape[1]]
    return _layer_norm(x, g, b, eps)


@partial(jax.jit, static_argnames=("patch", "precision"))
def _embed_patches(images, patch_w, cls_tok, pos, patch: int, precision: str):
    n, c, hh, ww = images.shape
    gh, gw = hh // patch, ww // patch
    x = images.reshape(n, c, gh, patch, gw, patch)
    x = x.transpose(0, 2, 4, 1, 3, 5).reshape(n, gh * gw, c * patch * patch)
    x = _mm("npk,hk->nph", x, patch_w.reshape(patch_w.shape[0], -1), precision)
    cls = jnp.broadcast_to(cls_tok.reshape(1, 1, -1), (n, 1, x.shape[-1]))
    return jnp.concatenate([cls, x], axis=1) + pos


@partial(jax.jit, static_argnames=("pooler", "precision"))
def _head(x, w: Dict[str, jax.Array], pooler: bool, precision: str):
    feat = x[:, 0]
    if pooler:
        feat = jnp.tanh(_mm("nh,hk->nk", feat, w["pool_w"], precision)
                        + w["pool_b"])
    logits = _mm("nh,hc->nc", feat, w["clf_w"], precision) + w["clf_b"]
    return logits, feat


_LAYER_KEYS = ("att_qw", "att_qb", "att_kw", "att_kb", "att_vw", "att_vb",
               "att_ow", "att_ob", "ln1_g", "ln1_b", "ffn1w", "ffn1b",
               "ffn2w", "ffn2b", "ln2_g", "ln2_b")


class Reference:
    """The forward pass of one configuration over blocks of rows.

    It returns BERT's ``logits`` and ``pooled``, ViT's ``logits`` and
    ``features``: the graphs' own output names."""

    def __init__(self, config: dict, initializers: Dict[str, np.ndarray]):
        self.config = config
        self.tokens = "vocab_size" in config
        self.feature_name = "pooled" if self.tokens else "features"

        def put(name):
            return jnp.asarray(np.ascontiguousarray(initializers[name]),
                               jnp.float32)

        self.layers: List[Dict[str, jax.Array]] = [
            {k: put(f"l{i}_{k}") for k in _LAYER_KEYS}
            for i in range(config["num_hidden_layers"])]
        if self.tokens:
            self.embed = {k: put(k) for k in
                          ("tok_emb", "pos_emb", "emb_ln_g", "emb_ln_b")}
            self.head = {k: put(k) for k in
                         ("pool_w", "pool_b", "clf_w", "clf_b")}
        else:
            self.embed = {k: put(k) for k in ("patch_w", "cls_tok", "pos_emb")}
            self.head = {k: put(k) for k in ("clf_w", "clf_b")}

    def forward(self, feeds: Dict[str, np.ndarray],
                precision: str = "float32") -> Dict[str, np.ndarray]:
        """One block of rows, layer by layer; ``feeds`` by graph input name."""
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        c, e = self.config, self.embed
        eps = float(c["layer_norm_eps"])
        if self.tokens:
            x = _embed_tokens(jnp.asarray(feeds["input_ids"], jnp.int32),
                              e["tok_emb"], e["pos_emb"], e["emb_ln_g"],
                              e["emb_ln_b"], eps=eps)
        else:
            x = _embed_patches(jnp.asarray(feeds["data"], jnp.float32),
                               e["patch_w"], e["cls_tok"], e["pos_emb"],
                               patch=c["patch_size"], precision=precision)
        for w in self.layers:
            x = _layer(x, w, heads=c["num_attention_heads"], eps=eps,
                       precision=precision)
        logits, feat = _head(x, self.head, pooler=self.tokens,
                             precision=precision)
        return {"logits": np.asarray(logits),
                self.feature_name: np.asarray(feat)}

    def forward_blocks(self, feeds: Dict[str, np.ndarray], block_rows: int,
                       precision: str = "float32") -> Dict[str, np.ndarray]:
        """All rows of ``feeds`` in blocks of ``block_rows`` (the float32
        attention scores of a whole bucket would not fit beside anything)."""
        n = len(next(iter(feeds.values())))
        parts: List[Dict[str, np.ndarray]] = [
            self.forward({k: v[lo:lo + block_rows] for k, v in feeds.items()},
                         precision)
            for lo in range(0, n, block_rows)]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
