"""Ring + Ulysses (all-to-all) sequence-parallel attention.

Design (the standard TPU recipe — mesh axis over the sequence dimension,
collectives over ICI):

- **Ring attention**: each shard keeps its query block resident and passes
  K/V blocks around the ring with ``lax.ppermute`` while accumulating
  flash-style online softmax (running max ``m``, denominator ``l``,
  numerator ``acc``). Peak memory per chip is one K/V block — sequence
  length scales with the number of chips. Communication: n-1 block
  rotations riding neighbor links.
- **Ulysses attention**: ``lax.all_to_all`` re-shards sequence-sharded
  projections into head-sharded full sequences, runs exact local attention
  per head group, and re-shards back. One collective each way; head counts
  that don't divide the axis are zero-padded through the collective, and
  GQA (fewer K/V heads) re-shards the GROUPED heads, expanding locally.

Both are exact (parity-tested against dense attention on the virtual mesh).
All tensors are (batch, seq, heads, head_dim).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Optional

import numpy as np

__all__ = ["ring_attention", "ulysses_attention",
           "sequence_sharded_attention"]


def _expand_gqa(q, k, v):
    """Grouped-query attention: replicate each K/V head over its query-head
    group (what real GQA checkpoints — Llama/Mistral-style — need before a
    head-count-symmetric attention path). No-op when head counts match."""
    import jax.numpy as jnp

    h, h_kv = q.shape[2], k.shape[2]
    if h_kv == h:
        return k, v
    if h % h_kv:
        raise ValueError(f"query heads {h} must be a multiple of kv heads "
                         f"{h_kv} (GQA groups)")
    rep = h // h_kv
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _auto_block(s: int, cap: int = 512) -> int:
    """Largest power-of-2 block <= cap dividing ``s`` (flash blocks must
    divide the sequence; gathered Ulysses sequences are rarely multiples of
    the kernel defaults). Shares the divisor rule with the kernel's own
    auto-pick so the two cannot drift."""
    from .flash import _pow2_divisor

    return _pow2_divisor(s, cap)


def ring_attention(q, k, v, axis_name: str, causal: bool = False):
    """Flash-style ring attention over sequence shards.

    Call INSIDE ``shard_map``: ``q``/``k``/``v`` are the LOCAL sequence
    blocks (B, s_local, H, D); shard i holds global positions
    ``[i*s_local, (i+1)*s_local)``. K/V may carry fewer (grouped) heads —
    GQA rotates the GROUPED blocks around the ring (group-size-times less
    ICI traffic per hop) and expands to the query head count locally at each
    step. Returns the local output block.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    h, h_kv = q.shape[2], k.shape[2]
    if h % h_kv:
        raise ValueError(f"query heads {h} must be a multiple of kv heads "
                         f"{h_kv} (GQA groups)")
    rep = h // h_kv
    b, s_local, h, d = q.shape
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(d)
    q32 = q.astype(jnp.float32)

    qpos = my * s_local + jnp.arange(s_local)  # global query positions

    def step(i, carry):
        m, l, acc, k_blk, v_blk = carry
        # the block currently held started at shard (my - i) mod n
        src = (my - i) % n
        kpos = src * s_local + jnp.arange(s_local)
        mask = (qpos[:, None] >= kpos[None, :]) if causal else None
        # GQA: expand the grouped K block locally (free VMEM copy) — only
        # grouped heads ride the ring
        k_full = (jnp.repeat(k_blk, rep, axis=2) if rep > 1 else k_blk)
        s = jnp.einsum("bqhd,bkhd->bqhk", q32,
                       k_full.astype(jnp.float32)) * scale
        if mask is not None:
            s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        # exp(-inf - -inf) guard: rows with no visible keys keep m=-inf
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[..., None])
        if mask is not None:
            p = jnp.where(mask[None, :, None, :], p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * corr + p.sum(-1)
        v_full = (jnp.repeat(v_blk, rep, axis=2) if rep > 1 else v_blk)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p, v_full.astype(jnp.float32))
        m = m_new
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return m, l, acc, k_blk, v_blk

    m0 = jnp.full((b, s_local, h), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, s_local, h), jnp.float32)
    acc0 = jnp.zeros((b, s_local, h, d), jnp.float32)
    m, l, acc, _, _ = lax.fori_loop(0, n, step, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      local: str = "dense", interpret: bool = False,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Call INSIDE ``shard_map`` with (B, s_local, H, D) blocks. Re-shards to
    (B, S_global, H/n, D), runs local attention over the full gathered
    sequence, re-shards back. Heads that don't divide the axis size are
    zero-padded through the all-to-all and sliced off after (padded heads
    attend zeros -> produce zeros); K/V may carry fewer (grouped) heads —
    GQA expands first. ``local='flash'`` runs the local attention as the
    Pallas flash kernel (``flash.py``) — at long S the head-sharded score
    tensor is exactly the HBM blow-up flash avoids; ``'dense'`` stays
    exact-XLA. ``block_q``/``block_k`` override the flash block sizes
    (default: largest power-of-2 divisor of the gathered length, <= 512)."""
    import jax.numpy as jnp
    from jax import lax

    b, s_local, h, d = q.shape
    n = lax.psum(1, axis_name)  # axis sizes are static: this is a Python int
    h_kv = k.shape[2]
    rep = 1
    if h_kv != h:
        if h % h_kv:
            raise ValueError(f"query heads {h} must be a multiple of kv "
                             f"heads {h_kv} (GQA groups)")
        if h % n == 0 and h_kv % n == 0:
            # grouped re-shard: shard s's q-head slice [s*h/n, (s+1)*h/n)
            # covers exactly kv groups [s*h_kv/n, (s+1)*h_kv/n), so K/V ride
            # the all-to-all at group width and expand locally after —
            # group-size-times less collective traffic
            rep = h // h_kv
        else:
            k, v = _expand_gqa(q, k, v)
            h_kv = h
    pad_h = (-h) % n
    if pad_h:
        def zpad(x):
            return jnp.concatenate(
                [x, jnp.zeros((b, s_local, pad_h, d), x.dtype)], axis=2)
        q, k, v = zpad(q), zpad(k), zpad(v)

    # sequence-sharded -> head-sharded: split heads, concat sequence
    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)  # (B, S, H_pad/n, D)
    if rep > 1 and local != "flash":
        # dense local path: expand grouped K/V after the collective; the
        # flash kernel instead resolves GQA in-kernel via its BlockSpec
        # index map, so the expanded K/V never materialize in HBM
        kh = jnp.repeat(kh, rep, axis=2)
        vh = jnp.repeat(vh, rep, axis=2)
    if local == "flash":
        S = qh.shape[1]
        any_auto = block_q is None or block_k is None
        bq = block_q or _auto_block(S)
        bk = block_k or _auto_block(S)
        if any_auto and min(bq, bk) < 128:
            # an odd / small-power-of-2-factor gathered length auto-blocks
            # below the (8, 128) Mosaic tile minimum — the kernel would be
            # rejected or crawl at sub-tile grids; dense local attention is
            # both correct and faster at these sizes. Explicit blocks are
            # honored (interpret-mode tests and expert tuning).
            from .flash import note_dense_substitute

            note_dense_substitute("ulysses_attention",
                                  (b, S, S, qh.shape[2], kh.shape[2], d),
                                  (bq, bk))  # then the dense path below
        else:
            from .flash import flash_attention

            out = flash_attention(qh, kh, vh, causal=causal,
                                  block_q=bq, block_k=bk,
                                  interpret=interpret)
            out = to_seq(out.astype(q.dtype))
            return out[:, :, :h] if pad_h else out
    if kh.shape[2] != qh.shape[2]:
        # reached via the flash sub-tile fallback with grouped K/V intact
        kh = jnp.repeat(kh, qh.shape[2] // kh.shape[2], axis=2)
        vh = jnp.repeat(vh, qh.shape[2] // vh.shape[2], axis=2)
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bqhk", qh.astype(jnp.float32),
                   kh.astype(jnp.float32)) * scale
    if causal:
        S = s.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = jnp.einsum("bqhk,bkhd->bqhd", p, vh.astype(jnp.float32))
    out = to_seq(out.astype(q.dtype))
    return out[:, :, :h] if pad_h else out


def sequence_sharded_attention(q, k, v, mesh, axis: str = "seq",
                               strategy: str = "ring",
                               causal: bool = False,
                               local: str = "dense",
                               interpret: bool = False,
                               block_q: Optional[int] = None,
                               block_k: Optional[int] = None):
    """Host-level entry: GLOBAL (B, S, H, D) arrays -> attention output,
    with S sharded over ``mesh`` axis ``axis`` and the chosen strategy's
    collectives over the ICI ring. K/V may carry fewer (grouped/GQA) heads;
    ``block_q``/``block_k`` tune the ``local='flash'`` kernel (default:
    auto-picked to divide the gathered sequence)."""
    from ..runtime.layout import as_layout

    if strategy not in ("ring", "ulysses"):
        raise ValueError(f"unknown strategy {strategy!r}")
    # canonical sharding layout (runtime/layout.py): the sequence axis is
    # the layout's row axis; accepts a raw Mesh (back-compat) or SpecLayout
    layout = as_layout(mesh, data_axis=axis)
    n = layout.data_size
    S = q.shape[1]
    if S % n:
        raise ValueError(f"sequence length {S} must be divisible by the "
                         f"{layout.data_axis!r} axis size {n}")
    if local not in ("dense", "flash"):
        raise ValueError(f"unknown local attention {local!r}")
    run = _sharded_attn_fn(layout, strategy, causal, local, interpret,
                           block_q, block_k)
    spec = layout.batch(rank=4, dim=1)
    return run(layout.put(q, spec), layout.put(k, spec),
               layout.put(v, spec))


@lru_cache(maxsize=64)
def _sharded_attn_fn(layout, strategy: str, causal: bool,
                     local: str = "dense", interpret: bool = False,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None):
    # cached per (layout, strategy, causal): a fresh jit closure per call
    # would retrace + recompile on every invocation (per layer / per step);
    # SpecLayout is frozen/hashable exactly so it can key this cache
    import jax

    axis = layout.data_axis
    if strategy == "ring":
        fn = partial(ring_attention, axis_name=axis, causal=causal)
    else:
        fn = partial(ulysses_attention, axis_name=axis, causal=causal,
                     local=local, interpret=interpret,
                     block_q=block_q, block_k=block_k)
    spec = layout.batch(rank=4, dim=1)
    return jax.jit(layout.shard_map(
        fn,
        in_specs=(spec, spec, spec), out_specs=spec,
        check=False,
    ))
