"""Pallas flash attention — the TPU kernel for long-sequence inference.

SURVEY.md §5 marks long-context support as net-new; ``ring.py`` provides the
cross-chip recipes (ppermute ring / all-to-all). This module provides the
ON-CHIP kernel: blockwise attention with online softmax running entirely in
VMEM, so the (S_q, S_k) score matrix never materializes in HBM. XLA's dense
attention allocates the full score tensor per head — at S=8k, H=12 that is
B * 12 * 8k * 8k * 4 bytes = 3 GB HBM traffic per batch element; the flash
kernel streams K/V blocks through VMEM instead (the standard
memory-bound-to-compute-bound move).

Layout: inputs (B, S, H, D) like ``ring.py``, which the kernel reads WHERE
THEY LIE as ``[B, S, H x D]`` (a free reshape): the grid is (batch, group of
heads, query block, key block), the block index maps pick a group's columns,
and the key-block walk carries the (m, l, acc) online-softmax state in VMEM
scratch; widths that are no blocks of 128 lanes go through copies laid out
heads first (:func:`reads_in_place`). Masking uses a finite ``-1e30`` (an
actual ``-inf`` makes ``exp(m - m_new)`` produce NaN for fully-masked leading
causal rows), and only where the causal diagonal crosses: a key block above
it is neither fetched nor multiplied, one below it is multiplied whole, and
the tile it crosses goes in sub-tiles that leave out what the mask would
discard (:func:`_diag_rows`).

``interpret=True`` runs the same kernel through the Pallas interpreter on
CPU — the parity tests exercise the kernel logic without TPU hardware.
"""

from __future__ import annotations

import functools
import math

__all__ = ["flash_attention", "dense_attention", "masked_attention",
           "cached_attention", "cached_attention_takes"]

_NEG = -1e30
# what a grid step's blocks and carries may take of VMEM: half of the 16 MiB
# a v5e kernel has by default, the rest being a tile's float32 scores, their
# exponentials and the rounded copy
_STEP_VMEM = 8 << 20


def dense_attention(q, k, v, causal: bool = False, pv_dtype=None,
                    causal_block: int = 1, scale: float = None):
    """Reference dense attention, (B, S, H, D) layout, f32 accumulation;
    ``v`` may have a width of its own (the result has it), ``scale``
    defaults to ``1 / sqrt(D)`` of the queries and keys.

    ``pv_dtype`` casts the probabilities for the P@V matmul (e.g. bf16 —
    the performant-XLA baseline bench.py compares flash against; the flash
    kernel makes the same cast). Default keeps everything f32 (the exact
    parity reference the tests use). ``causal_block`` as in
    :func:`flash_attention`."""
    import jax.numpy as jnp

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        S_q, S_k = s.shape[1], s.shape[3]
        q_pos = jnp.arange(S_q)[:, None] + (S_k - S_q)
        if causal_block > 1:  # a query sees its whole block of positions
            q_pos = q_pos | (causal_block - 1)
        mask = q_pos >= jnp.arange(S_k)[None, :]
        s = jnp.where(mask[None, :, None, :], s, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    if pv_dtype is not None:
        p = p.astype(pv_dtype)
        out = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(pv_dtype))
    else:
        out = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def masked_attention(q, k, v, mask, causal: bool = False,
                     scale: float = None):
    """Dense attention under a boolean ``mask`` (true: visible) that
    broadcasts to ``[B, H, S_q, S_k]``; (B, S, H, D) layout, ``v`` ``(B, S_k,
    H_kv, D_v)`` with a width of its own. The form for a mask only the run
    knows, as a few queries against a cache filled so far: grouped key-value
    heads are read as they lie (never repeated in HBM; one head of latents
    serves every query head), the two products run in the inputs' type with
    float32 accumulation, the softmax in float32; the probabilities take the
    values' type for the second product, as in the kernel. ``scale`` (default
    ``1 / sqrt(D)``) multiplies the float32 scores."""
    import jax.numpy as jnp

    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    rep = h // h_kv
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(b, s_q, h_kv, rep, d), k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(d) if scale is None else s * scale
    mask = jnp.asarray(mask)
    mask = mask.reshape((1,) * (4 - mask.ndim) + mask.shape)
    if causal:
        mask = mask & (jnp.arange(s_q)[:, None] + (s_k - s_q)
                       >= jnp.arange(s_k)[None, :])
    if mask.shape[1] == 1:
        mask = mask[:, :, None]
    else:
        mask = mask.reshape(mask.shape[0], h_kv, rep, *mask.shape[2:])
    s = jnp.where(mask, s, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s_q, h, v.shape[-1]).astype(q.dtype)


@functools.lru_cache(maxsize=256)
def note_dense_substitute(caller: str, shape: tuple, blocks: tuple) -> None:
    """The Pallas kernel was asked for and dense attention runs instead
    (the sequence lengths have no block that meets Mosaic's (8, 128) tile).
    Said once per caller and shape — a warning on the ``synapseml_tpu.flash``
    logger and a ``flash/dense_substitute`` telemetry event — so a run that
    believes it exercised the kernel can see that it did not."""
    import logging

    from ..core import telemetry

    logging.getLogger("synapseml_tpu.flash").warning(
        "%s: auto-picked blocks %s for (b, s_q, s_k, h, h_kv, d)=%s are "
        "below Mosaic's (8, 128) tile; running dense attention, not the "
        "Pallas kernel", caller, blocks, shape)
    telemetry.log_event("dense_substitute", className="flash", uid=caller,
                        shape=list(shape), blocks=list(blocks))


def _pow2_divisor(s: int, cap: int) -> int:
    """Largest power-of-2 divisor of ``s`` that is <= cap."""
    b = cap
    while b > 1 and s % b:
        b //= 2
    return b


def _pick_blocks(bh: int, s_q: int, s_k: int):
    """Block sizes: two classes, both of which Mosaic compiles on a
    v5e under jax 0.9.0 / libtpu 0.0.34 (bf16, D=64, causal, with and
    without GQA; ``chip_smoke.py`` checks both against dense attention).

    - small grids (bh < 32) at long S take wide (2048, 1024) q/k blocks:
      fewer grid steps where each step's fixed cost dominates;
    - bigger grids (serving batches, B*H >= 32) take (1024, 1024);
    - everything clamps to power-of-2 divisors of the sequence lengths.

    Measured on a v5e (``tools/flash_width_forms.py``, PERF.md section 6, PR
    37) is the second class where the benchmark's cells run it: (1024, 1024)
    at S = 4,096, where the kernel on operands as they lie reads 51.5 % of
    the MXU peak at 192 / 128-wide heads and 54.4 % at 128-wide grouped
    ones, and one (256, 256) tile a head at S = 256. A tile the causal
    diagonal crosses is cut in two there (:func:`_diag_rows`: halves beat
    quarters and eighths). The (2048, 1024) class is not measured on this
    toolchain, and its diagonal's tiles stay whole.
    """
    bq_target = 2048 if (bh < 32 and s_q >= 16384) else 1024
    return (_pow2_divisor(s_q, bq_target), _pow2_divisor(s_k, 1024))


def auto_blocks_tile(bh: int, s_q: int, s_k: int,
                     causal_block: int = 1) -> bool:
    """Whether the blocks :func:`flash_attention` picks by itself meet
    Mosaic's (8, 128) tile and hold whole blocks of ``causal_block``
    positions: it then runs the kernel, else its dense substitute (a caller
    that has to know which asks here first)."""
    block_q, block_k = _pick_blocks(bh, s_q, s_k)
    block_q, block_k = min(block_q, s_q), min(block_k, s_k)
    return (block_q >= 8 and block_k >= 128 and block_q % causal_block == 0
            and (s_k - s_q) % causal_block == 0)


def flash_attention(q, k, v, causal: bool = False, block_q: int = None,
                    block_k: int = None, interpret: bool = False,
                    causal_block: int = 1, scale: float = None):
    """Blockwise-online-softmax attention as ONE Pallas kernel.

    ``q`` (B, S_q, H, D), ``k`` (B, S_k, H_kv, D), ``v`` (B, S_k, H_kv, D_v)
    -> (B, S_q, H, D_v): the values may have a width of their own (latent
    attention's expanded form: 192-wide queries and keys, 128-wide values).
    ``scale`` multiplies the float32 scores (default ``1 / sqrt(D)``).
    ``H_kv`` may divide ``H`` (grouped-query attention): the kernel maps
    each query head's grid step onto its K/V group IN-KERNEL via the block
    index map, so grouped K/V are never expanded in HBM (Llama/Mistral
    checkpoints pay 1/group of the K/V bandwidth).

    ``causal`` aligns the diagonal to the END of the key sequence (queries
    are the LAST S_q positions), matching decode/ring conventions. Block
    sizes default to :func:`_pick_blocks`; explicit values must divide the
    sequence lengths. ``causal_block`` B > 1 (a power of two that divides
    ``block_q`` and ``S_k - S_q``) makes the causal mask one of blocks of B
    positions: a query sees every earlier block and all of its own, ``kpos
    <= qpos | (B - 1)``. Only the diagonal tiles' comparison changes; which
    tiles are skipped does not, since B divides the tiles.

    The operands are read where they lie wherever a head's columns are
    blocks of 128 lanes (:func:`reads_in_place`; no transposed copy in HBM),
    and the mask costs products only inside the tile it crosses
    (:func:`_diag_rows`): both follow from the shapes.

    This is the long-sequence path: dense attention at S=32k would need
    ~34 GB for the score tensor alone.
    """
    import jax.numpy as jnp

    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    h_kv, d_v = k.shape[2], v.shape[-1]
    if k.shape != (b, s_k, h_kv, d) or v.shape != (b, s_k, h_kv, d_v):
        raise ValueError(f"shape mismatch: q {q.shape}, k {k.shape}, "
                         f"v {v.shape}")
    if h % h_kv:
        raise ValueError(f"query heads {h} must be a multiple of kv heads "
                         f"{h_kv} (GQA groups)")
    rep = h // h_kv
    if causal and s_q > s_k:
        # queries are the LAST s_q positions of the key sequence; more
        # queries than keys would leave leading rows with no visible key
        # (and silently all-zero outputs)
        raise ValueError(f"causal flash attention needs s_q <= s_k, got "
                         f"s_q={s_q} > s_k={s_k}")
    causal_block = int(causal_block) if causal else 1
    if causal_block < 1 or causal_block & (causal_block - 1):
        raise ValueError(f"causal_block {causal_block} is not a power of two")
    req_q, req_k = block_q, block_k  # the USER's values, pre-clamp
    auto_bq, auto_bk = _pick_blocks(b * h, s_q, s_k)
    block_q = min(block_q or auto_bq, s_q)  # each side auto-fills alone
    block_k = min(block_k or auto_bk, s_k)
    if s_q % block_q or s_k % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide "
                         f"sequence lengths ({s_q}, {s_k})")
    if block_q % causal_block or (s_k - s_q) % causal_block:
        raise ValueError(
            f"causal_block {causal_block} must divide block_q {block_q} and "
            f"the diagonal's offset {s_k - s_q}")
    # Mosaic tile minimum: the (block_q, block_k) score tile needs >= 8
    # sublanes and >= 128 lanes. Awkward sequence lengths with few
    # power-of-2 factors (S=1200 -> 16, odd S -> 1) used to auto-pick
    # sub-tile blocks and die inside Mosaic (or crawl); the Pallas
    # interpreter has no such minimum, so CPU parity tests keep passing
    # small explicit blocks with interpret=True.
    if not interpret and (block_q < 8 or block_k < 128):
        # raise only for blocks the USER requested below the minimum; a
        # legal explicit block that a short sequence clamped down
        # (block_k=1024 at s_k=64) takes the dense fallback like the auto
        # path — "pass bigger blocks" would be unsatisfiable advice there
        if (req_q is not None and req_q < 8) or \
                (req_k is not None and req_k < 128):
            raise ValueError(
                f"flash_attention blocks ({block_q}, {block_k}) are below "
                f"Mosaic's (8, 128) tile minimum; pass blocks that divide "
                f"the sequence lengths ({s_q}, {s_k}) and meet the minimum, "
                f"or use interpret=True / dense_attention")
        # auto-picked sub-tile (the sequence length simply has no legal
        # block): fall back to dense attention when its score tensor is
        # affordable — a long ODD sequence would OOM in dense with an
        # equally opaque error, so that case raises with the fix named
        if 4 * b * h * s_q * s_k > 2e9:
            raise ValueError(
                f"flash_attention cannot tile sequence lengths ({s_q}, "
                f"{s_k}): the largest power-of-2 block divisors "
                f"({block_q}, {block_k}) are below Mosaic's (8, 128) tile "
                f"minimum, and the lengths are too large for the dense "
                f"fallback. Pad the sequences to a multiple of 128.")
        note_dense_substitute("flash_attention", (b, s_q, s_k, h, h_kv, d),
                              (block_q, block_k))
        if rep != 1:  # dense needs matching head counts: expand GQA K/V
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return dense_attention(q, k, v, causal=causal,
                               causal_block=causal_block, scale=scale)

    out = _flash_jit()(q, k, v, causal=bool(causal), block_q=int(block_q),
                       block_k=int(block_k), interpret=bool(interpret),
                       causal_block=causal_block,
                       scale=None if scale is None else float(scale))
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=1)
def _flash_jit():
    """Profiled jit entry point, applied lazily so importing the package
    never imports jax. ``observability.profiling`` times every compile
    (``smt_compile_seconds{fn="flash.attention"}``), counts recompiles by
    the signature change that caused them (block-size churn shows up as
    ``cause="static"``), and caches cost_analysis FLOPs so serving spans
    report the kernel's achieved MFU."""
    from ..observability.profiling import profiled_jit

    return profiled_jit(_flash_impl, name="flash.attention",
                        static_argnames=("causal", "block_q", "block_k",
                                         "interpret", "causal_block",
                                         "scale"))


def reads_in_place(d: int, d_v: int, itemsize: int) -> bool:
    """Whether the kernel reads ``[B, S, H x D]`` operands where they lie. A
    head of values, and of the result, has to be a block of 128 lanes. A
    head of queries or keys is a block of lanes too where ``D`` is a
    multiple of 128; any other ``D`` of whole sublane tiles (latent
    attention's 192) is read with the POSITIONS minor, ``[B, H x D, S]``,
    where a head is rows of a block: that is how the compiler lays such
    heads out anyway (it will not pad 192 lanes to 256 in HBM), so the
    transpose in front of the kernel is no operation, where row-major
    operands cost a copy of ``q`` and of ``k`` a layer (2.5 ms each at ``[16,
    4096, 6144]``; PERF.md section 6, PR 37), and a matrix product writes
    either layout at the same price. Any other width (64-wide values) is
    laid out heads first in HBM before the kernel and the result back after
    it: no cell of the benchmark has one."""
    return d_v % 128 == 0 and d % (32 // itemsize) == 0


def _diag_rows(block_q: int, block_k: int, diag_off: int,
               causal_block: int) -> int:
    """Query rows of a sub-tile of a tile the causal diagonal crosses. Where
    the tiles are square and the diagonal runs through their corners every
    such tile looks the same, and rows ``[r x n, (r + 1) x n)`` of it see its
    first ``(r + 1) x n`` keys, a static slice: ``n`` is half the tile (the
    upper half leaves the keys of the right half out: 12 / 16 of the tile's
    products), whole 128-lane tiles of scores and whole blocks of the mask's
    granularity. Halves read as fast as quarters at 192-wide heads and 5 %
    faster at 128-wide ones, eighths lose to both
    (``tools/flash_width_forms.py``, PERF.md section 6, PR 37). Elsewhere
    (the (2048, 1024) class, an offset off the tiles) the tile is computed
    whole under the mask: ``block_q``."""
    rows = block_q // 2
    if block_q != block_k or diag_off % block_k or rows % 128 \
            or rows % causal_block:
        return block_q
    return rows


def _heads_a_step(h: int, h_kv: int, d: int, d_v: int, block_q: int,
                  block_k: int, itemsize: int) -> int:
    """Query heads a grid step takes: as many as _STEP_VMEM holds blocks and
    carries for, a divisor of the heads that share a key-value head (they
    share its blocks of keys and values, fetched once a step) or, where each
    has its own, of all heads (fewer, longer steps). A head brings its
    blocks of queries and result, each fetched or written while the one
    before is used, and its float32 carries. Measured (PERF.md section 6, PR
    37): two heads of 192 / 128 a step read 3 % faster than one, two of 16
    that share a key-value head at (1,024, 1,024) tiles 5 %, eight of eight
    at (256, 256) tiles 45 %; four at (1,024, 1,024) tiles do not compile
    for a v5e."""
    a_head = 2 * block_q * (d + d_v) * itemsize + 4 * block_q * (128 + d_v)
    keys_values = 2 * block_k * (d + d_v) * itemsize
    among = h // h_kv
    if among == 1:  # every head its own keys and values
        among, a_head, keys_values = h, a_head + keys_values, 0
    fit = max(1, (_STEP_VMEM - keys_values) // a_head)
    return max(g for g in range(1, among + 1) if among % g == 0 and g <= fit)


def _flash_impl(q, k, v, causal, block_q, block_k, interpret,
                causal_block=1, scale=None):
    """``flash_attention`` behind its checks, (B, S, H, D) in and out: the
    kernel on the operands where they lie (free reshapes to ``[B, S, H x
    D]``, for heads off the lanes a transpose that is none) where
    :func:`reads_in_place` says so, else on copies laid out heads first."""
    import jax.numpy as jnp

    b, s_q, h, d = q.shape
    s_k, h_kv, d_v = k.shape[1], k.shape[2], v.shape[3]
    diag_rows = _diag_rows(block_q, block_k, s_k - s_q, causal_block)
    if reads_in_place(d, d_v, q.dtype.itemsize):
        q, k = q.reshape(b, s_q, h * d), k.reshape(b, s_k, h_kv * d)
        minor = d % 128 != 0
        if minor:  # no operation: the layout such heads have (reads_in_place)
            q, k = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
        out = _flash_call(
            q, k, v.reshape(b, s_k, h_kv * d_v), heads=h, kv_heads=h_kv,
            group=_heads_a_step(h, h_kv, d, d_v, block_q, block_k,
                                q.dtype.itemsize),
            batch_rep=1, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, causal_block=causal_block,
            diag_rows=diag_rows, scale=scale, positions_minor=minor)
        return out.reshape(b, s_q, h, d_v)

    # (B, S, H, D) -> (B*H, S, D): a head is a row of the leading axis, so
    # its columns are the array's own and any width is a block. K/V keep
    # their GROUPED head count: query row ``bhi`` reads key-value row ``bhi
    # // rep`` (since h = rep x h_kv, (batch x h + head) // rep == batch x
    # h_kv + head // rep)
    def to_bh(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(
            b * x.shape[2], x.shape[1], x.shape[3])

    out = _flash_call(
        to_bh(q), to_bh(k), to_bh(v), heads=1, kv_heads=1, group=1,
        batch_rep=h // h_kv, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, causal_block=causal_block, diag_rows=diag_rows,
        scale=scale)
    return out.reshape(b, h, s_q, d_v).transpose(0, 2, 1, 3)


def _flash_call(q, k, v, *, heads, kv_heads, group, batch_rep, causal,
                block_q, block_k, diag_rows, interpret, causal_block=1,
                scale=None, positions_minor=False):
    """ONE ``pallas_call``: ``q`` (N, S_q, heads x D), ``k`` (N // batch_rep,
    S_k, kv_heads x D), ``v`` (.., kv_heads x D_v) -> (N, S_q, heads x D_v);
    ``positions_minor`` takes ``q`` and ``k`` as (.., heads x D, S), a head
    then being rows of a block (any ``D`` of whole sublane tiles) and the
    scores a contraction over the leading axis of both. The grid is (row,
    group of ``group`` query heads, query block, key block); the block index
    maps pick the group's heads, and for keys and values those of its own
    ``group`` heads or the ONE head the group shares (``head // rep``), so no
    operand is laid out again in HBM.

    Key blocks wholly above the causal diagonal are neither fetched (the
    index map stays at the row of tiles' last block) nor multiplied; blocks
    wholly below it are multiplied with no mask; a block the diagonal
    crosses goes in sub-tiles of ``diag_rows`` query rows, each against the
    keys its rows can see (static slices of the blocks in VMEM), with the
    comparison and select on the sub-tile the diagonal crosses alone: as two
    halves 12 / 16 of the tile's products, where the whole tile under the
    mask (``diag_rows`` = ``block_q``) makes 16 / 16 and throws 6 away."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # queries and keys as (.., S, heads x D) or, positions minor, (.., heads x
    # D, S): a head's numbers are then rows of a block, not lanes
    seq, cols = (2, 1) if positions_minor else (1, 2)
    n, s_q, s_k = q.shape[0], q.shape[seq], k.shape[seq]
    d, d_v = q.shape[cols] // heads, v.shape[2] // kv_heads
    rep = heads // kv_heads
    # key-value heads in a step's blocks: the group's own, or the ONE its
    # query heads share
    kv_group = group if rep == 1 else 1
    if rep % group and rep != 1:
        raise ValueError(f"{group} query heads a step do not share one of "
                         f"{kv_heads} key-value heads ({heads} query heads)")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    nk = s_k // block_k
    # causal diagonal sits at the END of the key axis (ring/decode layout)
    diag_off = s_k - s_q
    if diag_rows != block_q and (block_q != block_k or diag_off % block_k
                                 or block_q % diag_rows
                                 or diag_rows % causal_block):
        raise ValueError(
            f"sub-tiles of {diag_rows} rows need square tiles the diagonal "
            f"runs corner to corner through (blocks ({block_q}, {block_k}), "
            f"offset {diag_off}, causal_block {causal_block})")
    # a crossing tile as (first row, rows, keys they see, keys under the mask)
    crossing = [(r, diag_rows, r + diag_rows, diag_rows)
                for r in range(0, block_q, diag_rows)] \
        if diag_rows != block_q else [(0, block_q, block_k, block_k)]

    # bf16 inputs run the two dots at the MXU's native rate with f32
    # accumulation (p is cast to the value dtype for the PV dot — the
    # standard flash-kernel precision tradeoff). f32 inputs are exact in
    # the interpreter; compiled by Mosaic the dots run at its default
    # precision (1.4e-2 max abs error against a highest-precision dense
    # reference on a v5e, S=512, D=64)
    in_dt = q.dtype

    def kernel(q_ref, k_ref, v_ref, o_ref, ml_s, acc_s):
        # one (block_q, 128) scratch a head holds BOTH online-softmax carries
        # (m in lane 0, l in lane 1): each needs a single lane, and the saved
        # block_q x 128 f32 buffer is what lets 2k-wide blocks fit scoped
        # VMEM
        iq = pl.program_id(2)
        jk = pl.program_id(3)

        @pl.when(jk == 0)
        def _():
            ml_s[:, :, 0:1] = jnp.full((group, block_q, 1), _NEG, jnp.float32)
            ml_s[:, :, 1:2] = jnp.zeros((group, block_q, 1), jnp.float32)
            acc_s[...] = jnp.zeros_like(acc_s)

        def update(t, r0, n_rows, n_keys, n_masked):
            """Rows ``[r0, r0 + n_rows)`` of head ``t`` against the block's
            first ``n_keys`` keys, the last ``n_masked`` of them under the
            causal mask."""
            rows = slice(r0, r0 + n_rows)
            t_kv = t if rep == 1 else 0
            vb = v_ref[0, 0:n_keys, t_kv * d_v:(t_kv + 1) * d_v]
            of_q, of_k = slice(t * d, (t + 1) * d), \
                slice(t_kv * d, (t_kv + 1) * d)
            if positions_minor:
                s = jax.lax.dot_general(
                    q_ref[0, of_q, rows], k_ref[0, of_k, 0:n_keys],
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            else:
                s = jax.lax.dot_general(
                    q_ref[0, rows, of_q], k_ref[0, 0:n_keys, of_k],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            s = s * scale
            if n_masked:
                seen = n_keys - n_masked
                qpos = iq * block_q + r0 + diag_off + \
                    jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_masked), 0)
                if causal_block > 1:  # the whole block of positions
                    qpos = qpos | (causal_block - 1)
                kpos = jk * block_k + seen + jax.lax.broadcasted_iota(
                    jnp.int32, (n_rows, n_masked), 1)
                cut = jnp.where(qpos >= kpos, s[:, seen:], _NEG)
                s = jnp.concatenate([s[:, :seen], cut], axis=1) if seen \
                    else cut
            m = ml_s[t, rows, 0:1]
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            ml_s[t, rows, 1:2] = ml_s[t, rows, 1:2] * corr \
                + p.sum(-1, keepdims=True)
            acc_s[t, rows, :] = acc_s[t, rows, :] * corr + jax.lax.dot_general(
                p.astype(in_dt), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ml_s[t, rows, 0:1] = m_new

        def tile(pieces):
            def run():
                for t in range(group):
                    for piece in pieces:
                        update(t, *piece)
            return run

        whole = tile([(0, block_q, block_k, 0)])
        if causal:
            # key blocks before this one hold no key any row cannot see;
            # blocks from first_masked on hold none any row can
            first_crossing = (iq * block_q + diag_off + causal_block) \
                // block_k
            first_masked = ((iq + 1) * block_q + diag_off
                            + block_k - 1) // block_k
            pl.when(jk < first_crossing)(whole)
            pl.when((jk >= first_crossing) & (jk < first_masked))(
                tile(crossing))
        else:
            whole()

        @pl.when(jk == pl.num_programs(3) - 1)
        def _():
            for t in range(group):
                o_ref[0, :, t * d_v:(t + 1) * d_v] = (
                    acc_s[t] / jnp.maximum(ml_s[t, :, 1:2], 1e-30)
                ).astype(o_ref.dtype)

    # GQA: a group of query heads reads K/V head ``head // rep``, a row of
    # the heads-first layout K/V row ``row // batch_rep``: the grouped K/V
    # are never expanded in HBM. rep == 1 keeps the plain identity map (a
    # division in the index map can pessimize Mosaic's block-revisit
    # analysis).
    def kv_map(row, hg, i, j):
        if causal:
            # a block wholly above the diagonal is not fetched: the index
            # stays at the last block this row of tiles needs
            j = jnp.minimum(j, ((i + 1) * block_q + diag_off + block_k - 1)
                            // block_k - 1)
        return (row if batch_rep == 1 else row // batch_rep, j,
                hg if rep == 1 else hg // (rep // group))

    def of_q_or_k(n_seq, n_cols, index_map):
        """A block of ``n_seq`` positions and ``n_cols`` of the heads' numbers
        and its (row, position, heads) index map, as the operand lies."""
        if not positions_minor:
            return pl.BlockSpec((1, n_seq, n_cols), index_map)
        return pl.BlockSpec(
            (1, n_cols, n_seq),
            lambda *step: tuple(index_map(*step)[i] for i in (0, 2, 1)))

    return pl.pallas_call(
        kernel,
        grid=(n, heads // group, s_q // block_q, nk),
        in_specs=[
            of_q_or_k(block_q, group * d, lambda row, hg, i, j: (row, i, hg)),
            of_q_or_k(block_k, kv_group * d, kv_map),
            pl.BlockSpec((1, block_k, kv_group * d_v), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, group * d_v),
                               lambda row, hg, i, j: (row, i, hg)),
        # output in the INPUT dtype: the caller casts to q.dtype anyway, and
        # an f32 out block doubles what the (2048, 1024) class keeps in
        # scoped VMEM
        out_shape=jax.ShapeDtypeStruct((n, s_q, heads * d_v), q.dtype),
        scratch_shapes=[
            # a head's running max (lane 0) + denominator (lane 1)
            pltpu.VMEM((group, block_q, 128), jnp.float32),
            pltpu.VMEM((group, block_q, d_v), jnp.float32),  # numerator
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            # row/head/q-block steps are independent; only the key-block
            # walk carries state -> Mosaic can pipeline block DMAs across
            # steps
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


# what a grid step of the cached kernel may hold in VMEM: its blocks of keys
# and values, each fetched while the one before is used, and its float32
# scores (half of the 16 MiB a v5e kernel has by default)
_CACHED_VMEM = 8 << 20
# rows a step takes before the key axis is kept whole: under 8 a step's
# fixed cost shows (4 rows a step read 9 % slower than 8 in the table)
_CACHED_ROWS = 8
# query rows a key-value head may bring (positions x the query heads that
# share it) for the kernel to be the form: the matrix unit's height (at 128
# the table reads 3.4 times the dense form's speed, at the cell's 32 it reads
# 4.1; beyond, the products are no small ones and nothing is measured)
_CACHED_QUERY_ROWS = 128


def _key_mask_rows(mask_shape, b: int, s_k: int):
    """1 or ``b`` where a mask of that shape spans key positions alone
    (``[1, S_k]``, ``[B, 1, 1, S_k]`` and what broadcasts from them), else
    None: a mask a head or a query has to itself."""
    shape = (1,) * (4 - len(mask_shape)) + tuple(mask_shape)
    if len(shape) != 4 or shape[1:] != (1, 1, s_k) or shape[0] not in (1, b):
        return None
    return shape[0]


def cached_attention_takes(q_shape, k_shape, v_shape, mask_shape,
                           dtype) -> bool:
    """Whether :func:`cached_attention` compiles for these shapes ((B, S, H,
    D) layout) and is the form for them: ONE head size, a multiple of the 128
    lanes, for queries, keys and values (a key-value head is then a column
    block of the cache as it lies); key-value heads that divide the query
    heads; at most ``_CACHED_QUERY_ROWS`` query rows a key-value head, whole
    sublane tiles of them or not (one query a head of 30 on 30 key-value
    heads, 20 query heads on one); a mask over key positions alone."""
    import numpy as np

    b, s_q, h, d = q_shape
    s_k, h_kv = k_shape[1], k_shape[2]
    sublanes = 32 // np.dtype(dtype).itemsize
    if tuple(k_shape) != (b, s_k, h_kv, d) or tuple(v_shape) != tuple(k_shape):
        return False
    if d % 128 or h % h_kv or s_k % sublanes:
        return False
    return s_q * (h // h_kv) <= _CACHED_QUERY_ROWS \
        and _key_mask_rows(mask_shape, b, s_k) is not None


def _cached_blocks(rows: int, n: int, s_k: int, d: int, itemsize: int):
    """Rows and key positions a grid step of the cached kernel takes, from
    the VMEM it may use. A (row, key position) pair costs its key and its
    value twice (the next block is fetched meanwhile) and three float32
    numbers a query row (the score, its exponential, the rounded copy); the
    ``n`` query rows count as the whole sublane tiles they fill in VMEM (one
    query row of bfloat16 takes a tile of 16). The whole key axis is one
    block where 8 rows of it fit (no running maximum, no rescaling: at 320
    positions key blocks of 128 take half as long again), else blocks of the
    multiple of 128 keys that 8 rows fit; then as many rows as fit, a
    divisor of ``rows`` (8 to 32 rows a step read within 2 % of each other).
    ``tools/cached_attention_forms.py`` has the table (PERF.md section 6, PR
    35)."""
    sublanes = 32 // itemsize
    n = -(-n // sublanes) * sublanes
    fit = _CACHED_VMEM // (4 * d * itemsize + 12 * n)
    few = min(rows, _CACHED_ROWS)
    block_k = s_k if few * s_k <= fit else max(128, fit // few // 128 * 128)
    most = max(1, min(rows, fit // block_k))
    return next(r for r in range(most, 0, -1) if rows % r == 0), block_k


def cached_attention(q, k, v, mask, scale: float = None,
                     interpret: bool = False):
    """A few queries against a key-value cache under a boolean ``mask`` over
    key positions, as ONE Pallas kernel: ``q`` (B, S_q, H, D), ``k`` and
    ``v`` (B, S_k, H_kv, D), ``mask`` (true: visible) ``[1, S_k]``, ``[B, 1,
    1, S_k]`` or what broadcasts from them; -> (B, S_q, H, D). The
    mathematics is :func:`masked_attention`'s (products in the inputs' type
    with float32 accumulation, ``scale`` on the float32 scores, the finite
    ``-1e30`` where the mask is false, the softmax in float32, the
    probabilities rounded to the values' type before the second product);
    what differs is where the numbers go:

    - the cache is read ONCE and as it lies: a grid step takes a block of
      rows and one key-value head as a 128-lane-multiple column block of
      ``[B, S_k, H_kv x D]`` (the reshape below undoes the caller's and
      moves nothing), so no ``[B, S_k, H_kv, D]`` copy is made;
    - the ``S_q x H / H_kv`` query rows that share the head go against it in
      one product, and the scores live and die in VMEM; a block of ``(rows,
      n, D)`` queries spans its array's ``n``, so it is a legal block at any
      ``n`` from 1 on, whole sublane tiles or not: Mosaic pads it in VMEM,
      and no padded copy of the queries or the result crosses HBM;
    - where the key axis is beyond what a step can hold it goes in blocks
      under the online softmax of the flash kernel (``m``, ``l``, ``acc``).

    The queries (small) are laid out ``[B, H_kv, S_q x rep, D]`` outside the
    kernel and the result back. Rows and keys a step: :func:`_cached_blocks`.
    :func:`cached_attention_takes` says which shapes Mosaic compiles; the
    interpreter takes any."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    mask = jnp.asarray(mask)
    mask_rows = _key_mask_rows(mask.shape, b, s_k)
    if k.shape != (b, s_k, h_kv, d) or v.shape != k.shape or h % h_kv \
            or mask_rows is None:
        raise ValueError(f"cached_attention: q {q.shape}, k {k.shape}, v "
                         f"{v.shape}, mask {mask.shape}: one head size, "
                         f"grouped heads and a mask over key positions")
    rep = h // h_kv
    n = s_q * rep
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    rows, block_k = _cached_blocks(b, n, s_k, d, q.dtype.itemsize)
    n_k = -(-s_k // block_k)
    ragged = s_k % block_k != 0

    def kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, *carried):
        j = pl.program_id(2)
        vb = v_ref[...]                                       # (rows, bk, d)
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask_ref[...] != 0, s, _NEG)            # (rows, n, bk)
        if ragged:
            # past the cache's end a block holds whatever lay there: those
            # keys weigh exactly nothing and their values are zeros
            def inside(shape, axis):
                return j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, shape, axis) < s_k

            s = jnp.where(inside((1, 1, block_k), 2), s, -jnp.inf)
            vb = jnp.where(inside((1, block_k, 1), 1), vb,
                           jnp.zeros_like(vb))

        def context(p):
            return jax.lax.dot_general(p.astype(vb.dtype), vb,
                                       (((2,), (1,)), ((0,), (0,))),
                                       preferred_element_type=jnp.float32)

        if n_k == 1:  # the dense form's own order: normalise, round, multiply
            p = jnp.exp(s - s.max(-1, keepdims=True))
            o_ref[...] = context(p * (1.0 / p.sum(-1, keepdims=True))
                                 ).astype(o_ref.dtype)
            return
        ml_s, acc_s = carried  # running maximum in lane 0, sum in lane 1

        @pl.when(j == 0)
        def _():
            ml_s[:, :, 0:1] = jnp.full((rows, n, 1), _NEG, jnp.float32)
            ml_s[:, :, 1:2] = jnp.zeros((rows, n, 1), jnp.float32)
            acc_s[...] = jnp.zeros_like(acc_s)

        m = ml_s[:, :, 0:1]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        ml_s[:, :, 1:2] = ml_s[:, :, 1:2] * corr + p.sum(-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + context(p)
        ml_s[:, :, 0:1] = m_new

        @pl.when(j == n_k - 1)
        def _():
            o_ref[...] = (acc_s[...] / ml_s[:, :, 1:2]).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(b // rows, h_kv, n_k),
        in_specs=[
            # one mask for every row, or a row's own
            pl.BlockSpec((rows if mask_rows > 1 else 1, 1, block_k),
                         lambda i, g, j: (i if mask_rows > 1 else 0, 0, j)),
            pl.BlockSpec((rows, None, n, d), lambda i, g, j: (i, g, 0, 0)),
            pl.BlockSpec((rows, block_k, d), lambda i, g, j: (i, j, g)),
            pl.BlockSpec((rows, block_k, d), lambda i, g, j: (i, j, g)),
        ],
        out_specs=pl.BlockSpec((rows, None, n, d),
                               lambda i, g, j: (i, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h_kv, n, d), q.dtype),
        scratch_shapes=[] if n_k == 1 else [
            pltpu.VMEM((rows, n, 128), jnp.float32),
            pltpu.VMEM((rows, n, d), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="cached_attention",
    )(mask.reshape(mask_rows, 1, s_k).astype(jnp.int32),
      # [b, s_q, kv, rep, d] -> [b, kv, s_q x rep, d]
      jnp.transpose(q.reshape(b, s_q, h_kv, rep, d),
                    (0, 2, 1, 3, 4)).reshape(b, h_kv, n, d),
      k.reshape(b, s_k, h_kv * d), v.reshape(b, s_k, h_kv * d))
    return jnp.transpose(out.reshape(b, h_kv, s_q, rep, d),
                         (0, 2, 1, 3, 4)).reshape(b, s_q, h, d)
