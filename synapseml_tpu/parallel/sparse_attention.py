"""Learned sparse attention (DeepSeek Sparse Attention's selection, as
Keye-VL-2.0's ``sa_config`` declares it): a learned indexer scores every
visible key of a query, the ``topk`` best are kept, and attention reads only
those.

For a row, a query at position ``t`` and a key at ``s <= t``::

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])     j over the index heads
    S_t     = the min(t + 1, topk) positions of largest I[t, s],
              ties to the lower position
    o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, g(h)] * scale) v[s, g(h)]

``g(h)`` is the key-value head query head ``h`` shares (grouped-query
attention); one ``S_t`` serves every head. ``I`` is float32 (products of the
operands' type, accumulated in float32); so are the scores and the softmax,
and the probabilities are rounded to the values' type before the second
product, as ``flash.masked_attention`` does.

Layouts: ``q [N, s, H x d]``, ``k``, ``v`` ``[N, L, H_kv, d]`` (or ``[N, L,
H_kv x d]``), ``qI [N, s, H_I x dI]``, ``kI [N, L, dI]`` (one index key head), ``w [N, s, H_I]``; query
``i`` of row ``n`` sits at position ``start[n] + i`` and sees keys ``0 ..
start[n] + i``.

The selection is never a sort. A score's float32 bits map to an int32 ``key``
of the same order (:func:`order_keys`); the ``topk``-th largest key ``theta``
is built bit by bit, 32 counts of ``key >= candidate`` over the query's
visible keys, and where more keys tie at ``theta`` than are wanted, the
position ``p`` of the last one kept is built the same way from the
positions. Key ``s`` is then chosen where ``key > theta``, or ``key == theta``
and ``s <= p``: ``(theta, p)`` a query stands for its whole set.

- :func:`plain_form`: ``jax.numpy`` over every (query, key) score, the set by
  ``lax.top_k`` (ties to the lower index), attention over the gathered rows:
  the CPU's form and the kernels' oracle.
- :func:`index_select`: the indexer as ONE Pallas kernel. A grid step scores
  a block of queries against a block of index keys (each head one product,
  ``w`` and the ReLU on the vector unit) and keeps the keys in VMEM; at the
  last block the bisections give each query's ``(theta, p)``. With one
  query a row it also hands the keys out, for :func:`compact`.
- :func:`blockwise_form`: a prompt pass's attention as ONE Pallas kernel: a
  grid step takes a block of queries, every head, and a block of keys, scores
  the index again and holds the scores to the queries' sets under the flash
  kernel's online softmax. It reads key tiles, not rows (a row gather of
  2,048 rows for each of 65,536 queries is 268 M row moves a layer); a tile
  in which no query of the block chose a key (:func:`index_select` marks
  them) is skipped. No ``[S, S]`` array is ever written.
- :func:`compact` and :func:`gather_form`: a decode pass's attention. The
  chosen positions in position order (``topk`` of them, unused slots 0),
  then ONE Pallas kernel that DMAs exactly those rows of the bfloat16 caches,
  as they lie, into VMEM and attends over them: the cache is read ``topk``
  rows a row, not whole.
- :func:`prompt_form` and :func:`decode_form` put them together.
"""

from __future__ import annotations

import math

__all__ = ["order_keys", "plain_form", "index_select", "blockwise_form",
           "tile_words", "compact", "gather_form", "prompt_form",
           "decode_form", "prompt_takes", "decode_takes"]

_INT_MIN = -2 ** 31
# a masked key: below the key of every finite score
_MASKED = _INT_MIN
# theta where every visible key is chosen: below every finite score's key
_EVERY = _INT_MIN + 1
# queries a grid step of the indexer takes (sublanes), and keys (lanes)
_INDEX_QUERIES = 32
_INDEX_KEYS = 2048
# queries and keys a grid step of the blockwise attention takes
_BLOCK_Q = 128
_BLOCK_K = 512
_NEG = -1e30
_VMEM_LIMIT = 64 * 2 ** 20


def order_keys(scores):
    """float32 ``scores`` -> int32 keys of the same order (-0 as +0)."""
    import jax
    import jax.numpy as jnp

    scores = jnp.where(scores == 0, jnp.float32(0), scores)
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _index_scores(q_index, weights, k_index):
    """``I [N, s, L]`` float32 from ``qI [N, s, H_I, dI]``, ``w [N, s,
    H_I]``, ``kI [N, L, dI]``."""
    import jax
    import jax.numpy as jnp

    dots = jnp.einsum("nqhd,nkd->nqhk", q_index, k_index,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("nqh,nqhk->nqk", weights.astype(jnp.float32),
                      jax.nn.relu(dots))


def plain_form(q, k, v, q_index, k_index, weights, start=None, *, heads: int,
               kv_heads: int, topk: int, scale: float = None):
    """Learned sparse attention in ``jax.numpy`` (module docstring): every
    score of the index, ``lax.top_k`` for the set, attention over the
    gathered rows. -> ``[N, s, H x d]`` in ``v``'s type."""
    import jax
    import jax.numpy as jnp

    n, s, _ = q.shape
    length, index_heads = k.shape[1], weights.shape[-1]
    d = q.shape[-1] // heads
    scale = d ** -0.5 if scale is None else float(scale)
    start = jnp.zeros((n,), jnp.int32) if start is None \
        else jnp.asarray(start, jnp.int32)
    position = start[:, None] + jnp.arange(s)[None, :]              # [N, s]
    visible = jnp.arange(length)[None, None, :] <= position[..., None]
    index = _index_scores(q_index.reshape(n, s, index_heads, -1), weights,
                          k_index)
    index = jnp.where(index == 0, jnp.float32(0), index)
    kept = min(topk, length)
    _, chosen = jax.lax.top_k(jnp.where(visible, index, -jnp.inf), kept)
    valid = jnp.arange(kept)[None, None, :] < jnp.minimum(
        position + 1, topk)[..., None]                               # [N,s,K]
    rows = jnp.arange(n)[:, None, None]
    keys = k.reshape(n, length, kv_heads, d)[rows, chosen]     # [N,s,K,Hkv,d]
    values = v.reshape(n, length, kv_heads, d)[rows, chosen]
    rep = heads // kv_heads
    queries = q.reshape(n, s, kv_heads, rep, d)
    scores = jnp.einsum("nqgrd,nqkgd->nqgrk", queries, keys,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, :, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nqgrk,nqkgd->nqgrd", probs.astype(v.dtype), values,
                     preferred_element_type=jnp.float32)
    return out.reshape(n, s, heads * d).astype(v.dtype)


def _divisor(n: int, cap: int, unit: int = 128) -> int:
    """The largest multiple of ``unit`` that divides ``n`` and is at most
    ``cap`` (``n`` a multiple of ``unit``)."""
    return next(b for b in range(min(cap, n) // unit * unit, 0, -unit)
                if n % b == 0)


def _index_tile(qi_ref, w_ref, ki_ref):
    """A tile of ``I`` in a kernel: ``qi_ref [H_I, q, dI]``, ``w_ref [q,
    H_I]`` float32, ``ki_ref [k, dI]`` -> ``[q, k]`` float32; each index
    head's scores one product, its weight and the ReLU on the vector
    unit."""
    import jax
    import jax.numpy as jnp

    index = None
    for h in range(qi_ref.shape[0]):
        dots = jax.lax.dot_general(qi_ref[h], ki_ref[...],
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        term = w_ref[:, h:h + 1] * jnp.maximum(dots, 0.0)
        index = term if index is None else index + term
    return index


def _count(keys_s, rows: int, block: int, chunks, hit):
    """How many keys of the first ``chunks`` blocks of ``block`` lanes of the
    VMEM ref ``keys_s [rows, L]`` meet ``hit(keys, first position)``, a
    row: ``[rows, 1]`` int32."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def one_block(c, acc):
        at = pl.multiple_of(c * block, 128)
        got = hit(keys_s[:, pl.ds(at, block)], c * block).astype(jnp.int32)
        for lane in range(0, block, 128):
            acc = acc + got[:, lane:lane + 128]
        return acc

    acc = jax.lax.fori_loop(0, chunks, one_block,
                            jnp.zeros((rows, 128), jnp.int32))
    return jnp.sum(acc, axis=1, keepdims=True)


def _select(keys_s, position, chunks, *, rows: int, block: int, topk: int,
            position_bits: int):
    """``(theta, p)`` ``[rows, 1]`` of queries at ``position [rows, 1]`` from
    their keys in ``keys_s`` (module docstring)."""
    import jax
    import jax.numpy as jnp

    def count(hit):
        return _count(keys_s, rows, block, chunks, hit)

    def bit(b, theta_u):  # theta in the unsigned order, as int32 bits
        cand = theta_u | jnp.left_shift(jnp.int32(1), 31 - b)
        signed = cand ^ jnp.int32(_INT_MIN)
        enough = count(lambda keys, at: keys >= signed) >= topk
        return jnp.where(enough, cand, theta_u)

    theta = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows, 1), jnp.int32)
                              ) ^ jnp.int32(_INT_MIN)
    need = topk - count(lambda keys, at: keys > theta)
    ties = count(lambda keys, at: keys == theta)

    def tie_bit(b, last):  # the largest P with fewer than ``need`` ties < P
        cand = last | jnp.left_shift(jnp.int32(1), position_bits - 1 - b)

        def before(keys, at):
            lanes = at + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
            return (keys == theta) & (lanes < cand)

        return jnp.where(count(before) < need, cand, last)

    last = jax.lax.fori_loop(0, position_bits, tie_bit,
                             jnp.zeros((rows, 1), jnp.int32))
    every = position + 1 <= topk
    theta = jnp.where(every, jnp.int32(_EVERY), theta)
    last = jnp.where(every | (ties <= need), position, last)
    return theta, last


def index_select(q_index, weights, k_index, start, *, topk: int,
                 with_keys: bool = False, tile: int = 0,
                 interpret: bool = False):
    """The indexer's choice as ONE Pallas kernel (module docstring).

    ``q_index [N, H_I, s, dI]`` heads first, ``weights [N, s, H_I]``
    float32, ``k_index [N, L, dI]``, ``start [N]`` int32 -> ``sel [N, s,
    128]`` int32, lane 0 a query's ``theta`` and lane 1 its ``p``; with
    ``with_keys`` (one query a row) also its keys ``[N, s, L]`` int32,
    ``-2^31`` past its position; with a ``tile`` (a multiple of 128 that
    divides ``L``) also ``occupied [N, s / _INDEX_QUERIES, 1, T]`` int32,
    lane ``c`` 1 where a query of the block chose a key of positions ``c x
    tile ..``, ``T`` the tiles rounded up to 128 lanes. A grid step is a
    row, a block of queries and a block of keys: each index head's scores
    are one product, weighted and summed on the vector unit into the block's
    keys, kept in VMEM ``[q, L]``; the blocks of keys no query of the block
    sees are neither fetched nor scored. At the last block of keys the
    bisections run over the keys the block's queries see."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, index_heads, s, di = q_index.shape
    length = k_index.shape[1]
    block_q = min(s, _INDEX_QUERIES)
    block_k = _divisor(length, _INDEX_KEYS)
    n_k = length // block_k
    position_bits = max(1, (length - 1).bit_length())
    n_tiles = length // tile if tile else 0
    lanes_t = -(-n_tiles // 128) * 128

    def kernel(start_ref, qi_ref, w_ref, ki_ref, sel_ref, *rest):
        keys_s = rest[-1]
        row, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        first = start_ref[row] + i * block_q  # the block's first query
        last = first + block_q - 1

        @pl.when(j * block_k <= last)
        def _():
            index = _index_tile(qi_ref, w_ref, ki_ref)
            query = first + jax.lax.broadcasted_iota(jnp.int32, index.shape,
                                                     0)
            key = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                         index.shape, 1)
            keys = jnp.where(key <= query, order_keys(index),
                             jnp.int32(_MASKED))
            keys_s[:, pl.ds(pl.multiple_of(j * block_k, 128), block_k)] = keys
            if with_keys:
                rest[0][...] = keys

        if with_keys:
            @pl.when(j * block_k > last)
            def _():
                rest[0][...] = jnp.full((block_q, block_k), _MASKED,
                                        jnp.int32)

        @pl.when(j == n_k - 1)
        def _():
            position = first + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, 1), 0)
            theta, kept = _select(
                keys_s, position, jnp.minimum(last // block_k + 1, n_k),
                rows=block_q, block=block_k, topk=topk,
                position_bits=position_bits)
            lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, 128), 1)
            sel_ref[...] = jnp.where(lane == 0, theta,
                                     jnp.where(lane == 1, kept, 0))
            if tile:
                rest[-2][...] = _occupied(keys_s, theta, kept, tile,
                                          jnp.minimum(last // tile + 1,
                                                      n_tiles), lanes_t)

    def keys_block(b, i, j, start_ref):  # no fetch past what the block sees
        last = start_ref[b] + i * block_q + block_q - 1
        return b, jnp.minimum(j, last // block_k), 0

    out_shape = [jax.ShapeDtypeStruct((n, s, 128), jnp.int32)]
    out_specs = [pl.BlockSpec((None, block_q, 128),
                              lambda b, i, j, st: (b, i, 0))]
    if with_keys:
        out_shape.append(jax.ShapeDtypeStruct((n, s, length), jnp.int32))
        out_specs.append(pl.BlockSpec((None, block_q, block_k),
                                      lambda b, i, j, st: (b, i, j)))
    if tile:
        out_shape.append(jax.ShapeDtypeStruct((n, s // block_q, 1, lanes_t),
                                              jnp.int32))
        out_specs.append(pl.BlockSpec((None, None, 1, lanes_t),
                                      lambda b, i, j, st: (b, i, 0, 0)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, s // block_q, n_k),
            in_specs=[
                pl.BlockSpec((None, index_heads, block_q, di),
                             lambda b, i, j, st: (b, 0, i, 0)),
                pl.BlockSpec((None, block_q, index_heads),
                             lambda b, i, j, st: (b, i, 0)),
                pl.BlockSpec((None, block_k, di), keys_block),
            ],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((block_q, length), jnp.int32)]),
        out_shape=out_shape,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="sparse_index_select",
    )(jnp.asarray(start, jnp.int32), q_index, weights.astype(jnp.float32),
      k_index)
    return tuple(out) if with_keys or tile else out[0]


def _occupied(keys_s, theta, kept, tile: int, tiles, lanes: int):
    """``[1, lanes]`` int32: lane ``c < tiles`` 1 where a query of the
    block, whose keys are the VMEM ref ``keys_s [rows, L]`` and whose set
    ``(theta, kept)`` is, chose a key of positions ``c x tile ..``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def one_tile(c, row):
        at = pl.multiple_of(c * tile, 128)
        keys = keys_s[:, pl.ds(at, tile)]
        position = at + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
        got = ((keys > theta) | ((keys == theta) & (position <= kept))
               ).astype(jnp.int32)
        acc = got[:, 0:128]
        for off in range(128, tile, 128):
            acc = acc + got[:, off:off + 128]
        hits = jnp.sum(jnp.sum(acc, axis=1, keepdims=True), axis=0,
                       keepdims=True)                                # [1, 1]
        return jnp.where(lane == c, jnp.minimum(hits, 1), row)

    return jax.lax.fori_loop(0, tiles, one_tile,
                             jnp.zeros((1, lanes), jnp.int32))


def tile_words(occupied, group: int):
    """``occupied [N, B, 1, T]`` from :func:`index_select` -> the tiles
    chosen in blocks of ``group`` of its blocks of queries, as bits: ``[N x
    B / group x T / 32]`` int32 words, tile ``c`` bit ``c % 32`` of word ``c
    // 32``, small enough for scalar memory."""
    import jax
    import jax.numpy as jnp

    n, blocks, _, lanes = occupied.shape
    merged = occupied.reshape(n, blocks // group, group, lanes).max(2)
    bits = merged.reshape(n, blocks // group, lanes // 32, 32).astype(
        jnp.uint32) << jnp.arange(32, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(bits.sum(-1, dtype=jnp.uint32),
                                        jnp.int32).reshape(-1)


def blockwise_form(q, k, v, q_index, weights, k_index, sel, words, *,
                   heads: int, kv_heads: int, scale: float = None,
                   interpret: bool = False):
    """A prompt pass's selected attention as ONE Pallas kernel (module
    docstring): queries at positions ``0 .. S - 1`` against the same ``S``
    keys. ``q [N, S, H x d]``, ``k``, ``v`` ``[N, S, H_kv x d]``, ``q_index
    [N, H_I, S, dI]`` heads first, ``weights [N, S, H_I]`` float32,
    ``k_index [N, S, dI]``, ``sel [N, S, 128]`` from :func:`index_select`
    and ``words``, its tiles of ``_BLOCK_K`` keys chosen in blocks of
    ``_BLOCK_Q`` queries (:func:`tile_words`) -> ``[N, S, H x d]``. A grid
    step is a row, ``_BLOCK_Q`` queries with every head and ``_BLOCK_K``
    keys: the index's scores are taken again, a key is in a query's set
    where :func:`index_select`'s rule says so, and each head's scores go
    through the online softmax held to that set. Blocks of keys after the
    queries' own are skipped and not fetched; a tile no query of the block
    chose from is skipped."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, s, _ = q.shape
    index_heads, di = q_index.shape[1], q_index.shape[3]
    d = q.shape[-1] // heads
    rep = heads // kv_heads
    scale = d ** -0.5 if scale is None else float(scale)
    block_q, block_k = min(s, _BLOCK_Q), min(s, _BLOCK_K)
    n_k = s // block_k
    n_words = -(-n_k // 128) * 4  # 128 lanes of tiles a block, 32 a word

    def kernel(words_ref, q_ref, k_ref, v_ref, qi_ref, w_ref, ki_ref, sel_ref,
               o_ref, m_s, l_s, acc_s):
        b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        last = i * block_q + block_q - 1
        word = words_ref[(b * (s // block_q) + i) * n_words + j // 32]
        chose = jnp.right_shift(word, j % 32) & 1

        @pl.when(j == 0)
        def _():
            m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
            l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
            acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

        @pl.when((j * block_k <= last) & (chose == 1))
        def _():
            index = _index_tile(qi_ref, w_ref, ki_ref)
            keys = order_keys(index)
            query = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, index.shape, 0)
            key = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                         index.shape, 1)
            theta, kept = sel_ref[:, 0:1], sel_ref[:, 1:2]
            chosen = (key <= query) & ((keys > theta)
                                       | ((keys == theta) & (key <= kept)))
            for g in range(kv_heads):
                kg = k_ref[:, g * d:(g + 1) * d]
                vg = v_ref[:, g * d:(g + 1) * d]
                for r in range(rep):
                    h = g * rep + r
                    scores = jax.lax.dot_general(
                        q_ref[:, h * d:(h + 1) * d], kg,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    scores = jnp.where(chosen, scores, _NEG)
                    m_prev = m_s[h][:, 0:1]
                    m_new = jnp.maximum(m_prev,
                                        scores.max(-1, keepdims=True))
                    p = jnp.where(chosen, jnp.exp(scores - m_new), 0.0)
                    corr = jnp.exp(m_prev - m_new)
                    l_s[h] = l_s[h] * corr + p.sum(-1, keepdims=True)
                    acc_s[h] = acc_s[h] * corr + jax.lax.dot_general(
                        p.astype(vg.dtype), vg, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    m_s[h] = jnp.broadcast_to(m_new, (block_q, 128))

        @pl.when(j == n_k - 1)
        def _():
            for h in range(heads):
                o_ref[:, h * d:(h + 1) * d] = (
                    acc_s[h] / l_s[h][:, 0:1]).astype(o_ref.dtype)

    def keys_at(b, i, j, words):  # no fetch past the queries' own block
        return b, jnp.minimum(j, (i * block_q + block_q - 1) // block_k), 0

    def queries_at(b, i, j, words):
        return b, i, 0

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, s // block_q, n_k),
            in_specs=[
                pl.BlockSpec((None, block_q, heads * d), queries_at),
                pl.BlockSpec((None, block_k, kv_heads * d), keys_at),
                pl.BlockSpec((None, block_k, kv_heads * d), keys_at),
                pl.BlockSpec((None, index_heads, block_q, di),
                             lambda b, i, j, words: (b, 0, i, 0)),
                pl.BlockSpec((None, block_q, index_heads), queries_at),
                pl.BlockSpec((None, block_k, di), keys_at),
                pl.BlockSpec((None, block_q, 128), queries_at),
            ],
            out_specs=pl.BlockSpec((None, block_q, heads * d), queries_at),
            scratch_shapes=[pltpu.VMEM((heads, block_q, 128), jnp.float32),
                            pltpu.VMEM((heads, block_q, 128), jnp.float32),
                            pltpu.VMEM((heads, block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, s, heads * d), v.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="sparse_blockwise_attention",
    )(jnp.asarray(words, jnp.int32), q, k, v, q_index,
      weights.astype(jnp.float32), k_index, sel)


def compact(keys, sel, position, *, topk: int):
    """The positions one query a row chose, in position order: ``keys [N,
    L]`` and ``sel [N, 128]`` from :func:`index_select`, ``position [N]``
    -> ``(index [N, topk] int32, count [N] int32)``, slots from ``count`` on
    holding 0. A count of chosen keys by block of 128 positions, their
    running sum, then the block and the place in it of each slot: nothing
    of ``L`` elements is scattered or sorted."""
    import jax.numpy as jnp

    n, length = keys.shape
    at = jnp.arange(length, dtype=jnp.int32)[None, :]
    theta, kept = sel[:, 0:1], sel[:, 1:2]
    chosen = (at <= position[:, None]) & (
        (keys > theta) | ((keys == theta) & (at <= kept)))
    blocks = chosen.reshape(n, length // 128, 128).astype(jnp.int32)
    per_block = blocks.sum(-1)
    ends = jnp.cumsum(per_block, axis=-1)                          # [N, C]
    slot = jnp.arange(topk, dtype=jnp.int32)
    block_of = jnp.sum(ends[:, None, :] <= slot[None, :, None], axis=-1)
    block_of = jnp.minimum(block_of, length // 128 - 1)            # [N, K]
    rank = slot[None, :] - jnp.take_along_axis(ends - per_block, block_of,
                                               axis=-1)
    lanes = jnp.take_along_axis(blocks, block_of[..., None], axis=1)
    lane = jnp.sum(jnp.cumsum(lanes, axis=-1) <= rank[..., None], axis=-1)
    count = ends[:, -1]
    index = jnp.where(slot[None, :] < count[:, None], block_of * 128 + lane,
                      0)
    return index.astype(jnp.int32), count.astype(jnp.int32)


def gather_form(q, k, v, index, count, *, heads: int, kv_heads: int,
                scale: float = None, interpret: bool = False):
    """One query a row against the rows ``index`` of the caches, as ONE
    Pallas kernel: ``q [N, H, d]``, ``k``, ``v`` ``[N, L, H_kv, d]``
    bfloat16 as they lie (a position a leading index: a DMA takes no slice
    of a tiled dimension that is not whole tiles), ``index [N, topk]`` and ``count [N]`` from
    :func:`compact` -> ``[N, H, d]``. A grid step is a row: one DMA a chosen
    row of each cache (slots past ``count`` fetch row 0 and weigh nothing)
    into VMEM, then every head's scores against its key-value head's rows,
    the softmax in float32 and the context."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, _, d = q.shape
    topk = index.shape[1]
    rep = heads // kv_heads
    if k.ndim != 4 or v.shape != k.shape:
        raise ValueError(f"gather_form: caches {k.shape} and {v.shape}: "
                         f"[N, L, H_kv, d] both")
    scale = d ** -0.5 if scale is None else float(scale)

    def kernel(count_ref, index_ref, q_ref, k_hbm, v_hbm, o_ref, k_rows,
               v_rows, sems):
        row = pl.program_id(0)

        def copies(j, at):
            return (pltpu.make_async_copy(k_hbm.at[row, pl.ds(at, 1)],
                                          k_rows.at[pl.ds(j, 1)], sems.at[0]),
                    pltpu.make_async_copy(v_hbm.at[row, pl.ds(at, 1)],
                                          v_rows.at[pl.ds(j, 1)], sems.at[1]))

        def issue(j, carry):
            for c in copies(j, index_ref[0, j]):
                c.start()
            return carry

        def wait(j, carry):
            for c in copies(0, 0):
                c.wait()
            return carry

        jax.lax.fori_loop(0, topk, issue, 0)
        jax.lax.fori_loop(0, topk, wait, 0)
        group = jax.lax.broadcasted_iota(jnp.int32, (heads, topk), 0) // rep
        slot = jax.lax.broadcasted_iota(jnp.int32, (heads, topk), 1)
        scores = jnp.full((heads, topk), _NEG, jnp.float32)
        for g in range(kv_heads):
            got = jax.lax.dot_general(
                q_ref[...], k_rows[:, g, :],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            scores = jnp.where(group == g, got, scores)
        scores = jnp.where(slot < count_ref[row], scores * scale, -jnp.inf)
        p = jnp.exp(scores - scores.max(-1, keepdims=True))
        p = (p * (1.0 / p.sum(-1, keepdims=True))).astype(v_rows.dtype)
        out = jnp.zeros((heads, d), jnp.float32)
        for g in range(kv_heads):
            out = out + jax.lax.dot_general(
                jnp.where(group == g, p, jnp.zeros_like(p)),
                v_rows[:, g, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((None, 1, topk), lambda b, c: (b, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((None, heads, d), lambda b, c: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, heads, d), lambda b, c: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((topk, kv_heads, d), k.dtype),
                            pltpu.VMEM((topk, kv_heads, d), v.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((n, heads, d), v.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="sparse_gather_attention",
    )(jnp.asarray(count, jnp.int32),
      jnp.asarray(index, jnp.int32).reshape(n, 1, topk), q, k, v)


def _heads_first(q_index, index_heads: int):
    """``[N, s, H_I x dI]`` -> ``[N, H_I, s, dI]``."""
    import jax.numpy as jnp

    n, s, _ = q_index.shape
    return jnp.transpose(q_index.reshape(n, s, index_heads, -1), (0, 2, 1, 3))


def prompt_takes(q_shape, k_shape, index_shape, *, heads: int,
                 kv_heads: int) -> bool:
    """Whether :func:`prompt_form` compiles for these shapes: ``S`` queries
    against their own ``S`` keys, ``S`` a multiple of the blocks, one head
    size of a multiple of 128 lanes."""
    n, s, wide = q_shape
    d = wide // heads
    return (k_shape[1] == s and s % _BLOCK_Q == 0
            and s % min(s, _BLOCK_K) == 0 and d % 128 == 0 and heads % kv_heads == 0
            and math.prod(k_shape[2:]) == kv_heads * d
            and index_shape[-1] <= 128)


def decode_takes(q_shape, k_shape, index_shape, *, heads: int, kv_heads: int,
                 topk: int) -> bool:
    """Whether :func:`decode_form` compiles for these shapes: one query a
    row, a cache of whole blocks of 128 positions holding at least ``topk``,
    one head size of a multiple of 128 lanes."""
    n, s, wide = q_shape
    d = wide // heads
    length = k_shape[1]
    return (s == 1 and length % 128 == 0 and topk <= length
            and topk % 8 == 0 and d % 128 == 0 and heads % kv_heads == 0
            and tuple(k_shape[2:]) == (kv_heads, d)
            and index_shape[-1] <= 128)


def prompt_form(q, k, v, q_index, k_index, weights, *, heads: int,
                kv_heads: int, topk: int, scale: float = None,
                interpret: bool = False):
    """A prompt pass (queries at ``0 .. S - 1``, keys the same ``S``):
    :func:`index_select` with the tiles its blocks chose from, then
    :func:`blockwise_form`."""
    import jax.numpy as jnp

    n, s, _ = q.shape
    k, v = k.reshape(n, s, -1), v.reshape(n, s, -1)
    index_heads = weights.shape[-1]
    q_index = _heads_first(q_index, index_heads)
    weights = weights.astype(jnp.float32)
    sel, occupied = index_select(q_index, weights, k_index,
                                 jnp.zeros((n,), jnp.int32), topk=topk,
                                 tile=min(s, _BLOCK_K), interpret=interpret)
    words = tile_words(occupied, min(s, _BLOCK_Q) // min(s, _INDEX_QUERIES))
    return blockwise_form(q, k, v, q_index, weights, k_index, sel, words,
                          heads=heads, kv_heads=kv_heads, scale=scale,
                          interpret=interpret)


def decode_form(q, k, v, q_index, k_index, weights, start, *, heads: int,
                kv_heads: int, topk: int, scale: float = None,
                interpret: bool = False):
    """One query a row at ``start [N]`` against the caches ``[N, L, H_kv,
    d]``: the indexer's kernel with its keys, :func:`compact`, then
    :func:`gather_form`."""
    import jax.numpy as jnp

    n, _, wide = q.shape
    index_heads = weights.shape[-1]
    start = jnp.asarray(start, jnp.int32)
    sel, keys = index_select(_heads_first(q_index, index_heads),
                             weights.astype(jnp.float32), k_index, start,
                             topk=topk, with_keys=True, interpret=interpret)
    index, count = compact(keys[:, 0], sel[:, 0], start, topk=topk)
    out = gather_form(q.reshape(n, heads, wide // heads), k, v, index, count,
                      heads=heads, kv_heads=kv_heads, scale=scale,
                      interpret=interpret)
    return out.reshape(n, 1, wide)
