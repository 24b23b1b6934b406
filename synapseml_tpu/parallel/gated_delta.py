"""The gated delta rule (Gated DeltaNet: Yang, Kautz and Hatamizadeh,
arXiv:2412.06464) as the published ``chunk_gated_delta_rule(q, k, v, g,
beta, initial_state=..., output_final_state=True,
use_qk_l2norm_in_kernel=True)`` computes it at its default ``scale``, in
four forms of one arithmetic.

For a row and a head, over positions ``t`` in order (``q``, ``k`` of ``dk``
numbers, ``v`` of ``dv``, ``g`` and ``beta`` scalars)::

    q_t <- q_t / |q_t| / sqrt(dk),  k_t <- k_t / |k_t|
    S   <- exp(g_t) S                                 S [dk, dv]
    u_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

all in float32 whatever the operands' type; ``o`` is rounded once, to
``v``'s type. ``|x|`` is ``sqrt(sum x^2 + 1e-6)``, the published ``l2norm``.
Operands are laid out as the published code takes them, positions before
heads: ``q``, ``k`` ``[rows, S, H, dk]``, ``v`` ``[rows, S, H, dv]``, ``g``,
``beta`` ``[rows, S, H]``. The state is ``[rows, dk, H x dv]`` float32: head
``h``'s ``[dk, dv]`` is columns ``h dv .. (h + 1) dv``, the heads side by side
along the lanes. The published ``[rows, H, dk, dv]`` would pad a 96 x 192
state to 96 x 256 under the (8, 128) tiling; ``[rows, 96, 30 x 192]`` is
whole tiles.

- :func:`chunked_form`: the WY form over chunks of ``C`` positions (any
  backend, any length). Inside a chunk, with ``G_t = exp(sum of g up to
  t)``, the pseudo-values ``U`` solve ``(I + A) U = diag(beta) V - diag(beta
  G) K S0`` where ``A[t, s] = beta_t (G_t / G_s) k_t . k_s`` for ``s < t``; one
  triangular solve gives ``U~`` and ``W`` with ``U = U~ - W S0``. Then ``O =
  diag(G) Q S0 + (Q K^T * M) U`` (``M[t, s] = G_t / G_s``, ``s <= t``) and the
  state leaving is ``G_C S0 + (K * G_C / G)^T U``: matrix products, and the
  state crosses HBM once a chunk. Padded positions carry ``g`` 0 and
  ``beta`` 0 and leave the state as it was.
- :func:`chunked_kernel_form`: the same form as ONE Pallas kernel: a grid
  step is a row and a chunk, the row's state and the chunk's float32
  products stay in VMEM, and the state crosses HBM once a row.
- :func:`step_form`: one position in plain ``jax.numpy`` over the state as it
  lies: a head's ``k`` and ``q`` are spread over its ``dv`` lanes.
- :func:`kernel_form`: one position as ONE Pallas kernel: a grid step owns a
  row's state ``[dk, H x dv]`` in VMEM, reads it once and writes it once (in
  place), and walks the heads in groups whose lanes are whole 128-lane
  blocks.
"""

from __future__ import annotations

import math

__all__ = ["chunked_form", "chunked_kernel_form", "chunked_kernel_takes",
           "step_form", "kernel_form", "kernel_takes"]

# positions of a chunk of the WY form (the published kernels' 64)
_CHUNK = 64
# rows of a group: the float32 sublane tile the kernel's per-row operands
# come in
_ROWS = 8
# the published ``l2norm``'s epsilon
_EPS = 1e-6
# VMEM the chunked kernel may hold (v5e has 128 MiB): its double-buffered
# blocks and a chunk's products
_VMEM_LIMIT = 64 * 2 ** 20


def _normed(q, k):
    """``q`` and ``k`` over their norms a head, ``q`` over ``sqrt(dk)``,
    float32."""
    import jax
    import jax.numpy as jnp

    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + _EPS)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + _EPS)
    return q * q.shape[-1] ** -0.5, k


def chunked_form(q, k, v, g, beta, state_in=None, chunk: int = _CHUNK):
    """The rule over ``S`` positions in the WY form (module docstring); ->
    ``(out [rows, S, H, dv], state_out [rows, dk, H x dv] float32)``."""
    import jax
    import jax.numpy as jnp

    f32, hp = jnp.float32, jax.lax.Precision.HIGHEST
    rows, s, h, dk = q.shape
    dv = v.shape[-1]
    q, k = _normed(q, k)
    c = min(chunk, s)
    pad = (-s) % c
    n = (s + pad) // c

    def chunks(x):  # [rows, S, H, ...] -> [rows, H, n, C, ...]
        x = jnp.pad(x.astype(f32), [(0, 0), (0, pad)]
                    + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(rows, n, c, *x.shape[2:]), 3, 1)

    qc, kc, vc, gc, bc = (chunks(x) for x in (q, k, v, g, beta))
    gam = jnp.cumsum(gc, axis=-1)                       # [rows, H, n, C]
    t = jnp.arange(c)
    upto = t[:, None] >= t[None, :]
    # G_t / G_s for s <= t, 0 above the diagonal (no exponent there: it grows)
    decay = jnp.exp(jnp.where(upto, gam[..., :, None] - gam[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("...td,...sd->...ts", kc, kc, precision=hp)
    a = jnp.where(t[:, None] > t[None, :], kk * decay * bc[..., None], 0.0)
    rhs = jnp.concatenate([vc * bc[..., None],
                           kc * (bc * jnp.exp(gam))[..., None]], axis=-1)
    uw = jax.lax.linalg.triangular_solve(a, rhs, left_side=True, lower=True,
                                         unit_diagonal=True)
    qk = jnp.einsum("...td,...sd->...ts", qc, kc, precision=hp) * decay
    q_decayed = qc * jnp.exp(gam)[..., None]
    k_to_end = kc * jnp.exp(gam[..., -1:] - gam)[..., None]
    through = jnp.exp(gam[..., -1])                     # [rows, H, n]

    def one_chunk(state, at):
        uw_c, qk_c, q_c, k_c, through_c = at
        u = uw_c[..., :dv] - jnp.einsum("rhtk,rhkv->rhtv", uw_c[..., dv:],
                                        state, precision=hp)
        o = jnp.einsum("rhtk,rhkv->rhtv", q_c, state, precision=hp) \
            + jnp.einsum("rhts,rhsv->rhtv", qk_c, u, precision=hp)
        state = state * through_c[..., None, None] \
            + jnp.einsum("rhsk,rhsv->rhkv", k_c, u, precision=hp)
        return state, o

    state = jnp.zeros((rows, h, dk, dv), f32) if state_in is None else \
        jnp.swapaxes(jnp.asarray(state_in, f32).reshape(rows, dk, h, dv), 1, 2)
    state, o = jax.lax.scan(one_chunk, state, [
        jnp.moveaxis(x, 2, 0) for x in (uw, qk, q_decayed, k_to_end, through)])
    # o [n, rows, H, C, dv] -> [rows, n C, H, dv]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        rows, n * c, h, dv)[:, :s]
    return o.astype(v.dtype), \
        jnp.swapaxes(state, 1, 2).reshape(rows, dk, h * dv)


def _chunked_vmem_bytes(h: int, dk: int, dv: int) -> int:
    """VMEM of :func:`chunked_kernel_form`'s blocks, each held twice by the
    pipeline, at chunks of ``_CHUNK`` and float32 operands (the most they
    can be): ``q`` and ``k`` (``dk`` padded to a lane block), ``v`` and the
    output, the entering and leaving state, ``g`` and ``beta``."""
    lanes = -(-dk // 128) * 128
    qk = 2 * h * _CHUNK * lanes * 4
    by_lanes = 2 * _CHUNK * h * dv * 4
    state = 2 * dk * h * dv * 4
    per_head = 2 * _CHUNK * -(-h // 128) * 128 * 4
    return 2 * (qk + by_lanes + state + per_head)


def chunked_kernel_takes(rows: int, s: int, h: int, dk: int, dv: int) -> bool:
    """Whether :func:`chunked_kernel_form` compiles for these shapes: more
    than one position (any number of rows, any length: the last chunk is
    padded), a head's ``dk`` in whole sublane tiles, the heads' lanes in
    whole groups (:func:`_group`), and a row's blocks in half the VMEM the
    kernel asks for."""
    del rows
    return s > 1 and dk % 8 == 0 and (h * dv) % _group(dv) == 0 \
        and _chunked_vmem_bytes(h, dk, dv) <= _VMEM_LIMIT // 2


def _unit_lower_inverse(a, c: int, per: int):
    """``(I + A)^-1`` of ``per`` strictly lower triangular ``[C, C]`` side by
    side (``a`` ``[C, per C]``) by forward substitution, a column at a time:
    once the columns before ``j`` are taken out, row ``j`` is final, and
    every later row takes out ``A[t, j]`` times it. Float32 on the vector
    unit; the heads of a group share each step's lanes, and a step leaves
    the sublane tiles above row ``j + 1`` as they are."""
    import jax
    import jax.numpy as jnp

    lane = jax.lax.broadcasted_iota(jnp.int32, (c, per * c), 1)
    t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    eye = jnp.where(t == jax.lax.broadcasted_iota(jnp.int32, (c, c), 1),
                    1.0, 0.0).astype(jnp.float32)
    x = jnp.concatenate([eye] * per, axis=1)
    for j in range(c - 1):
        col = a[:, j:j + 1]
        for i in range(1, per):
            col = jnp.where(lane >= i * c, a[:, i * c + j:i * c + j + 1], col)
        below = (j + 1) // 8 * 8
        taken = x[below:] - col[below:] * x[j:j + 1, :]
        x = jnp.concatenate([x[:below], taken]) if below else taken
    return x


def chunked_kernel_form(q, k, v, g, beta, state_in=None, chunk: int = _CHUNK,
                        interpret: bool = False):
    """The WY form of :func:`chunked_form` as ONE Pallas kernel; -> ``(out
    [rows, S, H, dv], state_out [rows, dk, H x dv] float32)``.

    A grid step is a row and a chunk, the chunks in order: the row's state
    ``[dk, H x dv]`` lives in the leaving state's VMEM block across them
    (from ``state_in``, or zero, at the first) and goes to HBM once, after
    the last, in the layout the decode step takes. Inside a step the heads go
    in groups of :func:`_group` lanes (two heads of 192), a ``fori_loop``
    over groups; for each head ``q`` and ``k`` normed as :func:`_normed`
    does, ``G`` the cumulative ``g`` (every head at once, by doubling
    shifts down the chunk), the strictly lower ``A``, ``(I + A)^-1`` by
    forward substitution (:func:`_unit_lower_inverse`, the group's heads side
    by side), then ``W``, ``U~``, ``U = U~ - W S``, ``O = diag(G) Q S + (Q
    K^T * M) U`` and ``S <- G_C S + (K * G_C / G)^T U``: float32 throughout,
    every product at ``HIGHEST``, as :func:`chunked_form` has them. ``O`` is
    rounded once, to ``v``'s type, into ``[rows, S, H x dv]``. ``q`` and
    ``k`` cross HBM once more, heads first ``[rows, H, S, dk]`` in their own
    type, so that a head is a leading index. Positions past ``S`` are
    padded with ``g`` 0 and ``beta`` 0 and leave the state as it was.
    :func:`chunked_kernel_takes` says which shapes Mosaic compiles; the
    interpreter takes the same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, hp = jnp.float32, jax.lax.Precision.HIGHEST
    rows, s, h, dk = q.shape
    dv = v.shape[-1]
    if not chunked_kernel_takes(rows, s, h, dk, dv) or chunk % 8:
        raise ValueError(f"chunked_kernel_form: {rows} rows x {s} positions "
                         f"of {h} heads of {dk} / {dv} in chunks of {chunk}: "
                         f"more than one position, dk of whole sublane "
                         f"tiles, the heads' lanes in groups of {_group(dv)}, "
                         f"chunks of whole sublane tiles")
    c = chunk
    pad = (-s) % c
    n = (s + pad) // c
    wide, width = h * dv, _group(dv)
    per = width // dv

    def padded(x):
        return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))

    operands = [jnp.swapaxes(padded(q), 1, 2), jnp.swapaxes(padded(k), 1, 2),
                padded(v).reshape(rows, n * c, wide),
                padded(g.astype(f32)), padded(beta.astype(f32))]
    if state_in is not None:
        operands.append(jnp.asarray(state_in, f32))

    def dot(a, b, dims=((1,), (0,))):
        return jax.lax.dot_general(a, b, (dims, ((), ())), precision=hp,
                                   preferred_element_type=f32)

    def kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *refs):
        out_ref, state_ref, g_rows_ref = refs[-3:]

        @pl.when(pl.program_id(1) == 0)
        def _():
            state_ref[0] = refs[0][0] if state_in is not None \
                else jnp.zeros((dk, wide), f32)

        t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        src = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        at = jax.lax.broadcasted_iota(jnp.int32, (c, h), 0)
        head_lane = jax.lax.broadcasted_iota(jnp.int32, (c, h), 1)
        gam = g_ref[0]                                  # [C, H]
        shift = 1
        while shift < c:
            gam = gam + jnp.where(at >= shift, pltpu.roll(gam, shift, 0), 0.0)
            shift *= 2
        g_rows_ref[...] = gam.T                         # [H, C]
        betas = beta_ref[0]

        def column(x, head):  # [C, H] -> the head's [C, 1]
            return jnp.sum(jnp.where(head_lane == head, x, 0.0), axis=1,
                           keepdims=True)

        def group(i, carry):
            lanes = pl.ds(pl.multiple_of(i * width, 128), width)
            state = state_ref[0, :, lanes]
            values = v_ref[0, :, lanes].astype(f32)
            heads = []
            for j in range(per):
                head = i * per + j
                qn, kn = _normed(q_ref[0, head], k_ref[0, head])
                gcol, bcol = column(gam, head), column(betas, head)
                # G_t / G_s for s <= t, 0 above (no exponent there)
                decay = jnp.where(t >= src, jnp.exp(jnp.where(
                    t >= src, gcol - g_rows_ref[pl.ds(head, 1), :], 0.0)),
                    0.0)
                # Q K^T and K K^T as one product: two chunks of rows
                qk_kk = dot(jnp.concatenate([qn, kn]), kn, ((1,), (1,)))
                a = jnp.where(t > src, qk_kk[c:] * decay * bcol, 0.0)
                heads.append((qn, kn, gcol, bcol, qk_kk[:c] * decay, a))
            inverse = _unit_lower_inverse(
                jnp.concatenate([x[-1] for x in heads], axis=1), c, per)
            outs, states = [], []
            for j, (qn, kn, gcol, bcol, qk, _) in enumerate(heads):
                inv = inverse[:, j * c:(j + 1) * c]
                s_j = state[:, j * dv:(j + 1) * dv]
                grown = jnp.exp(gcol)
                # W S and diag(G) Q S as one product
                ws_qs = dot(jnp.concatenate([dot(inv, kn * (bcol * grown)),
                                             qn * grown]), s_j)
                u = dot(inv, values[:, j * dv:(j + 1) * dv] * bcol) \
                    - ws_qs[:c]
                outs.append(ws_qs[c:] + dot(qk, u))
                last = gcol[c - 1:c, :]
                states.append(s_j * jnp.exp(last) + dot(
                    kn * jnp.exp(last - gcol), u, ((0,), (0,))))
            state_ref[0, :, lanes] = jnp.concatenate(states, axis=1)
            out_ref[0, :, lanes] = jnp.concatenate(outs, axis=1).astype(
                out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, wide // width, group, 0)

    def of_chunk(r, ci):
        return r, ci, 0

    def of_row(r, ci):
        return r, 0, 0

    heads_block = pl.BlockSpec((1, h, c, dk), lambda r, ci: (r, 0, ci, 0))
    lanes_block = pl.BlockSpec((1, c, wide), of_chunk)
    per_head_block = pl.BlockSpec((1, c, h), of_chunk)
    state_block = pl.BlockSpec((1, dk, wide), of_row)
    out, state = pl.pallas_call(
        kernel,
        grid=(rows, n),
        in_specs=[heads_block, heads_block, lanes_block, per_head_block,
                  per_head_block] + [state_block] * (state_in is not None),
        out_specs=[lanes_block, state_block],
        out_shape=[jax.ShapeDtypeStruct((rows, n * c, wide), v.dtype),
                   jax.ShapeDtypeStruct((rows, dk, wide), f32)],
        scratch_shapes=[pltpu.VMEM((h, c), f32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            # the state is carried from a row's chunk to the next
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gated_delta_chunked",
    )(*operands)
    return out.reshape(rows, n * c, h, dv)[:, :s], state


def _lanes(x, dv: int):
    """``[..., H]`` -> ``[..., H x dv]``: a head's number over its lanes."""
    import jax.numpy as jnp

    return jnp.repeat(x.astype(jnp.float32), dv, axis=-1)


def step_form(q, k, v, g, beta, state_in=None):
    """One position (``S`` = 1) from ``state_in`` in plain ``jax.numpy`` on
    ``[rows, dk, H x dv]``; -> ``(out [rows, 1, H, dv], state_out)``."""
    import jax.numpy as jnp

    rows, s, h, dk = q.shape
    dv = v.shape[-1]
    if s != 1:
        raise ValueError(f"step_form: {s} positions; one")
    q, k = _normed(q[:, 0], k[:, 0])
    k_l = _lanes(jnp.swapaxes(k, 1, 2), dv)             # [rows, dk, H dv]
    q_l = _lanes(jnp.swapaxes(q, 1, 2), dv)
    state = jnp.zeros((rows, dk, h * dv), jnp.float32) if state_in is None \
        else jnp.asarray(state_in, jnp.float32)
    state = state * _lanes(jnp.exp(g[:, 0].astype(jnp.float32)), dv)[:, None]
    kv = jnp.sum(k_l * state, axis=1)                   # [rows, H dv]
    u = _lanes(beta[:, 0], dv) * (
        v[:, 0].astype(jnp.float32).reshape(rows, h * dv) - kv)
    state = state + k_l * u[:, None]
    o = jnp.sum(q_l * state, axis=1)
    return o.reshape(rows, 1, h, dv).astype(v.dtype), state


def _group(dv: int) -> int:
    """Lanes of the kernel's group of heads: whole heads in whole 128-lane
    blocks."""
    return dv * 128 // math.gcd(dv, 128)


def kernel_takes(rows: int, h: int, dk: int, dv: int) -> bool:
    """Whether :func:`kernel_form` compiles for these shapes: rows in whole
    groups of 8, a head's ``dk`` in whole sublane tiles, the heads' lanes in
    whole groups (:func:`_group`), at most 128 heads."""
    return rows % _ROWS == 0 and dk % 8 == 0 and (h * dv) % _group(dv) == 0 \
        and h <= 128


def kernel_form(q, k, v, g, beta, state_in=None, interpret: bool = False):
    """One position as ONE Pallas kernel (module docstring); -> ``(out [rows,
    1, H, dv], state_out [rows, dk, H x dv] float32)``, the state written
    where it was read. :func:`kernel_takes` says which shapes Mosaic
    compiles; the interpreter takes the same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    rows, s, h, dk = q.shape
    dv = v.shape[-1]
    if s != 1 or not kernel_takes(rows, h, dk, dv):
        raise ValueError(f"kernel_form: {rows} rows x {s} positions of {h} "
                         f"heads of {dk} / {dv}: one position, rows in "
                         f"groups of {_ROWS}, dk of whole sublane tiles, the "
                         f"heads' lanes in groups of {_group(dv)}")
    wide, width = h * dv, _group(dv)
    q, k = _normed(q[:, 0], k[:, 0])

    def columns(x):  # [rows, H, dk] -> [rows, dk, 128]: a head a lane
        return jnp.pad(jnp.swapaxes(x, 1, 2), ((0, 0), (0, 0), (0, 128 - h)))

    def by_group(x):  # [rows, H dv] -> [rows / 8, 8, H dv]
        return x.reshape(rows // _ROWS, _ROWS, wide)

    state = jnp.zeros((rows, dk, wide), f32) if state_in is None \
        else jnp.asarray(state_in, f32)
    operands = [state, columns(k), columns(q),
                by_group(v[:, 0].astype(f32).reshape(rows, wide)),
                by_group(_lanes(jnp.exp(g[:, 0].astype(f32)), dv)),
                by_group(_lanes(beta[:, 0], dv))]

    def kernel(state_ref, k_ref, q_ref, v_ref, decay_ref, beta_ref, out_ref,
               new_ref):
        at = pl.ds(pl.program_id(1), 1)           # the row within its group
        k_cols, q_cols = k_ref[0], q_ref[0]       # (dk, 128)
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)
        for first in range(0, wide, width):       # a group of heads
            lanes = slice(first, first + width)
            head = first // dv

            def spread(cols):  # head head + i's column over its dv lanes
                out = jnp.broadcast_to(cols[:, head:head + 1], (dk, width))
                for i in range(1, width // dv):
                    out = jnp.where(lane >= i * dv,
                                    cols[:, head + i:head + i + 1], out)
                return out

            k_l, q_l = spread(k_cols), spread(q_cols)
            state = state_ref[0, :, lanes] * decay_ref[0, at, lanes]
            kv = jnp.sum(k_l * state, axis=0, keepdims=True)
            u = beta_ref[0, at, lanes] * (v_ref[0, at, lanes] - kv)
            state = state + k_l * u
            new_ref[0, :, lanes] = state
            out_ref[0, at, lanes] = jnp.sum(q_l * state, axis=0,
                                            keepdims=True)

    def of_row(i, r):
        return i * _ROWS + r, 0, 0

    def of_group(i, r):
        return i, 0, 0

    state_block = pl.BlockSpec((1, dk, wide), of_row)
    cols_block = pl.BlockSpec((1, dk, 128), of_row)
    group_block = pl.BlockSpec((1, _ROWS, wide), of_group)
    out, new = pl.pallas_call(
        kernel,
        grid=(rows // _ROWS, _ROWS),
        in_specs=[state_block, cols_block, cols_block] + [group_block] * 3,
        out_specs=[group_block, state_block],
        out_shape=[jax.ShapeDtypeStruct((rows // _ROWS, _ROWS, wide), f32),
                   jax.ShapeDtypeStruct((rows, dk, wide), f32)],
        input_output_aliases={0: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            # a group's result block is written row by row across its steps
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_step",
    )(*operands)
    return out.reshape(rows, 1, h, dv).astype(v.dtype), new
