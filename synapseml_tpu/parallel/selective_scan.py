"""Mamba-1's selective scan: a recurrence whose decay is a number of its own
for every (channel, state) pair and position, in three forms of one
arithmetic.

For a row, over positions ``t`` in order, channel ``c``, state index ``j``::

    delta[t, c] = softplus(delta_raw[t, c] + delta_bias[c])
    s[j, c]    <- exp(delta[t, c] A[c, j]) s[j, c] + delta[t, c] B[t, j] u[t, c]
    y[t, c]     = sum_j C[t, j] s[j, c] + D[c] u[t, c]
    out[t, c]   = y[t, c] silu(z[t, c])

all in float32 whatever the operands' type; ``out`` is rounded once, to
``u``'s type. This is the published ``selective_scan_fn(u, delta, A, B, C, D,
z, delta_bias, delta_softplus=True, return_last_state=True)`` with positions
before channels (``u``, ``delta``, ``z`` ``[rows, S, d]``; ``B``, ``C``
``[rows, S, n]``; ``A [d, n]``) and the state laid out ``[rows, n, d]``,
channels minor: 16 states on the minor axis would be padded eight times over
by the (8, 128) tiling.

There is no chunked matrix form of this recurrence (Mamba-2's scalar decay a
head has one): the work is ``d x n`` multiply-adds and exponentials a
position, on the vector unit. What the forms differ in is where the state
lives between positions:

- :func:`scan_form`: ``lax.scan`` over positions; the state crosses HBM every
  position. Any backend, any shape.
- :func:`step_form`: one position (a generating loop's body): plain
  ``jax.numpy`` on ``[rows, n, d]``, which XLA fuses into a pass or two over
  the state.
- :func:`kernel_form`: one Pallas kernel. A grid step owns one row and a
  block of channels; its state ``[n, d_block]`` stays in VMEM while the step
  walks its block of positions, and across the blocks of positions of that
  row (the last grid axis, ``arbitrary``); the operands are read once, where
  they lie; the state leaves once, at the end.
"""

from __future__ import annotations

__all__ = ["scan_form", "step_form", "kernel_form", "kernel_takes"]

# positions a step of the kernel's inner loop takes: the float32 sublane tile
_GROUP = 8
# channels a grid step owns, at most. What a position costs apart from its
# channels (the lane broadcasts of its B and C columns, the loop's own steps)
# is shared by the block: on a v5e the cell's scan reads 14.03 / 7.10 / 4.54 /
# 3.98 ms a layer at 128 / 256 / 512 / 1,024 channels (tools/
# selective_scan_forms.py; PERF.md section 6, PR 39)
_CHANNELS = 1024
# what a grid step's blocks may take of VMEM: three operands and the result,
# each fetched or written while the one before is used (half of the 16 MiB a
# v5e kernel has by default; the rest is the state, the narrow operands and
# a group's float32 temporaries)
_STEP_VMEM = 8 << 20


def _softplus(x):
    """``log(1 + exp(x))``, linear beyond 20 as the published code's."""
    import jax.numpy as jnp

    return jnp.where(x > 20.0, x, jnp.log1p(jnp.exp(jnp.minimum(x, 20.0))))


def _one_position(state, dl, x, a_t, b, c):
    """``state [rows, n, d]`` through one position: ``dl``, ``x`` ``[rows,
    d]`` (the step and the input, float32), ``a_t [n, d]``, ``b``, ``c``
    ``[rows, n]``; -> the new state and ``sum_j c[j] state[j]`` ``[rows,
    d]``."""
    import jax.numpy as jnp

    state = jnp.exp(dl[:, None, :] * a_t) * state \
        + (dl * x)[:, None, :] * b[:, :, None]
    return state, jnp.sum(state * c[:, :, None], axis=1)


def _prepared(u, delta, A, D, delta_bias, state_in, delta_softplus):
    """Float32 views of what the plain forms need: the step ``[rows, S, d]``,
    ``A`` as ``[n, d]``, ``D`` and the entering state."""
    import jax.numpy as jnp

    rows, _, d = u.shape
    dl = delta.astype(jnp.float32) + jnp.asarray(delta_bias, jnp.float32)
    if delta_softplus:
        dl = _softplus(dl)
    state = jnp.zeros((rows, A.shape[1], d), jnp.float32) \
        if state_in is None else jnp.asarray(state_in, jnp.float32)
    return dl, jnp.asarray(A, jnp.float32).T, jnp.asarray(D, jnp.float32), \
        state


def _gated(y, z, dtype):
    import jax
    import jax.numpy as jnp

    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)


def scan_form(u, delta, A, B, C, D, z, delta_bias, state_in=None,
              delta_softplus: bool = True):
    """The recurrence as ``lax.scan`` over positions (module docstring); ->
    ``(out [rows, S, d], state_out [rows, n, d] float32)``."""
    import jax
    import jax.numpy as jnp

    dl, a_t, skip, state = _prepared(u, delta, A, D, delta_bias, state_in,
                                     delta_softplus)
    x = u.astype(jnp.float32)

    def step(state, at):
        dl_t, x_t, b_t, c_t = at
        state, y = _one_position(state, dl_t, x_t, a_t, b_t, c_t)
        return state, y + skip * x_t

    by_position = [jnp.moveaxis(v, 1, 0) for v in (
        dl, x, B.astype(jnp.float32), C.astype(jnp.float32))]
    state, y = jax.lax.scan(step, state, by_position)
    return _gated(jnp.moveaxis(y, 0, 1), z, u.dtype), state


def step_form(u, delta, A, B, C, D, z, delta_bias, state_in=None,
              delta_softplus: bool = True):
    """One position (``S`` = 1) from ``state_in``: plain ``jax.numpy`` on
    ``[rows, n, d]``; -> ``(out [rows, 1, d], state_out)``."""
    import jax.numpy as jnp

    if u.shape[1] != 1:
        raise ValueError(f"step_form: {u.shape[1]} positions; one")
    dl, a_t, skip, state = _prepared(u, delta, A, D, delta_bias, state_in,
                                     delta_softplus)
    x = u[:, 0].astype(jnp.float32)
    state, y = _one_position(state, dl[:, 0], x, a_t,
                             B[:, 0].astype(jnp.float32),
                             C[:, 0].astype(jnp.float32))
    return _gated((y + skip * x)[:, None, :], z, u.dtype), state


def kernel_takes(s: int, d: int) -> bool:
    """Whether :func:`kernel_form` compiles for ``S`` positions of ``d``
    channels and is the form for them: more than one position, whole groups
    of 8 of them, channels in whole blocks of 128 lanes."""
    return s > 1 and s % _GROUP == 0 and d % 128 == 0


def _blocks(s: int, d: int, itemsize: int, channels: int = None,
            positions: int = None):
    """Channels and positions a grid step takes: the largest multiple of 128
    lanes up to ``channels`` that divides ``d``, then the largest multiple of
    8 positions that divides ``S`` whose eight blocks (``u``, ``delta``,
    ``z`` and the result, two of each in flight) fit ``_STEP_VMEM``: 512
    positions of 1,024 bfloat16 channels (a step's fixed cost is then under
    a hundredth of its work: 128 positions read 3 % slower at 4,096).
    ``positions`` caps them further."""
    d_block = next(c for c in range(min(channels or _CHANNELS, d) // 128
                                    * 128, 0, -128) if d % c == 0)
    most = min(_STEP_VMEM // (8 * d_block * itemsize), positions or s, s)
    s_block = next(p for p in range(most // _GROUP * _GROUP, 0, -_GROUP)
                   if s % p == 0)
    return d_block, s_block


def kernel_form(u, delta, A, B, C, D, z, delta_bias, state_in=None,
                delta_softplus: bool = True, channels: int = None,
                positions: int = None, interpret: bool = False):
    """The recurrence as ONE Pallas kernel (module docstring); -> ``(out
    [rows, S, d], state_out [rows, n, d] float32)``. ``channels`` and
    ``positions`` cap a grid step's block (:func:`_blocks`: for the tool's
    table and the tests);
    :func:`kernel_takes` says which shapes Mosaic compiles, the interpreter
    takes the same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, s, d = u.shape
    if not kernel_takes(s, d):
        raise ValueError(f"kernel_form: {s} positions of {d} channels: whole "
                         f"groups of {_GROUP} positions, more than one, and "
                         f"whole blocks of 128 channels")
    d_block, s_block = _blocks(s, d, u.dtype.itemsize, channels, positions)
    f32 = jnp.float32
    n = A.shape[1]

    def grouped(m):  # [rows, S, n] -> [rows, S / 8, n, 8]: a group's columns
        return jnp.swapaxes(m.astype(f32).reshape(rows, s // _GROUP, _GROUP,
                                                  n), 2, 3)

    operands = [u, delta, z, grouped(B), grouped(C), jnp.asarray(A, f32).T,
                jnp.asarray(D, f32).reshape(1, d),
                jnp.asarray(delta_bias, f32).reshape(1, d)]
    entering = state_in is not None
    if entering:
        operands.append(jnp.asarray(state_in, f32))
    groups = s_block // _GROUP
    n_s = s // s_block

    def kernel(u_ref, delta_ref, z_ref, b_ref, c_ref, a_ref, skip_ref,
               bias_ref, *rest):
        if entering:
            in_ref, out_ref, last_ref, state_ref, y_ref = rest
        else:
            out_ref, last_ref, state_ref, y_ref = rest
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _():
            state_ref[...] = in_ref[0] if entering \
                else jnp.zeros((n, d_block), f32)

        a = a_ref[...]                                        # (n, d_block)

        def one_group(g, state):
            at = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
            dl = delta_ref[0, at, :].astype(f32) + bias_ref[...]
            if delta_softplus:
                dl = _softplus(dl)
            x = u_ref[0, at, :].astype(f32)                   # (8, d_block)
            dx = dl * x
            b, c = b_ref[0, g], c_ref[0, g]                   # (n, 8)
            for i in range(_GROUP):
                state = jnp.exp(dl[i:i + 1, :] * a) * state \
                    + dx[i:i + 1, :] * b[:, i:i + 1]
                y_ref[i:i + 1, :] = jnp.sum(state * c[:, i:i + 1], axis=0,
                                            keepdims=True)
            gate = z_ref[0, at, :].astype(f32)
            y = (y_ref[...] + skip_ref[...] * x) * (gate * jax.nn.sigmoid(gate))
            out_ref[0, at, :] = y.astype(out_ref.dtype)
            return state

        state_ref[...] = jax.lax.fori_loop(0, groups, one_group,
                                           state_ref[...])

        @pl.when(j == n_s - 1)
        def _():
            last_ref[0] = state_ref[...]

    def along(r, c, j):     # a block of [rows, S, d]
        return r, j, c

    def of_channels(r, c, j):
        return 0, c

    def of_state(r, c, j):  # a block of [rows, n, d]
        return r, 0, c

    wide = pl.BlockSpec((1, s_block, d_block), along)
    narrow = pl.BlockSpec((1, groups, n, _GROUP), lambda r, c, j: (r, j, 0, 0))
    state_block = pl.BlockSpec((1, n, d_block), of_state)
    out, last = pl.pallas_call(
        kernel,
        grid=(rows, d // d_block, n_s),
        in_specs=[wide, wide, wide, narrow, narrow,
                  pl.BlockSpec((n, d_block), of_channels),
                  pl.BlockSpec((1, d_block), of_channels),
                  pl.BlockSpec((1, d_block), of_channels)]
        + [state_block] * entering,
        out_specs=[wide, state_block],
        out_shape=[jax.ShapeDtypeStruct((rows, s, d), u.dtype),
                   jax.ShapeDtypeStruct((rows, n, d), f32)],
        scratch_shapes=[pltpu.VMEM((n, d_block), f32),
                        pltpu.VMEM((_GROUP, d_block), f32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            # rows and blocks of channels are independent; the walk over
            # blocks of positions carries the state
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(*operands)
    return out, last
