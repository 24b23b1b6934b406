#!/usr/bin/env python3
"""What the chip charges to move one row of a table by index --
``python tools/row_move_rates.py`` (PERF.md section 6, PR 31; the prices
``ops._held_picks_sum`` and ``tools/expert_combine_forms.py`` rest on).

One JSON line a probe, nanoseconds a row (five calls on the host's clock
after one that compiles):

- ``gather``: XLA's row gather ``table[ids]`` out of 393,216 rows, all of
  them and a quarter, by type and width (``ExpertFFN``'s rows are bfloat16
  ``[2688]``), with and without the hints a caller can give;
- ``scatter_set`` / ``scatter_add``: XLA's row scatter of 24,576 rows (one
  chunk of sorted pairs) into the table given, ``set`` with distinct ids and
  float32 ``add`` with ids that repeat, the table donated;
- ``dma``: a Pallas kernel that issues one DMA a wanted row (a row laid out
  as 16 sublane rows of 128 words, since Mosaic takes no slice of fewer than
  8 rows of a tiled array) and waits for them tile by tile.

``--rehearse-on-cpu`` runs the XLA probes at toy sizes and prints no time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROWS, CHUNK, TOKENS, K = 393216, 24576, 65536, 6
SUBLANES = 16  # sublane rows of 128 words that a packed [2688] bfloat16 row takes


def dma_rows(place, rows, *, tile):
    """One DMA a wanted row: ``place [k, n]`` names a row of ``rows``
    (``[ROWS * SUBLANES, 128]`` words) or -1; ``tile`` tokens a grid step."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, n = place.shape

    def kernel(place_ref, rows_ref, out_ref, buffer, semaphore):
        def issue(i, count):
            for s in range(k):
                p = place_ref[s, i]

                @pl.when(p >= 0)
                def _():
                    pltpu.make_async_copy(
                        rows_ref.at[pl.ds(pl.multiple_of(p * SUBLANES,
                                                         SUBLANES),
                                          SUBLANES), :],
                        buffer.at[pl.ds(pl.multiple_of(
                            (s * tile + i) * SUBLANES, SUBLANES),
                            SUBLANES), :],
                        semaphore).start()
                count = count + (p >= 0).astype(jnp.int32)
            return count

        def wait(_, carry):
            pltpu.make_async_copy(rows_ref.at[pl.ds(0, SUBLANES), :],
                                  buffer.at[pl.ds(0, SUBLANES), :],
                                  semaphore).wait()
            return carry

        lax.fori_loop(0, lax.fori_loop(0, tile, issue, jnp.int32(0)), wait, 0)
        # touch what arrived: one strided load a pick
        seen = jnp.zeros((8, 128), jnp.uint32)
        for s in range(k):
            seen = seen + buffer[pl.ds(s * tile * SUBLANES, 8,
                                       stride=SUBLANES), :]
        out_ref[...] = seen

    return pl.pallas_call(
        kernel, grid=(n // tile,),
        in_specs=[pl.BlockSpec((k, tile), lambda i: (0, i),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // tile * 8, 128), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((k * tile * SUBLANES, 128), jnp.uint32),
                        pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 * 2**20),
    )(place, rows)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse_on_cpu:
        print(f"no TPU here ({device.platform}); a time comes from the chip "
              f"alone", file=sys.stderr)
        return 3
    scale = 1 if on_chip else 256  # toy sizes for the rehearsal
    rows, chunk, tokens = ROWS // scale, CHUNK // scale, TOKENS // scale
    print(json.dumps({"device": {"platform": device.platform,
                                 "kind": device.device_kind},
                      "rows": rows, "rehearsal": not on_chip}), flush=True)

    def report(probe, moved, fn, *given, carried=False, **said):
        """``fn(*given)`` five times; where ``carried``, its result is the
        next call's first argument (a donated table)."""
        out = jax.block_until_ready(fn(*given))
        start = time.perf_counter()
        for _ in range(5):
            out = fn(*((out,) + given[1:] if carried else given))
        jax.block_until_ready(out)
        taken = (time.perf_counter() - start) / 5
        line = dict(probe=probe, rows_moved=moved, **said)
        if on_chip:
            line.update(ms=taken * 1e3, ns_a_row=taken * 1e9 / moved)
        print(json.dumps(line), flush=True)

    rng = np.random.default_rng(args.seed)
    every_row = jnp.asarray(rng.permutation(rows).astype(np.int32))
    quarter = every_row[:rows // 4]
    gather = jax.jit(lambda table, ids: table[ids])
    hinted = jax.jit(lambda table, ids, is_sorted: table.at[ids].get(
        mode="promise_in_bounds", unique_indices=True,
        indices_are_sorted=is_sorted), static_argnums=2)
    for dtype, width in ((jnp.bfloat16, 2688), (jnp.uint32, 1344),
                         (jnp.uint32, 1408), (jnp.float32, 2688),
                         (jnp.uint16, 2688), (jnp.bfloat16, 2816)):
        width //= 1 if on_chip else 64
        table = jnp.ones((rows, width), dtype)
        said = dict(dtype=jnp.dtype(dtype).name, width=width)
        report("gather", rows, gather, table, every_row, **said)
        report("gather", rows // 4, gather, table, quarter, **said)
        if dtype == jnp.bfloat16 and width == 2688 // (1 if on_chip else 64):
            report("gather", rows, hinted, table, every_row, False,
                   hints="in_bounds,unique", **said)
            report("gather", rows // 4, hinted, table, jnp.sort(quarter),
                   True, hints="in_bounds,unique,sorted", **said)
            report("scatter_set", chunk,
                   jax.jit(lambda table, ids, new: table.at[ids].set(
                       new, unique_indices=True, mode="drop"),
                       donate_argnums=0),
                   table, every_row[:chunk], table[:chunk], carried=True,
                   **said)
        del table

    width = 2688 // (1 if on_chip else 64)
    report("scatter_add", chunk,
           jax.jit(lambda total, ids, new: total.at[ids].add(new, mode="drop"),
                   donate_argnums=0),
           jnp.zeros((tokens, width), jnp.float32),
           jnp.asarray(rng.integers(0, tokens, chunk).astype(np.int32)),
           jnp.ones((chunk, width), jnp.float32), carried=True,
           dtype="float32", width=width)

    if on_chip:
        held = rng.random((K, TOKENS)) < 0.25
        place = np.full((K, TOKENS), -1, np.int32)
        place[held] = rng.permutation(ROWS)[:held.sum()].astype(np.int32)
        words = jnp.ones((ROWS * SUBLANES, 128), jnp.uint32)
        for tile in (128, 256):
            report("dma", int(held.sum()),
                   jax.jit(functools.partial(dma_rows, tile=tile)),
                   jnp.asarray(place), words, tile=tile)
    return 0


if __name__ == "__main__":
    sys.exit(main())
