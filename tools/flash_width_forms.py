#!/usr/bin/env python3
"""How the flash kernel should take a contraction that is not a multiple of
128 wide, timed on the chip -- ``python tools/flash_width_forms.py`` (PERF.md
section 6, PR 34).

Latent attention's expanded form has queries and keys of 192 = 128 + 64
numbers a head and values of 128. One causal ``flash_attention`` at the
prompt pass's shapes (``[16, 4096, 32, 192 / 128]`` bfloat16), one JSON line
a form:

- ``as_lies``: ``q`` and ``k`` 192 wide, as the executor hands them over (a
  block's last dimension is the array's own);
- ``padded``: ``q`` and ``k`` padded with zeros to 256 in HBM first (the pad
  is timed with the kernel: it is what a caller would pay), the scale kept
  at ``1 / sqrt(192)``;
- ``d128``: queries, keys and values 128 wide: the kernel every earlier
  program had, for scale.

A 128-wide and a 64-wide product summed is not a form of its own on this
chip: its matrix unit is 128 wide, so the 64-wide product costs a whole pass
and the pair costs what the padded contraction does.

A line holds the milliseconds of a call (the median of ten on the host's
clock, each ending in ``block_until_ready``), the causal operations ``2 x B x
H x S^2 / 2 x (qk + v)``, their share of the chip's peak (``benchmark/
peaks.json``) and the largest difference from dense attention on one row and
four heads. ``--rehearse-on-cpu`` runs the same code at toy sizes through the
Pallas interpreter and prints no time.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--rehearse-on-cpu", action="store_true")
    args = p.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    rehearse = args.rehearse_on_cpu
    if not rehearse and jax.default_backend() != "tpu":
        print("flash_width_forms: needs a TPU (or --rehearse-on-cpu)",
              file=sys.stderr)
        return 3
    b, s, h = (1, 256, 2) if rehearse else (16, 4096, 32)
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f).get(jax.devices()[0].device_kind)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)

    def draw(key, width):
        return jax.random.normal(key, (b, s, h, width), jnp.bfloat16)

    def kernel(q, k, v, scale):
        return flash.flash_attention(q, k, v, causal=True, scale=scale,
                                     interpret=rehearse,
                                     block_q=128 if rehearse else None,
                                     block_k=128 if rehearse else None)

    def padded(q, k, v):
        wide = ((0, 0),) * 3 + ((0, 256 - q.shape[-1]),)
        return kernel(jnp.pad(q, wide), jnp.pad(k, wide), v,
                      1.0 / math.sqrt(q.shape[-1]))

    forms = {"as_lies": (192, lambda q, k, v: kernel(q, k, v, None)),
             "padded": (192, padded),
             "d128": (128, lambda q, k, v: kernel(q, k, v, None))}
    for name, (qk, fn) in forms.items():
        q, k, v = draw(keys[0], qk), draw(keys[1], qk), draw(keys[2], 128)
        run = jax.jit(fn)
        out = jax.block_until_ready(run(q, k, v))
        want = flash.dense_attention(q[:1, :, :4], k[:1, :, :4], v[:1, :, :4],
                                     causal=True)
        line = {"form": name, "qk": qk, "v": 128, "shape": [b, s, h],
                "max_abs_diff": float(jnp.max(jnp.abs(
                    out[:1, :, :4].astype(jnp.float32)
                    - want.astype(jnp.float32))))}
        if not rehearse:
            times = []
            for _ in range(10):
                t0 = time.perf_counter()
                jax.block_until_ready(run(q, k, v))
                times.append(time.perf_counter() - t0)
            flops = 2.0 * b * h * s * s / 2 * (qk + 128)
            ms = 1e3 * statistics.median(times)
            line.update(ms=ms, tflop=flops / 1e12,
                        share_of_peak_pct=100 * flops / (ms / 1e3)
                        / peaks["bf16_flops_per_s"])
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
