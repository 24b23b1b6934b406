#!/usr/bin/env python3
"""What Mamba-1's selective scan costs in each form, timed on the chip --
``python tools/selective_scan_forms.py`` (PERF.md section 6, PR 39;
``synapseml_tpu/parallel/selective_scan.py`` ``_blocks`` cites the table).

One ``synapseml_tpu::SelectiveScan`` at ``jamba2_3b``'s widths (5,120
channels, 16 states, bfloat16 ``u``, ``delta``, ``z``; float32 ``A``, ``B``,
``C``, ``D``, bias and state) at three loads, rows x positions: ``cell``
(128 x 128: the prompt pass of ``jamba2_3b.s128_gen128``), ``long`` (16 x
4,096: a long prompt, the state carried across eight blocks of positions)
and ``step`` (128 x 1 from a carried state: a decode pass's node, run for
``layers`` layers' states inside a ``lax.fori_loop`` that carries them the
way an ONNX ``Loop`` does, so that 1.09 GB of state streams from HBM as the
cell's does). One JSON line a load and form. A form is

- ``kernel:<channels>[:<positions>]``: the Pallas kernel
  ``selective_scan.kernel_form`` with a grid step's block capped at that many
  channels (a multiple of 128) and positions (a multiple of 8; default: as
  many as its blocks fit 8 MiB of VMEM with, 512 at 1,024 channels);
- ``scan``: ``lax.scan`` over positions, the state crossing HBM every
  position (what a CPU, or shapes off the tile, run);
- ``step``: the single-position form (the ``step`` load alone);
- ``shipped``: ``ops._selective_scan`` as it stands, the kernels on.

A line holds the milliseconds of a call on the host's clock (the median of
three sets of five, each ending in ``block_until_ready``; for ``step`` a
layer and pass), the device operations of one traced call that took longest
(the kernel is ``selective_scan``), the operands' bytes (``u``, ``delta``,
``z``, ``B``, ``C`` read, the result and the state written; for ``step`` the
state in and out beside them) over the time as GB/s beside the chip's
bandwidth, how the node lowered, the largest difference of the result and of
the leaving state from the float32 loop (``scan_form`` on the operands
widened to float32) and whether two calls gave the same bits. A form the
chip's compiler refuses gives its error in place of a time.
``--rehearse-on-cpu`` runs the same code at toy sizes through the Pallas
interpreter and prints no time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.join(REPO, "tools")):
    if path not in sys.path:
        sys.path.insert(0, path)

# load -> rows, positions, channels, states, layers (of carried state)
LOADS = {"cell": (128, 128, 5120, 16, 1), "long": (16, 4096, 5120, 16, 1),
         "step": (128, 1, 5120, 16, 26)}
TOY = {"cell": (2, 32, 256, 16, 1), "long": (2, 64, 256, 16, 1),
       "step": (2, 1, 256, 16, 2)}
FORMS = {"cell": "scan,kernel:128,kernel:256,kernel:512,kernel:1024,"
                 "kernel:2560,kernel:512:64,shipped",
         "long": "scan,kernel:256,kernel:512,kernel:512:128,kernel:1024,"
                 "shipped",
         "step": "step,shipped"}
TOY_FORMS = {"cell": "scan,kernel:128,kernel:256:16,shipped",
             "long": "scan,kernel:128:16,shipped", "step": "step,shipped"}


def run_of(form: str, passes: int, interpret: bool, notes: dict):
    """``(u, delta, A, B, C, D, z, bias, states) -> (out, states)``: ``form``
    over every layer's state in turn, ``passes`` times inside a
    ``fori_loop`` that carries the states (one pass outside any loop where
    ``passes`` is 0: a prompt pass's node from no state)."""
    import jax.numpy as jnp
    from jax import lax

    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import selective_scan as scan

    kind, *caps = form.split(":")

    def node(u, delta, a, b, c, skip, z, bias, state):
        if kind == "shipped":
            kept = ops._kernels_on
            ops._kernels_on = lambda: True
            if interpret:
                kernel = scan.kernel_form
                scan.kernel_form = functools.partial(kernel, interpret=True)
            try:
                return ops._selective_scan(
                    [u, delta, a, b, c, skip, z, bias, state], {},
                    {"n_outputs": 2, "notes": notes})
            finally:
                ops._kernels_on = kept
                if interpret:
                    scan.kernel_form = kernel
        if kind == "kernel":
            return scan.kernel_form(
                u, delta, a, b, c, skip, z, bias, state, channels=int(caps[0]),
                positions=int(caps[1]) if len(caps) > 1 else None,
                interpret=interpret)
        return getattr(scan, kind + "_form")(u, delta, a, b, c, skip, z, bias,
                                             state)

    def run(u, delta, a, b, c, skip, z, bias, states):
        if not passes:
            out, state = node(u, delta, a, b, c, skip, z, bias, None)
            return out, (state,)

        def a_pass(t, carried):
            out, states = carried
            new = []
            for state in states:
                # a layer's input depends on the one before, by a thousandth
                # (a sum that feeds itself 416 times would overflow)
                out, state = node(u + out * 1e-3, delta, a, b, c, skip, z,
                                  bias, state)
                new.append(state)
            return out, tuple(new)

        return lax.fori_loop(0, passes, a_pass,
                             (jnp.zeros_like(u), tuple(states)))

    return run


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    parser.add_argument("--seed", type=int, default=39)
    parser.add_argument("--loads", default="cell,long,step",
                        help="of " + ", ".join(LOADS))
    parser.add_argument("--forms", default=None,
                        help="scan, step, kernel:<channels>[:<positions>], "
                        "shipped (default: the load's grid)")
    args = parser.parse_args(argv)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from expert_combine_forms import device_ops, milliseconds
    from synapseml_tpu.parallel import selective_scan as scan

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse_on_cpu:
        print(f"no TPU here ({device.platform}); a time comes from the chip "
              f"alone: --rehearse-on-cpu checks the answers", file=sys.stderr)
        return 3
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f).get(device.device_kind)
    print(json.dumps({"device": {"platform": device.platform,
                                 "kind": device.device_kind},
                      "rehearsal": not on_chip}), flush=True)

    for load in args.loads.split(","):
        rows, s, d, n, layers = (LOADS if on_chip else TOY)[load]
        passes = 0 if s > 1 else (16 if on_chip else 2)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 6 + layers)

        def draw(key, shape, dtype=jnp.bfloat16, scale=1.0):
            return (scale * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dtype)

        given = (
            draw(keys[0], (rows, s, d)), draw(keys[1], (rows, s, d)),
            -jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32), (d, 1)),
            draw(keys[2], (rows, s, n), jnp.float32),
            draw(keys[3], (rows, s, n), jnp.float32),
            jnp.ones((d,), jnp.float32), draw(keys[4], (rows, s, d)),
            # steps of some 0.01 to 0.1, as the family's initialisation
            jnp.full((d,), -3.0, jnp.float32),
            [draw(key, (rows, n, d), jnp.float32) for key in keys[6:]])
        wide = [v.astype(jnp.float32) if hasattr(v, "astype") else v
                for v in given[:8]]
        loop_out, loop_state = jax.jit(scan.scan_form)(
            *wide, given[8][0] if passes else None)
        # u, delta, z and the result; B and C; the state (in as well, in a
        # loop)
        moved = rows * (s * (4 * d * 2 + 2 * n * 4)
                        + n * d * 4 * (2 if passes else 1))
        forms = args.forms or (FORMS if on_chip else TOY_FORMS)[load]
        for form in forms.split(","):
            line = {"load": load, "form": form, "shape": [rows, s, d, n],
                    "layers": layers, "passes": passes}
            notes = {}
            try:
                if form.startswith("kernel"):
                    caps = [int(v) for v in form.split(":")[1:]]
                    line["channels_positions"] = list(scan._blocks(s, d, 2, *caps))
                fn = jax.jit(run_of(form, passes, not on_chip, notes)
                             ).lower(*given).compile()
            except Exception as error:  # the chip's compiler refusing a form
                line["error"] = f"{type(error).__name__}: {error}"[:300]
                print(json.dumps(line), flush=True)
                continue
            line["lowering"] = {form: v for (form,), v in notes.get(
                "selective_scan", {}).items()}
            out, states = fn(*given)
            again = fn(*given)[0]
            line["same_bits_twice"] = bool((np.asarray(
                out.astype(jnp.float32)) == np.asarray(
                    again.astype(jnp.float32))).all())
            line["finite"] = bool(np.isfinite(
                np.asarray(out.astype(jnp.float32))).all())
            if not passes:  # one node: held to the float32 loop
                line["max_abs_from_loop"] = float(jnp.abs(
                    out.astype(jnp.float32) - loop_out).max())
                line["state_max_abs_from_loop"] = float(jnp.abs(
                    states[0] - loop_state).max())
            if on_chip:
                per = max(passes, 1) * layers
                ms = milliseconds(fn, given, calls=5) / per
                line["ms"] = round(ms, 4)
                line["operand_gb_per_s"] = round(moved / ms / 1e6, 1)
                line["chip_gb_per_s"] = peaks["hbm_bytes_per_s"] / 1e9
                line["ops_ms_a_call"] = device_ops(fn, given, most=6)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
