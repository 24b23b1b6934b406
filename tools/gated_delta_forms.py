"""The gated delta rule's forms timed alone at ``olmo_hybrid_7b``'s widths
(30 heads of 96 / 192) on the chip: what ``PERF.md`` cites for the decode
step's kernel against plain ``jax.numpy``, and for the prompt pass's
chunked form as XLA's products against the Pallas kernel.

    python3 tools/gated_delta_forms.py [--rows 128] [--passes 32] [--prompt 256] [--rehearse-on-cpu]

``decode``: a ``fori_loop`` of ``--passes`` single positions carrying every
delta rule layer's state (6 of the cell's 8 layers), one form each:
``kernel`` (the Pallas kernel, the state written in place) and ``step``
(``jax.numpy`` over the same layout); ms a pass, and GB/s of the state read
and written. ``prompt``: one layer's rule over ``--prompt`` positions a row
from no state, ms a layer, in two forms: ``chunked`` (XLA's products) and
``chunked_kernel`` (the Pallas kernel), timed on the cell's bfloat16
operands and compared on the same numbers in float32 (largest difference
of the output and of the leaving state from ``chunked``). A decode form's
answer is held to the ``step`` form's. One JSON line a load and form.
``--rehearse-on-cpu``: toy sizes, the kernel through the
interpreter; no time means anything there.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _timed(fn, *args, repeats: int = 3) -> float:
    import jax

    jax.block_until_ready(fn(*args))  # compile and warm
    best = float("inf")
    for _ in range(repeats):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - start)
    return best


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=128)
    p.add_argument("--passes", type=int, default=32)
    p.add_argument("--layers", type=int, default=6)
    p.add_argument("--prompt", type=int, default=256)
    p.add_argument("--rehearse-on-cpu", action="store_true")
    args = p.parse_args(argv)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from synapseml_tpu.parallel import gated_delta as rule

    h, dk, dv = (4, 16, 32) if args.rehearse_on_cpu else (30, 96, 192)
    rows, layers = args.rows, args.layers
    device = jax.devices()[0]
    rng = np.random.default_rng(0)

    def draw(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    q, k, v = draw(rows, 1, h, dk), draw(rows, 1, h, dk), draw(rows, 1, h, dv)
    g = -jnp.abs(draw(rows, 1, h, dtype=jnp.float32))
    beta = jax.nn.sigmoid(draw(rows, 1, h, dtype=jnp.float32)) * 2
    states = tuple(0.1 * draw(rows, dk, h * dv, dtype=jnp.float32)
                   for _ in range(layers))
    forms = {"step": rule.step_form,
             "kernel": functools.partial(rule.kernel_form,
                                         interpret=args.rehearse_on_cpu)}
    state_bytes = 2 * layers * rows * dk * h * dv * 4  # read and written
    answers = {}
    for name, form in forms.items():
        @jax.jit
        def passes(states, form=form):
            def one(_, carried):
                return tuple(form(q, k, v, g, beta, s)[1] for s in carried)
            return jax.lax.fori_loop(0, args.passes, one, states)

        seconds = _timed(passes, states) / args.passes
        answers[name] = np.asarray(passes(states)[0])
        line = {"load": "decode", "form": name, "rows": rows,
                "layers": layers, "max_diff_from_step": float(np.abs(
                    answers[name] - answers["step"]).max()),
                "device": device.device_kind}
        if not args.rehearse_on_cpu:  # a CPU's time is nobody's measurement
            line.update(ms_a_pass=seconds * 1e3,
                        state_gb_per_s=state_bytes / seconds / 1e9)
        print(json.dumps(line), flush=True)

    s = args.prompt
    prompt = (draw(rows, s, h, dk), draw(rows, s, h, dk), draw(rows, s, h, dv),
              -jnp.abs(draw(rows, s, h, dtype=jnp.float32)),
              jax.nn.sigmoid(draw(rows, s, h, dtype=jnp.float32)) * 2)
    # the same numbers in float32, so that the output is compared unrounded
    wide = [x.astype(jnp.float32) for x in prompt]
    forms = {"chunked": jax.jit(rule.chunked_form),
             "chunked_kernel": jax.jit(functools.partial(
                 rule.chunked_kernel_form, interpret=args.rehearse_on_cpu))}
    want = [np.asarray(x) for x in forms["chunked"](*wide)]
    for name, form in forms.items():
        seconds = _timed(form, *prompt)
        got = [np.asarray(x) for x in form(*wide)]
        line = {"load": "prompt", "form": name, "rows": rows,
                "positions": s, "max_diff_out_from_chunked": float(
                    np.abs(got[0] - want[0]).max()),
                "max_diff_state_from_chunked": float(
                    np.abs(got[1] - want[1]).max()),
                "max_abs_out": float(np.abs(want[0]).max()),
                "device": device.device_kind}
        if not args.rehearse_on_cpu:
            line["ms_a_layer"] = seconds * 1e3
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
