#!/usr/bin/env python3
"""Where the flash kernel should read its operands and how it should take a
tile the causal diagonal crosses, timed on the chip -- ``python
tools/flash_width_forms.py`` (PERF.md section 6, PR 37; PR 34 for the widths).

One causal ``Attention`` as the executor meets it, ``q`` ``[B, S, H x D]``,
``k`` ``[B, S, H_kv x D]``, ``v`` ``[B, S, H_kv x D_v]`` bfloat16 in, ``[B,
S, H x D_v]`` out, at the three loads that run the kernel in a cell:

- ``joyai``: ``[16, 4096, 32 / 32, 192 / 128]``, latent attention's expanded
  form (``joyai_llm_flash.s4096_gen128``'s prompt pass, nine nodes);
- ``nemotron``: ``[16, 4096, 32 / 2, 128]`` (``nemotron3_nano.s4096``, one);
- ``sdar``: ``[128, 256, 32 / 4, 128]`` causal at a granularity of 4
  positions (``sdar_30b_a3b.gen64``'s prompt pass, six).

One JSON line a form and load:

- ``parent`` (with ``--parent <checkout of the parent commit>``): every
  operand transposed to ``[B x H, S, D]`` in HBM before THAT checkout's
  kernel and the result back after it: what every program ran before PR 37
  (every tile it computed under the mask, every key block fetched), the
  transposes timed with the kernel;
- ``heads_first[:<rows>]``: the same transposes round this checkout's kernel:
  what a width with no in-place form gets (``flash.reads_in_place``);
- ``in_place``: the kernel on the operands where they lie, the tile the
  diagonal crosses computed whole under the mask, one head a step. Heads
  that are no blocks of lanes (``joyai``'s 192) are read with the positions
  minor, ``[B, H x D, S]``: the layout the compiler gives such heads, so the
  tool hands ``q`` and ``k`` over so laid out and times no transpose of them;
- ``in_place:<rows>``: the diagonal's tile in sub-tiles of ``rows`` query
  rows, each against the keys its rows can see;
- ``in_place:<rows>:g<heads>``: ``heads`` query heads a step (those that
  share a key-value head share its blocks);
- ``shipped``: ``flash.flash_attention`` as ``onnx/ops._attention`` calls it.

A 128-wide and a 64-wide product summed is not a form of its own for 192-wide
queries and keys on this chip: its matrix unit is 128 wide, so the 64-wide
product costs a whole pass; padding them to 256 in HBM lost to the width as
it lies (48.95 against 42.39 ms, PR 34).

A line holds the milliseconds of a call on the host's clock (layout
included), the device milliseconds of its operations (``kernel_ms``: the
Pallas kernel's own), the causal operations ``2 x B x H x S^2 / 2 x (D +
D_v)``, their share of the chip's peak over the kernel's time
(``benchmark/peaks.json``), the largest difference from dense attention on
one row and a few heads, and whether the answer is ``heads_first``'s to the
bit. ``--rehearse-on-cpu`` runs the same code at toy sizes through the Pallas
interpreter and prints no time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.join(REPO, "tools")):
    if path not in sys.path:
        sys.path.insert(0, path)

# load -> (rows, positions, query heads, key-value heads, D, D_v, causal_block)
LOADS = {"joyai": (16, 4096, 32, 32, 192, 128, 1),
         "nemotron": (16, 4096, 32, 2, 128, 128, 1),
         "sdar": (128, 256, 32, 4, 128, 128, 4)}
TOY = {"joyai": (1, 256, 2, 2, 192, 128, 1),
       "nemotron": (1, 256, 4, 2, 128, 128, 1),
       "sdar": (2, 128, 4, 2, 128, 128, 4)}


def forms_of(block_q: int, among: int, rehearse: bool, parent: bool) -> list:
    """The parent's form, in place alone, every sub-tile height that divides
    the tile (whole lane tiles of scores on the chip), then more heads a
    step, at halves, of the ``among`` that can share a step."""
    least = 32 if rehearse else 128
    rows = [r for r in (512, 256, 128, 64, 32) if least <= r < block_q]
    return ["parent"] * parent + ["heads_first", "in_place"] \
        + [f"in_place:{r}" for r in rows] \
        + [f"in_place:{rows[0]}:g{g}" for g in (2, 4, 8) if among % g == 0] \
        + ["shipped"]


def attention(form: str, load, block: int, interpret: bool, parent=None):
    """``(q, k, v) -> result`` for rank-3 operands, as ``form`` says."""
    import jax.numpy as jnp

    from synapseml_tpu.parallel import flash

    b, s, h, h_kv, d, d_v, causal_block = load
    given = dict(causal=True, block_q=block, block_k=block,
                 interpret=interpret, causal_block=causal_block)

    def heads_first(kernel):
        def to_bh(x, heads):
            return jnp.transpose(x.reshape(b, s, heads, -1),
                                 (0, 2, 1, 3)).reshape(b * heads, s, -1)

        def run(q, k, v):
            out = kernel(to_bh(q, h), to_bh(k, h_kv), to_bh(v, h_kv))
            return jnp.transpose(out.reshape(b, h, s, d_v),
                                 (0, 2, 1, 3)).reshape(b, s, h * d_v)
        return run

    def in_place(rows, group):
        return lambda q, k, v: flash._flash_call(
            q, k, v, heads=h, kv_heads=h_kv, batch_rep=1, diag_rows=rows,
            group=group, positions_minor=d % 128 != 0, **given)

    def shipped(q, k, v):
        if d % 128:  # handed over with the positions minor, as below
            q, k = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
        return flash.flash_attention(
            q.reshape(b, s, h, d), k.reshape(b, s, h_kv, d),
            v.reshape(b, s, h_kv, d_v), **given).reshape(b, s, h * d_v)

    if form == "parent":
        return heads_first(lambda q, k, v: parent._flash_bh_impl(
            q, k, v, True, block, block, h // h_kv, interpret, causal_block))
    if form == "shipped":
        return shipped
    parts = form.split(":")[1:]
    rows = [int(x) for x in parts if x.isdigit()]
    group = [int(x[1:]) for x in parts if x.startswith("g")]
    if form.startswith("heads_first"):
        return heads_first(lambda q, k, v: flash._flash_call(
            q, k, v, heads=1, kv_heads=1, group=1, batch_rep=h // h_kv,
            diag_rows=rows[0] if rows else block, **given))
    return in_place(rows[0] if rows else block, group[0] if group else 1)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse-on-cpu", action="store_true")
    p.add_argument("--seed", type=int, default=37)
    p.add_argument("--loads", default=",".join(LOADS))
    p.add_argument("--forms", default=None,
                   help="parent, heads_first[:<rows>], in_place[:<rows>]"
                   "[:g<heads>], shipped (default: all of them, every "
                   "sub-tile height)")
    p.add_argument("--parent", default=None, help="a checkout of the parent "
                   "commit: its flash kernel behind the transposes is the "
                   "form 'parent'")
    args = p.parse_args(argv)
    parent = None
    if args.parent:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "parent_flash", os.path.join(args.parent, "synapseml_tpu",
                                         "parallel", "flash.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from expert_combine_forms import device_ops, milliseconds
    from synapseml_tpu.parallel import flash

    rehearse = args.rehearse_on_cpu
    device = jax.devices()[0]
    if not rehearse and device.platform != "tpu":
        print("flash_width_forms: needs a TPU (or --rehearse-on-cpu)",
              file=sys.stderr)
        return 3
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f).get(device.device_kind)
    for name in args.loads.split(","):
        load = (TOY if rehearse else LOADS)[name]
        b, s, h, h_kv, d, d_v, causal_block = load
        block = 128 if rehearse else min(flash._pick_blocks(b * h, s, s))
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        q, k, v = (jax.random.normal(key, (b, s, heads * width), jnp.bfloat16)
                   for key, heads, width in zip(keys, (h, h_kv, h_kv),
                                                (d, d, d_v)))
        rep = h // h_kv
        few = 4 if rep == 1 else 1  # key-value heads of dense attention's
        want = np.asarray(flash.dense_attention(
            q[:1].reshape(1, s, h, d)[:, :, :few * rep],
            *(jnp.repeat(x[:1].reshape(1, s, h_kv, -1)[:, :, :few], rep, 2)
              for x in (k, v)),
            causal=True, causal_block=causal_block).astype(jnp.float32))
        first = None
        for form in (args.forms.split(",") if args.forms
                     else forms_of(block, rep if rep > 1 else h, rehearse,
                                   parent is not None)):
            line = {"load": name, "form": form, "blocks": [block, block],
                    "shape": [b, s, h, h_kv, d, d_v, causal_block]}
            try:
                given = (q, k, v)
                if form.split(":")[0] in ("in_place", "shipped") and d % 128:
                    # as the compiler hands 192-wide heads over: laid out
                    # with the positions minor, outside what is timed
                    given = (jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), v)
                run = jax.jit(attention(form, load, block, rehearse, parent)
                              ).lower(*given).compile()
            except Exception as error:  # the chip's compiler refusing a form
                line["error"] = f"{type(error).__name__}: {error}"[:300]
                print(json.dumps(line), flush=True)
                continue
            answer = np.asarray(run(*given).astype(jnp.float32))
            if first is None:
                first = answer
            line["bits_of_first_form"] = bool((answer == first).all())
            line["max_abs_from_first_form"] = float(
                np.abs(answer - first).max())
            line["max_abs_diff"] = float(np.abs(
                answer[:1].reshape(1, s, h, d_v)[:, :, :few * rep]
                - want).max())
            if not rehearse:
                flops = 2.0 * b * h * s * s / 2 * (d + d_v)
                ops = device_ops(run, given, most=6)
                kernel = [ms for label, ms in ops.items() if "flash" in label
                          or "custom-call" in label] or [max(ops.values())]
                line.update(
                    ms=round(milliseconds(run, given, calls=10), 4),
                    kernel_ms=round(sum(kernel), 4), ops_ms_a_call=ops,
                    tflop=flops / 1e12,
                    share_of_peak_pct=round(
                        100 * flops / (sum(kernel) / 1e3)
                        / peaks["bf16_flops_per_s"], 2))
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
