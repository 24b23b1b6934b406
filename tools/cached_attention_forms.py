#!/usr/bin/env python3
"""What a few queries against a key-value cache cost in each form, timed on
the chip -- ``python tools/cached_attention_forms.py`` (PERF.md section 6;
``synapseml_tpu/parallel/flash.py`` ``_cached_blocks`` cites the table).

The cached ``Attention`` of a load's layers as a generating pass runs it,
inside a ``lax.fori_loop`` that carries the caches the way an ONNX ``Loop``
does (``sdar_30b_a3b.gen64``'s six layers' keys and values: half a
gigabyte, so that they stream from HBM as the cell's do; one layer's would
sit in the chip's faster memory): a pass writes its block's keys and values
into each cache (``ops._tensor_scatter``, in place), computes the mask over
key positions from the trip counter and calls ``ops._attention`` on the
cache as it lies (rank 3, ``[rows, L, kv x size]``); a layer's queries
depend on the layer before, so nothing is hoisted. One JSON line a load and
form. A form is

- ``dense``: ``flash.masked_attention``, the grouped dense form (what every
  program ran before PR 35 and what a CPU, a per-head mask or latent
  attention's absorbed form still runs);
- ``<rows>:<keys>``: the Pallas kernel ``flash.cached_attention`` with that
  many rows a grid step and the key axis ``whole`` or in blocks of that many
  positions (a multiple of 128; the last block may hang over the cache's
  end) under the online softmax;
- ``shipped``: ``ops._attention`` as it stands (``flash._cached_blocks``
  picks the rows and the keys).

The loads: ``cell`` (128 rows, 4 queries, a cache of 320, 32 / 4 heads of
128: ``sdar_30b_a3b.gen64``'s), ``q16`` (16 queries: 128 query rows a
key-value head, the most the dispatch gives the kernel), ``long`` (16 rows
against 4,224 positions: a cache beyond one block), ``olmo`` (128 rows, one
query, 30 / 30 heads of 128, a cache of 384, the two attention layers of
``olmo_hybrid_7b.s256_gen128``: ONE query row a key-value head) and
``jamba`` (128 rows, one query, 20 / 1 heads, a cache of 256, the two of
``jamba2_3b.s128_gen128``: 20 query rows on one key-value head).

A line holds the milliseconds of a layer's ``Attention`` in a pass (a call
of ``passes`` passes through ``layers`` layers, the median of three sets of
five on the host's clock, each ending in ``block_until_ready``, over passes
x layers; the caches' copy into the loop at the start of a call is in it:
a hundredth), a layer's cache streamed once at the chip's bandwidth
(``benchmark/peaks.json``) for scale, the device operations of
one traced call that took longest, how ``_attention`` lowered the node, how
many instructions of the compiled loop body make a copy of a whole cache
(``cache_copies_in_loop``: a ``copy``, ``reshape`` or ``transpose`` whose
result has the cache's elements) and a float32 convert of one
(``cache_converts_in_loop``), the largest difference from the dense form's
answer and whether two calls gave the same bits. A form the chip's
compiler refuses gives its error in place of a time. ``--rehearse-on-cpu``
runs the same code at toy sizes through the Pallas interpreter and prints
no time.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.join(REPO, "tools")):
    if path not in sys.path:
        sys.path.insert(0, path)

# load -> rows, queries a row, cache positions, query heads, key-value heads
LOADS = {"cell": (128, 4, 320, 32, 4), "q16": (128, 16, 320, 32, 4),
         "long": (16, 4, 4224, 32, 4), "olmo": (128, 1, 384, 30, 30),
         "jamba": (128, 1, 256, 20, 1)}
TOY = {"cell": (4, 4, 48, 8, 2), "q16": (4, 8, 48, 8, 2),
       "long": (2, 4, 96, 8, 2), "olmo": (4, 1, 48, 4, 4),
       "jamba": (4, 1, 32, 20, 1)}
# layers whose caches a pass carries, where the cell has other than six
LAYERS = {"olmo": 2, "jamba": 2}
SIZE = 128
FORMS = {"cell": "dense,4:whole,8:whole,16:whole,32:whole,8:128,16:128,"
                 "shipped",
         "q16": "dense,4:whole,8:whole,8:128,shipped",
         "long": "dense,4:512,8:512,8:640,4:1024,2:2048,shipped",
         "olmo": "dense,4:whole,8:whole,shipped",
         "jamba": "dense,4:whole,8:whole,shipped"}
TOY_FORMS = {"cell": "dense,2:whole,4:whole,2:16,4:32,shipped",
             "q16": "dense,2:whole,2:16,shipped",
             "long": "dense,1:32,2:16,shipped",
             "olmo": "dense,2:16,shipped", "jamba": "dense,2:16,shipped"}


@contextlib.contextmanager
def form_of(form: str, interpret: bool):
    """``ops._attention`` lowering its node as ``form`` says, for the
    programs traced inside."""
    from synapseml_tpu.onnx import ops
    from synapseml_tpu.parallel import flash

    kept = (ops._kernels_on, flash.cached_attention,
            flash.cached_attention_takes, flash._cached_blocks)
    ops._kernels_on = lambda: True
    if interpret:
        flash.cached_attention = functools.partial(kept[1], interpret=True)
    if form == "dense":
        flash.cached_attention_takes = lambda *shapes: False
    elif form != "shipped":
        rows, keys = form.split(":")
        flash._cached_blocks = lambda b, n, s_k, d, itemsize: (
            int(rows), s_k if keys == "whole" else int(keys))
    try:
        yield
    finally:
        (ops._kernels_on, flash.cached_attention,
         flash.cached_attention_takes, flash._cached_blocks) = kept


def passes_of_the_layers(passes: int, heads: int, kv_heads: int, prompt: int,
                         notes: dict):
    """``(q, caches, new_k, new_v) -> [rows, s_q, heads x size]``: the last
    of ``passes`` passes through every layer's cache (``caches``: keys and
    values, a layer after another), each against what the one before left;
    a layer's queries are the layer before's result."""
    import jax.numpy as jnp
    from jax import lax

    from synapseml_tpu.onnx import ops

    ctx = {"n_outputs": 1, "notes": notes}

    def run(q, caches, new_k, new_v):
        rows, s_q = q.shape[:2]
        length = caches[0].shape[1]
        blocks = (length - prompt) // s_q

        def a_pass(t, state):
            caches, out = list(state[0]), state[1]
            start = prompt + (t % blocks) * s_q
            starts = jnp.full((rows,), start, jnp.int32)
            visible = (jnp.arange(length) < start + s_q)[None]
            for at in range(0, len(caches), 2):
                for slot, new in enumerate((new_k, new_v)):
                    caches[at + slot] = ops._tensor_scatter(
                        [caches[at + slot], new, starts], {"axis": 1}, ctx)
                out = ops._attention(
                    [q + out, caches[at], caches[at + 1], visible],
                    {"q_num_heads": heads, "kv_num_heads": kv_heads}, ctx)
            return tuple(caches), out

        return lax.fori_loop(0, passes, a_pass,
                             (tuple(caches), jnp.zeros_like(q)))[1]

    return run


def _loop_body_results(text: str):
    """``(name, type, element count)`` of every instruction of a compiled
    program's loop bodies (fusions included, not what they call)."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    inside = False
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1) in bodies
        elif inside:
            made = re.match(
                r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]", line)
            if made:
                yield made.group(1), made.group(2), math.prod(
                    int(d) for d in made.group(3).split(",") if d)


def cache_copies_in_loop(text: str, cache_elements: int) -> int:
    """Instructions of a compiled program's loop bodies that lay a whole
    cache out again: a ``copy``, ``reshape`` or ``transpose`` (fused or not)
    whose result has the cache's element count."""
    return sum(size == cache_elements
               and bool(re.search(r"copy|reshape|transpose", name))
               for name, _, size in _loop_body_results(text))


def cache_converts_in_loop(text: str, cache_elements: int) -> int:
    """Instructions of a compiled program's loop bodies that convert a whole
    cache to float32 (fused or not)."""
    return sum(size == cache_elements and kind == "f32" and "convert" in name
               for name, kind, size in _loop_body_results(text))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    parser.add_argument("--seed", type=int, default=35)
    parser.add_argument("--loads", default="cell", help="of " + ", ".join(LOADS))
    parser.add_argument("--forms", default=None,
                        help="dense, <rows>:<whole|keys>, shipped; the "
                        "dense form's answer is what the others are "
                        "compared with (default: the load's grid)")
    args = parser.parse_args(argv)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from expert_combine_forms import device_ops, milliseconds

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse_on_cpu:
        print(f"no TPU here ({device.platform}); a time comes from the chip "
              f"alone: --rehearse-on-cpu checks the answers", file=sys.stderr)
        return 3
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f).get(device.device_kind)
    passes = 48 if on_chip else 3
    print(json.dumps({"device": {"platform": device.platform,
                                 "kind": device.device_kind},
                      "size": SIZE, "passes": passes,
                      "rehearsal": not on_chip}), flush=True)

    for load in args.loads.split(","):
        rows, s_q, length, heads, kv_heads = (LOADS if on_chip else TOY)[load]
        layers = LAYERS.get(load, 6) if on_chip else 2
        keys = jax.random.split(jax.random.PRNGKey(args.seed),
                                3 + 2 * layers)

        def draw(key, positions, n_heads):
            return jax.random.normal(key, (rows, positions, n_heads * SIZE),
                                     jnp.bfloat16)

        given = (draw(keys[0], s_q, heads),
                 [draw(key, length, kv_heads) for key in keys[3:]],
                 draw(keys[1], s_q, kv_heads), draw(keys[2], s_q, kv_heads))
        cache = given[1][0]
        dense = None
        forms = args.forms or (FORMS if on_chip else TOY_FORMS)[load]
        for form in forms.split(","):
            line = {"load": load, "form": form,
                    "shape": [rows, s_q, length, heads, kv_heads],
                    "layers": layers}
            notes = {}
            try:
                with form_of(form, interpret=not on_chip):
                    from synapseml_tpu.parallel import flash

                    if form != "dense":
                        line["rows_keys"] = list(flash._cached_blocks(
                            rows, s_q * heads // kv_heads, length, SIZE, 2))
                    # a function of its own: jit keeps a trace by function
                    fn = jax.jit(passes_of_the_layers(
                        passes, heads, kv_heads, length - 16 * s_q,
                        notes)).lower(*given).compile()
            except Exception as error:  # the chip's compiler refusing a form
                line["error"] = f"{type(error).__name__}: {error}"[:300]
                print(json.dumps(line), flush=True)
                continue
            line["lowering"] = {
                kind: count for (kind,), count in notes.get(
                    "attention_lowering", {}).items()
                if kind in ("cached", "masked")}
            text = fn.as_text()
            line["cache_copies_in_loop"] = cache_copies_in_loop(
                text, cache.size)
            line["cache_converts_in_loop"] = cache_converts_in_loop(
                text, cache.size)
            answer = np.asarray(fn(*given).astype(jnp.float32))
            line["same_bits_twice"] = bool(
                (np.asarray(fn(*given).astype(jnp.float32)) == answer).all())
            line["finite"] = bool(np.isfinite(answer).all())
            if dense is None:
                dense = answer
            line["max_abs_from_dense"] = float(np.abs(answer - dense).max())
            if on_chip:
                line["ms_a_layer_and_pass"] = round(
                    milliseconds(fn, given, calls=5) / passes / layers, 4)
                line["cache_stream_ms"] = round(
                    2 * cache.size * cache.dtype.itemsize
                    / peaks["hbm_bytes_per_s"] * 1e3, 4)
                line["ops_ms_a_call"] = device_ops(fn, given, most=8)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
