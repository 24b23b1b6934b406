#!/usr/bin/env python3
"""Which row tile and chunk ``ExpertFFN``'s grouped products want at a load,
timed on the chip -- ``python tools/expert_tile_forms.py`` (PERF.md section
6, PR 33; ``synapseml_tpu/onnx/ops.py`` ``_expert_tiling`` cites the table).

One ``ExpertFFN`` (``swiglu``, top-8 of 128 experts, all held, h 2,048, f 768:
a layer of ``sdar_30b_a3b``) with its parameters set from outside, one JSON
line a form and load. A form is ``<rows>:<chunk>:<k>[:<n>]``:

- ``rows``: the megablox kernel's row tile, of 32, 64, 128, 256, 512. The
  kernel visits a row tile once for every expert that touches it and
  computes the whole tile each visit;
- ``chunk``: ``load`` (``ops._chunk_rows``: 48 row tiles or, where there are
  fewer pairs, all of them rounded up to a tile), ``all`` (one chunk of all
  the pairs, however many) or a number of sorted pairs (24576: the constant
  every load had before PR 33). The row gather,
  ``silu(gate) * up`` and the buffer's update cover a chunk whole;
- ``k``: ``whole`` (the contraction is one tile: a weight tile is fetched
  once a visit) or ``512`` (tiles of at most 512, the caps of the 512-row
  tile);
- ``n``, where given: ``whole`` (the down-projection's 2,048 columns are one
  tile too) in place of tiles of at most 1,024.

``shipped`` is ``ops._expert_ffn`` as it stands. The loads, at 512 tokens
(4,096 pairs, 32 an expert: a generating pass of ``sdar_30b_a3b.gen64``):
``even`` (a uniform router: every expert some 32 pairs, as a commit pass's
real ids), ``mask_ids`` (every token's scores are one shared vector plus
noise of its own, as a block's mask ids that embed alike: 85 experts
reached, the largest with 136 pairs; the noise is set so that the 512-row
tile makes the 92 visits a product that PR 32's trace shows for a denoising
pass, where a commit pass makes 135) and ``eight_experts`` (every pick on
the same eight: 512 pairs each, the router at its most lopsided). ``even64``
to ``even512`` are the uniform router at 1,024 to 8,192 tokens, 64 to 512
pairs an expert: where the rule's steps go.

A line holds the milliseconds of a call (the median of three sets of ten on
the host's clock, each ending in ``block_until_ready``), the device
operations of one traced call that took longest (``ops_ms``; the grouped
kernel's are the ``gmm`` custom calls), the tile visits a product makes
(``visits``, from the sizes the load drew), the largest difference from the
first form's answer, and whether two calls gave the same bits. A form the
chip's compiler refuses gives its error in place of a time.
``--rehearse-on-cpu`` runs the same code at toy sizes, where
``lax.ragged_dot`` stands in for the kernel and only the chunk applies, and
prints no time.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.join(REPO, "tools")):
    if path not in sys.path:
        sys.path.insert(0, path)

ROWS = (512, 256, 128, 64, 32)
# load -> (tokens at the cell's size, how the router leans)
LOADS = {"even": (512, "even"), "mask_ids": (512, "mask_ids"),
         "eight_experts": (512, "eight_experts"),
         "even64": (1024, "even"), "even128": (2048, "even"),
         "even256": (4096, "even"), "even512": (8192, "even")}
EXPERTS, TOP_K = 128, 8


def default_forms(load: str) -> list:
    """The parent's form first (every other answer is compared with it),
    then the grid: at a pass's loads every tile under both chunks and both
    ``k`` tilings (the two smallest tiles also with the pairs in one chunk,
    the rule's tile also with the down-projection's columns in one tile), at
    the larger loads every tile at the load's chunk and the two middle tiles
    at the parent's and with the pairs in one chunk."""
    forms = ["512:24576:512"]
    at_a_pass = LOADS[load][0] == 512
    for rows in ROWS:
        for chunk in ("load", "24576") if at_a_pass else ("load",):
            forms += [f"{rows}:{chunk}:{k}" for k in ("whole", "512")]
    forms += ["64:4096:whole", "32:4096:whole", "128:load:whole:whole"] \
        if at_a_pass else ["256:24576:whole", "128:24576:whole",
                           "256:all:whole", "128:all:whole"]
    return list(dict.fromkeys(forms)) + ["shipped"]


@contextlib.contextmanager
def tiling(form: str):
    """``ops._expert_tiling`` and ``ops._gmm_tiling`` as ``form`` says, for
    the programs traced inside."""
    from synapseml_tpu.onnx import ops

    if form == "shipped":
        yield
        return
    rows, chunk, k_tile, n_tile = (form.split(":") + ["1024"])[:4]
    rows = int(rows)

    def expert_tiling(n_pairs, num_experts):
        return rows, (ops._chunk_rows(n_pairs, rows) if chunk == "load"
                      else -(-n_pairs // rows) * rows if chunk == "all"
                      else int(chunk))

    def gmm_tiling(rows, k, n, itemsize):
        return (rows, k if k_tile == "whole" else ops._tile(k, int(k_tile)),
                n if n_tile == "whole" else ops._tile(n, int(n_tile)))

    kept = ops._expert_tiling, ops._gmm_tiling
    ops._expert_tiling, ops._gmm_tiling = expert_tiling, gmm_tiling
    try:
        yield
    finally:
        ops._expert_tiling, ops._gmm_tiling = kept


def expert_ffn(x, index, weight, up, down, gate):
    from synapseml_tpu.onnx import ops

    return ops._expert_ffn(
        [x, index, weight, up, down, gate],
        dict(first_expert=0, num_experts=up.shape[0], activation="swiglu"),
        {"n_outputs": 1})


def draw(seed, tokens, h, f, experts, k, lean):
    """A layer's input and weights and a router's picks: ``k`` distinct
    experts a token, leaning as ``lean`` says."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (1, tokens, h), jnp.bfloat16)
    scores = jax.random.uniform(keys[1], (1, tokens, experts))
    if lean == "mask_ids":
        scores = jax.random.uniform(keys[5], (experts,)) + 2.1 * scores
    elif lean == "eight_experts":
        scores = jnp.where(jnp.arange(experts) % (experts // k) == 0,
                           scores + 1, scores)
    top, index = jax.lax.top_k(scores, k)
    weight = top / top.sum(-1, keepdims=True)
    up, gate = ((jax.random.normal(key, (experts, h, f), jnp.float32)
                 * h ** -0.5).astype(jnp.bfloat16) for key in keys[2:4])
    down = (jax.random.normal(keys[4], (experts, f, h), jnp.float32)
            * f ** -0.5).astype(jnp.bfloat16)
    return x, index, weight, up, down, gate


def visits(sizes, rows: int) -> int:
    """Grid steps along the rows one grouped product makes: a step for
    every (expert, row tile) pair in which the expert has a row."""
    import numpy as np

    ends = np.cumsum(sizes)
    starts = ends - sizes
    return int(((-(-ends // rows) - starts // rows) * (sizes > 0)).sum())


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    parser.add_argument("--seed", type=int, default=33)
    parser.add_argument("--loads", default=",".join(LOADS),
                        help="of " + ", ".join(LOADS))
    parser.add_argument("--forms", default=None,
                        help="<rows>:<load|all|pairs>:<whole|512>[:whole],... or "
                        "shipped; "
                        "the first is what the others are compared with "
                        "(default: the grid, the parent's 512:24576:512 first)")
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
    size = dict(h=2048, f=768, experts=EXPERTS, k=TOP_K) if on_chip \
        else dict(h=32, f=48, experts=16, k=4)
    print(json.dumps({"device": {"platform": device.platform,
                                 "kind": device.device_kind},
                      "size": size, "rehearsal": not on_chip}), flush=True)

    for load in args.loads.split(","):
        tokens, lean = LOADS[load]
        if not on_chip:
            tokens //= 16
        given = draw(args.seed, tokens, lean=lean, **size)
        sizes = np.bincount(np.asarray(given[1]).reshape(-1),
                            minlength=size["experts"])
        first = None
        forms = args.forms.split(",") if args.forms else default_forms(load)
        for form in forms:
            line = {"load": load, "form": form, "pairs": int(sizes.sum()),
                    "experts_reached": int((sizes > 0).sum()),
                    "largest_expert": int(sizes.max())}
            try:
                with tiling(form):
                    from synapseml_tpu.onnx import ops

                    rows, chunk = ops._expert_tiling(int(sizes.sum()),
                                                     size["experts"])
                    # a function of its own: jit keeps a trace by function
                    fn = jax.jit(lambda *a: expert_ffn(*a)).lower(
                        *given).compile()
            except Exception as error:  # the chip's compiler refusing a tiling
                line["error"] = f"{type(error).__name__}: {error}"[:300]
                print(json.dumps(line), flush=True)
                continue
            line.update(rows=rows, chunk=chunk, visits=visits(sizes, rows))
            answer = np.asarray(fn(*given).astype(jnp.float32))
            line["same_bits_twice"] = bool(
                (np.asarray(fn(*given).astype(jnp.float32)) == answer).all())
            line["finite"] = bool(np.isfinite(answer).all())
            if first is None:
                first = answer
            line["max_abs_from_first"] = float(np.abs(answer - first).max())
            if on_chip:
                line["ms"] = round(milliseconds(fn, given), 4)
                line["ops_ms"] = device_ops(fn, given, most=8)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
