#!/usr/bin/env python3
"""How ``ExpertFFN``'s product rows should reach their tokens, timed on the
chip -- ``python tools/expert_combine_forms.py`` (PERF.md section 6, PR 31).

The forms of the combine (``synapseml_tpu/onnx/ops.py`` ``_expert_ffn``):

- ``scatter_add``: from inside the chunk loop, a chunk's rows weighted in
  float32 and added by token id into one float32 ``[n_tokens, h]``
  accumulator, rounded once after the loop;
- ``scatter_set``: from inside the loop, a chunk's rows placed by pair id
  into the pair-ordered buffer (distinct indices), then the weighted sum
  over a ``[k, n_tokens, h]`` view with no gather;
- ``gathers``: no scatter: the held rows regathered into token order chunk
  by chunk, each row's same-token successors added with shifted, masked
  adds, one row a token gathered last;
- ``parent``: a zero buffer of all sorted pairs, every pair gathered back to
  pair order, masked, weighted and summed (what PR 27 wrote);
- ``held_first``: the buffer not zeroed; a token's picks put held ones
  first, the first ``every`` gathered for every token, the held ones beyond
  those added row by row (``ops._held_picks_sum``, what the op does since
  PR 31). ``every2``, ``every4`` and ``every6`` are that form with another
  ``every`` than the op's own (3 where a quarter of the experts is held; 6
  gathers all picks and adds no row), ``rest4096`` and ``rest16384`` with
  another ``ops._REST_ROWS`` than 1,024.

Each is timed ``alone`` (the product rows are an input: the loop slices them
where the op computes them) and ``in_block`` (the whole op: two sorts, the
gather of token rows, the two grouped products, the combine) at the cell's
shapes (65,536 tokens, top-6, h 2,688, f 1,856, 32 experts held). The loads
say how hard the router leans on this chip's 32 experts: ``deployment`` (a
128-wide router, uniform, as the cell draws: a quarter of the picks),
``half``, ``twice`` and ``thrice`` (a held expert drawn at 3/7, 3 and 9
times another's odds: an eighth, a half and three quarters of the picks),
``all_here`` (every pick of the 128-wide router: what the loop of rows
costs at its longest) and ``all_held`` (32 of 32: every pair is held, and
``every`` is 6). ``shipped`` is ``ops._expert_ffn`` itself, ``in_block``
only. The last ``in_block`` call of a form is traced: ``in_block_ops_ms``
has its device operations (a loop's beside the ``while`` that holds them).
One JSON line a form and load: milliseconds a call (the median of three
sets of ten calls on the host's clock, each set ending in
``block_until_ready``), the held picks beyond the op's ``every``
(``rows_beyond``), the largest difference from ``parent``'s answer where
``parent`` ran, and whether two calls gave the same bits.
``--rehearse-on-cpu`` runs the same code at toy sizes and prints no time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FORMS = ("parent", "scatter_add", "scatter_set", "gathers", "held_first")
# ``held_first`` with one constant changed
VARIANTS = {"every2": {"every": 2}, "every4": {"every": 4},
            "every6": {"every": 6}, "rest4096": {"rest_rows": 4096},
            "rest16384": {"rest_rows": 16384}}
# load -> (the router's width, a held expert's odds against another's; None:
# held experts only)
LOADS = {"deployment": (128, 1.0), "all_held": (32, 1.0), "half": (128, 3 / 7),
         "twice": (128, 3.0), "thrice": (128, 9.0), "all_here": (128, None)}
HELD = 32


def expert_ffn(form, x, index, weight, up, down, first, num_experts,
               rows=None, every=None, rest_rows=None):
    """``ops._expert_ffn`` with the combine of ``form``: its sort, sizes and
    chunk loop copied, so that any form can stand behind them (``held_first``
    and ``shipped`` agreeing in bits and time says the copy is true).
    ``rows`` (the sorted pairs' product rows, ``[n_chunks * chunk, h]``)
    stands in for the gather of token rows and the two grouped products:
    the combine alone. ``every`` and ``rest_rows`` stand in for
    ``held_first``'s own."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from synapseml_tpu.onnx import ops

    held, (h, k) = up.shape[0], (x.shape[-1], index.shape[-1])
    tokens = x.reshape(-1, h)
    n_tokens = tokens.shape[0]
    local = index.reshape(-1, k).T.reshape(-1).astype(jnp.int32) - first
    n_pairs = local.shape[0]
    tile, chunk = ops._expert_tiling(n_pairs, num_experts)
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(group[:, None] == jnp.arange(held, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    n_chunks = -(-n_pairs // chunk)
    n_padded = n_chunks * chunk
    order_padded = jnp.pad(order, (0, n_padded - n_pairs))
    by_pair = weight.reshape(-1, k).T.reshape(-1).astype(jnp.float32)
    n_loops = (ends[-1] + chunk - 1) // chunk

    def product(i):
        lo = i * chunk
        pairs = lax.dynamic_slice(order_padded, (lo,), (chunk,))
        if rows is not None:
            return lo, pairs, lax.dynamic_slice(rows, (lo, 0), (chunk, h))
        inside = (jnp.clip(ends, lo, lo + chunk)
                  - jnp.clip(ends - sizes, lo, lo + chunk))
        hidden = ops._grouped_product(tokens[pairs % n_tokens], up, inside,
                                      tile)
        return lo, pairs, ops._grouped_product(
            jnp.square(jax.nn.relu(hidden)), down, inside, tile)

    def sorted_results():
        def one_chunk(i, results):
            lo, _, out = product(i)
            return lax.dynamic_update_slice(results, out, (lo, 0))

        return lax.fori_loop(0, n_loops, one_chunk,
                             jnp.zeros((n_padded, h), x.dtype))

    if form == "sorted_rows":  # what the combine alone is given
        return sorted_results()

    if form == "parent":
        out = jnp.where(here[:, None],
                        sorted_results()[jnp.argsort(order)], 0)
        out = out.astype(jnp.float32) * by_pair[:, None]
        return out.reshape(k, n_tokens, h).sum(axis=0).astype(
            x.dtype).reshape(x.shape)

    if form == "held_first":  # the op's own combine
        # parent's buffer, not zeroed: what no chunk wrote is selected away
        def one_chunk(i, results):
            lo, _, out = product(i)
            return lax.dynamic_update_slice(results, out, (lo, 0))

        results = lax.fori_loop(0, n_loops, one_chunk,
                                lax.empty((n_padded, h), x.dtype))
        if every is None:
            every = min(k, -(-k * held // num_experts) + 1)
        kept, ops._REST_ROWS = ops._REST_ROWS, rest_rows or ops._REST_ROWS
        try:
            return ops._held_picks_sum(
                results, ~here.reshape(k, n_tokens),
                jnp.argsort(order).astype(jnp.int32).reshape(k, n_tokens),
                by_pair.reshape(k, n_tokens), every
            ).astype(x.dtype).reshape(x.shape)
        finally:
            ops._REST_ROWS = kept

    if form == "scatter_add":
        def one_chunk(i, acc):
            lo, pairs, out = product(i)
            live = lo + jnp.arange(chunk) < ends[-1]
            weighted = jnp.where(
                live[:, None],
                out.astype(jnp.float32) * by_pair[pairs][:, None], 0)
            token = jnp.where(live, pairs % n_tokens, n_tokens)
            return acc.at[token].add(weighted, mode="drop")

        acc = lax.fori_loop(0, n_loops, one_chunk,
                            jnp.zeros((n_tokens, h), jnp.float32))
        return acc.astype(x.dtype).reshape(x.shape)

    if form == "scatter_set":
        def one_chunk(i, results):
            lo, pairs, out = product(i)
            live = lo + jnp.arange(chunk) < ends[-1]
            return results.at[jnp.where(live, pairs, n_pairs)].set(
                out, unique_indices=True, mode="drop")

        results = lax.fori_loop(0, n_loops, one_chunk,
                                jnp.zeros((n_pairs, h), x.dtype))
        out = results.astype(jnp.float32) * by_pair[:, None]
        return out.reshape(k, n_tokens, h).sum(axis=0).astype(
            x.dtype).reshape(x.shape)

    if form == "gathers":
        results = sorted_results()
        reach = -(-(k - 1) // 8) * 8  # a token's later picks, a whole tile
        held_pair = jnp.arange(n_padded) < ends[-1]
        token = jnp.where(held_pair, order_padded % n_tokens, n_tokens)
        by_token = jnp.argsort(token, stable=True)
        token_sorted = jnp.pad(token[by_token], (0, reach),
                               constant_values=n_tokens)
        weight_sorted = jnp.pad(by_pair[order_padded[by_token] % n_pairs],
                                (0, reach))
        by_token = jnp.pad(by_token, (0, reach))

        def one_chunk(j, sums):
            lo = j * chunk
            at = lax.dynamic_slice(by_token, (lo,), (chunk + reach,))
            tok = lax.dynamic_slice(token_sorted, (lo,), (chunk + reach,))
            w = lax.dynamic_slice(weight_sorted, (lo,), (chunk + reach,))
            got = jnp.where((tok < n_tokens)[:, None],
                            results[at].astype(jnp.float32) * w[:, None], 0)
            total = got[:chunk]
            for d in range(1, k):
                same = tok[d:chunk + d] == tok[:chunk]
                total = total + jnp.where(same[:, None],
                                          got[d:chunk + d], 0)
            return lax.dynamic_update_slice(sums, total.astype(x.dtype),
                                            (lo, 0))

        sums = lax.fori_loop(0, n_loops, one_chunk,
                             jnp.zeros((n_padded, h), x.dtype))
        ids = jnp.arange(n_tokens, dtype=token.dtype)
        start = jnp.searchsorted(token_sorted, ids)
        has = token_sorted[start] == ids
        return jnp.where(has[:, None], sums[jnp.minimum(start, n_padded - 1)],
                         0).reshape(x.shape)

    raise ValueError(form)


def shipped(x, index, weight, up, down, first, num_experts):
    from synapseml_tpu.onnx import ops

    return ops._expert_ffn(
        [x, index, weight, up, down],
        dict(first_expert=first, num_experts=num_experts, activation="relu2"),
        {"n_outputs": 1})


def draw(seed, rows, seq, h, f, k, experts, odds):
    """A router's picks as the cell's random routers give them: ``k``
    distinct experts a token, drawn without replacement from ``experts``,
    one of the first ``HELD`` at ``odds`` times another's odds (1: uniform,
    the cell's; None: the first ``HELD`` only)."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (rows, seq, h), jnp.bfloat16)
    scores = jax.random.uniform(keys[1], (rows, seq, experts))
    top, index = jax.lax.top_k(scores, k)
    weight = 2.5 * top / top.sum(-1, keepdims=True)
    if odds != 1:
        held = jnp.arange(experts) < HELD
        index = jax.lax.top_k(
            jnp.where(held, scores, scores - 2) if odds is None
            else jnp.where(held, scores ** (1 / odds), scores), k)[1]
    up = (jax.random.normal(keys[2], (HELD, h, f), jnp.float32)
          * h ** -0.5).astype(jnp.bfloat16)
    down = (jax.random.normal(keys[3], (HELD, f, h), jnp.float32)
            * f ** -0.5).astype(jnp.bfloat16)
    return x, index, weight, up, down


def device_ops(fn, args, most=14):
    """The device operations of one call that took longest, label -> ms (a
    loop's operations beside the ``while`` that holds them)."""
    import glob
    import tempfile

    import jax

    from benchmark.trace_reduce import op_label

    totals = {}
    with tempfile.TemporaryDirectory() as where:
        jax.profiler.start_trace(where)
        jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(
            where, "plugins", "profile", "*", "*.xplane.pb"))[0]
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for event in line.events:
                    label = op_label(event.name)
                    totals[label] = totals.get(label, 0) + event.duration_ns
    top = sorted(totals.items(), key=lambda item: -item[1])[:most]
    return {label: round(ns * 1e-6, 3) for label, ns in top}


def milliseconds(fn, args, calls=10, sets=3):
    import jax

    jax.block_until_ready(fn(*args))
    taken = []
    for _ in range(sets):
        start = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        taken.append((time.perf_counter() - start) / calls * 1e3)
    return statistics.median(taken)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    parser.add_argument("--seed", type=int, default=31)
    parser.add_argument("--forms", default=",".join(FORMS + ("shipped",)),
                        help="of " + ", ".join(FORMS + tuple(VARIANTS))
                        + ", shipped")
    parser.add_argument("--loads", default="deployment,all_held",
                        help="of " + ", ".join(LOADS))
    args = parser.parse_args(argv)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from synapseml_tpu.onnx import ops

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse_on_cpu:
        print(f"no TPU here ({device.platform}); a time comes from the chip "
              f"alone: --rehearse-on-cpu checks the answers", file=sys.stderr)
        return 3
    if on_chip:
        size = dict(rows=16, seq=4096, h=2688, f=1856, k=6)
    else:
        size = dict(rows=2, seq=96, h=32, f=48, k=6)
        ops._expert_tiling = lambda n_pairs, num_experts: (64, 64)
    print(json.dumps({"device": {"platform": device.platform,
                                 "kind": device.device_kind},
                      "size": size, "rehearsal": not on_chip}), flush=True)

    for load in args.loads.split(","):
        experts, odds = LOADS[load]
        x, index, weight, up, down = draw(args.seed, experts=experts,
                                          odds=odds, **size)
        held_picks = (np.asarray(index) < HELD).sum(-1)
        held_pairs = int(held_picks.sum())
        every = min(size["k"], -(-size["k"] * HELD // experts) + 1)
        chunk = ops._expert_tiling(index.size, experts)[1]
        rows = jax.jit(functools.partial(expert_ffn, "sorted_rows", first=0,
                                        num_experts=experts))(
            x, index, weight, up, down)
        answers = {}
        for form in args.forms.split(","):
            line = {"load": load, "form": form, "held_pairs": held_pairs,
                    "pair_chunk": chunk, "chunks": -(-held_pairs // chunk),
                    "rows_beyond": int(np.maximum(held_picks - every, 0).sum())}
            if form == "shipped":
                whole = jax.jit(functools.partial(
                    shipped, first=0, num_experts=experts))
                alone = None
            else:
                one = functools.partial(
                    expert_ffn, "held_first" if form in VARIANTS else form,
                    first=0, num_experts=experts, **VARIANTS.get(form, {}))
                whole = jax.jit(one)
                alone = jax.jit(lambda *a, one=one: one(*a[:-1], rows=a[-1]))
            given = (x, index, weight, up, down)
            out = whole(*given)
            answers[form] = np.asarray(out.astype(jnp.float32))
            line["same_bits_twice"] = bool(
                (np.asarray(whole(*given).astype(jnp.float32))
                 == answers[form]).all())
            line["finite"] = bool(np.isfinite(answers[form]).all())
            if "parent" in answers:
                line["max_abs_from_parent"] = float(
                    np.abs(answers[form] - answers["parent"]).max())
                line["rows_differing_from_parent"] = int(
                    (answers[form] != answers["parent"]).any(-1).sum())
            if alone is not None:
                got = np.asarray(alone(*given, rows).astype(jnp.float32))
                line["alone_equals_in_block"] = bool(
                    (got == answers[form]).all())
            if on_chip:
                line["in_block_ms"] = milliseconds(whole, given)
                line["in_block_ops_ms"] = device_ops(whole, given)
                if alone is not None:
                    line["alone_ms"] = milliseconds(alone, given + (rows,))
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
