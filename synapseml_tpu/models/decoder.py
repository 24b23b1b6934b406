"""Parts the zoo's decoder builders share, and the one generating hybrid
decoder that ``jamba`` and ``olmo_hybrid`` both are.

- ``Weights``: the seeded weight store every decoder's bits come from. A
  tensor is seeded by (``seed``, its ordinal in the store, a slice), so the
  order in which a builder adds weights and integer constants is part of
  the weights' bits.
- Graph parts: ``gated_ffn`` (and its ``gated_weights``), the sigmoid
  ``router``, the greedy choice (``greedy``, ``choose``), ``of_shape``, a
  loop body's ``infos``, and a recurrent mixer's ``causal_conv`` (and its
  ``step_bias``).
- The runs a generating graph repeats: ``first_token`` (id 0 into the
  outputs), ``decode_tail`` (the body's norm, head and writes) and
  ``decode_loop`` (the ``Loop`` and the graph's outputs).
- ``hybrid_decoder``: a prompt pass over every layer, then ``Loop`` ``decode``
  of one position a row. A layer is the model's recurrent mixer or causal
  attention; the loop carries, for each layer, either (recurrent state
  float32, convolution rows bfloat16), replaced every pass, or (keys,
  values) bfloat16, written in place. The body reads the outer graph's
  initializers: weights are named ``l#_...`` once and used by both passes.
  The model supplies its mixer, its attention projections, its block and
  its head.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from ..onnx.builder import make_graph, make_model, node, value_info
from ..onnx.wire import DataType, ModelProto, numpy_to_tensor

EXPERT_DOMAIN = "synapseml_tpu"
_FLOAT = DataType.FLOAT


def _round_to_bfloat16(a: np.ndarray, out: np.ndarray) -> None:
    """float32 ``a`` (overwritten) -> the bits of the nearest bfloat16 into
    ``out`` (ties away from zero), in two passes of plain integer arithmetic
    (numpy releases the GIL for it; ``ml_dtypes``' cast does not promise to)."""
    bits = a.reshape(-1).view(np.uint32)
    bits += 0x8000
    out.reshape(-1)[...] = bits.view(np.uint16)[1::2]  # the high halves


# numbers a job draws at most: a thread's float32 scratch is this long and is
# used again and again (fresh pages cost more than the draws on some hosts)
_JOB_SIZE = 1 << 22


class Weights:
    """Initializers by name. A tensor is drawn in slices of rows, each by its
    own generator seeded by (``seed``, the tensor's ordinal, the slice's), so
    a thread pool fills them in any order to the same bits."""

    def __init__(self, seed: int):
        import ml_dtypes

        self.seed = seed
        self.bfloat16 = np.dtype(ml_dtypes.bfloat16)
        self.store: Dict[str, np.ndarray] = {}
        self._jobs: List[Tuple[np.ndarray, Tuple[int, ...], Callable]] = []

    def ints(self, name: str, values) -> str:
        self.store[name] = np.asarray(values, dtype=np.int64)
        return name

    def draw(self, name: str, shape: Tuple[int, ...], fill: Callable) -> str:
        """``fill(rng, scratch)`` writes float32 numbers into ``scratch``
        (flat, as long as the slice it fills)."""
        bits = np.empty(shape, np.uint16)
        ordinal = len(self.store)
        self.store[name] = bits.view(self.bfloat16)
        flat = bits.reshape(-1)
        self._jobs += [(flat[lo:lo + _JOB_SIZE], (self.seed, ordinal, i), fill)
                       for i, lo in enumerate(range(0, flat.size, _JOB_SIZE))]
        return name

    def normal(self, name: str, shape: Tuple[int, ...], std: float) -> str:
        def fill(rng, scratch):
            rng.standard_normal(out=scratch, dtype=np.float32)
            scratch *= np.float32(std)

        return self.draw(name, shape, fill)

    def full(self, name: str, shape: Tuple[int, ...], value: float) -> str:
        return self.draw(name, shape,
                         lambda rng, scratch: scratch.fill(value))

    def fill_all(self) -> None:
        local = threading.local()

        def run(job):
            bits, key, fill = job
            if not hasattr(local, "scratch"):
                local.scratch = np.empty(_JOB_SIZE, np.float32)
            scratch = local.scratch[:bits.size]
            fill(np.random.default_rng(key), scratch)
            _round_to_bfloat16(scratch, bits)

        with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
            list(pool.map(run, self._jobs))
        self._jobs = []


def cast_float(nodes, src: str, name: str) -> str:
    nodes.append(node("Cast", [src], [name], name=name, to=_FLOAT))
    return name


def gated_weights(w: Weights, p: str, hidden: int, width: int) -> None:
    w.normal(p + "_gate_w", (hidden, width), hidden ** -0.5)
    w.normal(p + "_up_w", (hidden, width), hidden ** -0.5)
    w.normal(p + "_down_w", (width, hidden), width ** -0.5)


def gated_ffn(add, p: str, wp: str, u: str) -> str:
    """``(silu(u G) * (u U)) D`` with the weights ``wp_{gate,up,down}_w``."""
    add(node("MatMul", [u, wp + "_gate_w"], [p + "_g"], name=p + "_gate"))
    add(node("Sigmoid", [p + "_g"], [p + "_g_s"], name=p + "_silu_s"))
    add(node("Mul", [p + "_g", p + "_g_s"], [p + "_g_a"], name=p + "_silu"))
    add(node("MatMul", [u, wp + "_up_w"], [p + "_u"], name=p + "_up"))
    add(node("Mul", [p + "_g_a", p + "_u"], [p + "_h"], name=p + "_gated"))
    add(node("MatMul", [p + "_h", wp + "_down_w"], [p + "_out"],
             name=p + "_down"))
    return p + "_out"


def router(nodes, w: Weights, p: str, u: str, hidden: int, experts: int,
           top_k: int, scaling: float, weights: str = None):
    """The sigmoid router of ``nemotron_h`` and ``joyai_flash``: scores
    ``sigmoid(u W_r)`` in float32 over every expert, as wide as published
    whatever is held here; the ``top_k`` largest of scores + bias are chosen,
    the chosen SCORES renormalised and scaled. Names the picks and their
    weights. ``weights`` prefixes the router's two tensors where they are
    not the nodes' own ``p`` (a graph that runs one layer in several
    passes); they are drawn on first use."""
    add, wp = nodes.append, weights or p
    if wp + "_router_w" not in w.store:
        w.normal(wp + "_router_w", (hidden, experts), hidden ** -0.5)
        w.normal(wp + "_router_bias", (experts,), 0.01)
    add(node("MatMul", [cast_float(nodes, u, p + "_u_f"),
                        cast_float(nodes, wp + "_router_w",
                                   p + "_router_w_f")],
             [p + "_router"], name=p + "_moe_route"))
    add(node("Sigmoid", [p + "_router"], [p + "_scores"],
             name=p + "_moe_scores"))
    add(node("Add", [p + "_scores",
                     cast_float(nodes, wp + "_router_bias",
                                p + "_router_bias_f")],
             [p + "_choice"], name=p + "_moe_choice"))
    add(node("TopK", [p + "_choice", w.ints("top_k", [top_k])],
             [p + "_top_v", p + "_top_i"], name=p + "_moe_topk", axis=-1))
    add(node("GatherElements", [p + "_scores", p + "_top_i"], [p + "_top_s"],
             name=p + "_moe_pick", axis=-1))
    add(node("ReduceSum", [p + "_top_s", w.ints("axes_9", [-1])],
             [p + "_top_sum"], name=p + "_moe_sum", keepdims=1))
    w.store["tiny"] = np.asarray(1e-20, np.float32)
    w.store["routed_scaling"] = np.asarray(scaling, np.float32)
    add(node("Add", [p + "_top_sum", "tiny"], [p + "_top_den"],
             name=p + "_moe_den"))
    add(node("Div", [p + "_top_s", p + "_top_den"], [p + "_top_n"],
             name=p + "_moe_norm"))
    add(node("Mul", [p + "_top_n", "routed_scaling"], [p + "_top_w"],
             name=p + "_moe_weight"))
    return p + "_top_i", p + "_top_w"


def greedy(add, c: str):
    """The greedy choice over the float32 widening of ``c_logits [N, 1,
    vocab]``: names the id ``[N, 1]`` and its log-softmax ``[N, 1]``."""
    add(node("Cast", [c + "_logits"], [c + "_logits_f"], name=c + "_logits_f",
             to=_FLOAT))
    add(node("ArgMax", [c + "_logits_f"], [c + "_id"], name=c + "_id",
             axis=-1, keepdims=0))
    add(node("ReduceMax", [c + "_logits_f", "axes_last"], [c + "_top"],
             name=c + "_top", keepdims=0))
    add(node("ReduceLogSumExp", [c + "_logits_f", "axes_last"], [c + "_lse"],
             name=c + "_lse", keepdims=0))
    add(node("Sub", [c + "_top", c + "_lse"], [c + "_logprob"],
             name=c + "_logprob"))
    return c + "_id", c + "_logprob"


def choose(add, c: str, final: str):
    """The head ``lm_head`` over ``final [N, 1, hidden]``, float32 logits,
    the greedy choice: names the id ``[N, 1]`` and its log-softmax."""
    add(node("MatMul", [final, "lm_head"], [c + "_logits"], name=c + "_head"))
    return greedy(add, c)


def of_shape(name: str, shape: str, value) -> object:
    return node("ConstantOfShape", [shape], [name], name=name,
                value=numpy_to_tensor(name + "_value", np.asarray([value])))


def infos(names: List[str], leading, n_caches: int = 0) -> List:
    """Value infos of a body's inputs or outputs: the ``leading`` types,
    then the caches in the checkpoint's type."""
    import ml_dtypes

    types = list(leading) + [ml_dtypes.bfloat16] * n_caches
    return [value_info(n, t) for n, t in zip(names, types)]


def step_bias(low: float, high: float):
    """A ``Weights.draw`` fill: the inverse softplus of a log-uniform step
    in ``[low, high]``."""
    def fill(rng, scratch):
        dt = np.exp(rng.uniform(np.log(low), np.log(high), scratch.size))
        scratch[...] = dt + np.log(-np.expm1(-dt))
    return fill


def causal_conv(add, p: str, wp: str, what: str, channels: int, width: int,
                bias: bool = False, conv_rows: str = None) -> str:
    """The depthwise causal convolution over positions of ``p_<what>_raw
    [N, s, channels]``, ``width`` taps ``wp_conv_w`` (and ``wp_conv_b`` where
    ``bias``). The prompt pass (no ``conv_rows``) pads with zeros; a decode
    pass runs the published single step over the window ``[width, N,
    channels]`` of ``conv_rows`` and its one new row, in float32, from the
    taps ``wp_conv_taps [width, 1, channels]`` (and ``wp_conv_b_f``). Names
    the output ``p_conv_out``; ``p_conv_rows [width - 1, N, channels]``,
    positions major, are what the next pass reads."""
    raw = f"{p}_{what}_raw"
    if conv_rows is None:
        add(node("Slice", [raw, "conv_keep_from", "huge_1d", "axes_1"],
                 [p + "_conv_kept"], name=p + "_conv_kept"))
        add(node("Transpose", [p + "_conv_kept"], [p + "_conv_rows"],
                 name=p + "_conv_rows", perm=[1, 0, 2]))
        add(node("Transpose", [raw], [p + "_conv_in"], name=p + "_conv_in",
                 perm=[0, 2, 1]))
        add(node("Conv", [p + "_conv_in", wp + "_conv_w"]
                 + ([wp + "_conv_b"] if bias else []), [p + "_conv_t"],
                 name=p + "_conv", group=channels, kernel_shape=[width],
                 pads=[width - 1, 0]))
        add(node("Transpose", [p + "_conv_t"], [p + "_conv_out"],
                 name=p + "_conv_out", perm=[0, 2, 1]))
        return p + "_conv_out"
    # positions lead, so every join, slice and product is of whole rows
    new = f"{p}_{what}_new"
    add(node("Transpose", [raw], [new], name=new, perm=[1, 0, 2]))
    add(node("Concat", [conv_rows, new], [p + "_window"], name=p + "_window",
             axis=0))
    add(node("Slice", [p + "_window", "index1", "huge_1d", "axes_0"],
             [p + "_conv_rows"], name=p + "_conv_rows"))
    add(node("Cast", [p + "_window"], [p + "_window_f"], name=p + "_window_f",
             to=_FLOAT))
    add(node("Mul", [p + "_window_f", wp + "_conv_taps"], [p + "_conv_terms"],
             name=p + "_conv_terms"))
    add(node("ReduceSum", [p + "_conv_terms", "axes_0"], [p + "_conv_sum"],
             name=p + "_conv_sum", keepdims=0))
    row = p + "_conv_sum"
    if bias:
        add(node("Add", [row, wp + "_conv_b_f"], [p + "_conv_f"],
                 name=p + "_conv_f"))
        row = p + "_conv_f"
    add(node("Unsqueeze", [row, "axes_1"], [p + "_conv_row"],
             name=p + "_conv_row"))
    add(node("CastLike", [p + "_conv_row", raw], [p + "_conv_out"],
             name=p + "_conv_out"))
    return p + "_conv_out"


# ---- the runs of a graph that generates greedily in a Loop "decode"

# what every such loop carries beside its caches, and their types
STATE = ["last_id", "tokens", "chosen_logprob", "pooled_sum"]
KINDS = [np.int64, np.int64, np.float32, np.float32]


def first_token(add, head) -> List[str]:
    """``head(add, "p", "p_final")`` at the prompt's last position: id 0 and
    its log-prob written at slot 0 of zeroed ``[N, generate]`` outputs, the
    final norm's output the pooled sum's start. Names the starts of
    ``STATE``."""
    first_id, first_logprob = head(add, "p", "p_final")
    add(of_shape("row_zero", "n_1d", np.int64(0)))
    add(of_shape("tokens_zero", "n_generate_shape", np.int64(0)))
    add(of_shape("logprob_zero", "n_generate_shape", np.float32(0)))
    add(node("TensorScatter", ["tokens_zero", first_id, "row_zero"],
             ["tokens_start"], name="tokens_start", axis=1))
    add(node("TensorScatter", ["logprob_zero", first_logprob, "row_zero"],
             ["logprob_start"], name="logprob_start", axis=1))
    add(node("Cast", ["p_final"], ["p_final_f"], name="p_final_f", to=_FLOAT))
    add(node("Squeeze", ["p_final_f", "axes_1"], ["pooled_start"],
             name="pooled_start"))
    return [first_id, "tokens_start", "logprob_start", "pooled_start"]


def decode_tail(add, head, x: str, eps: float) -> List[str]:
    """The end of a decode pass over ``x [N, 1, hidden]``: the final norm
    (``d_final``), ``head``, the id and its log-prob written at the trip's
    slot, the pooled sum. Names the body's condition and ``STATE``'s new
    values."""
    add(node("RMSNormalization", [x, "norm_f_w"], ["d_final"],
             name="d_norm_f", axis=-1, epsilon=eps))
    new_id, new_logprob = head(add, "d", "d_final")
    add(node("TensorScatter", ["d_tokens", new_id, "d_slot_1d"],
             ["d_tokens_out"], name="d_tokens_out", axis=1))
    add(node("TensorScatter", ["d_chosen_logprob", new_logprob, "d_slot_1d"],
             ["d_chosen_logprob_out"], name="d_chosen_logprob_out", axis=1))
    add(node("Cast", ["d_final"], ["d_final_f"], name="d_final_f",
             to=_FLOAT))
    add(node("Squeeze", ["d_final_f", "axes_1"], ["d_final_row"],
             name="d_final_row"))
    add(node("Add", ["d_pooled_sum", "d_final_row"], ["d_pooled_sum_out"],
             name="d_pooled_sum_out"))
    add(node("Identity", ["trip_cond"], ["trip_cond_out"],
             name="trip_cond_out"))
    return ["trip_cond_out", new_id, "d_tokens_out", "d_chosen_logprob_out",
            "d_pooled_sum_out"]


def decode_loop(add, body, state: List[str], starts: List[str],
                finals: List[str], generate: int, hidden: int) -> List:
    """``Loop`` ``decode`` over ``body`` from ``starts`` (``state``'s, then
    the caches'; ``finals`` names the caches it leaves), then ``tokens``,
    ``chosen_logprob`` and ``pooled`` (the sum over the ``generate``
    positions, divided). Names their value infos."""
    add(node("Loop", ["trips", ""] + starts,
             [s + "_total" for s in state] + finals, name="decode",
             body=body))
    add(node("Identity", ["tokens_total"], ["tokens"], name="tokens"))
    add(node("Identity", ["chosen_logprob_total"], ["chosen_logprob"],
             name="chosen_logprob"))
    add(node("Cast", ["generate_1d"], ["generate_f"], name="generate_f",
             to=_FLOAT))
    add(node("Div", ["pooled_sum_total", "generate_f"], ["pooled"],
             name="pooled"))
    return [value_info("tokens", np.int64, ["N", generate]),
            value_info("chosen_logprob", np.float32, ["N", generate]),
            value_info("pooled", np.float32, ["N", hidden])]


def hybrid_decoder(w: Weights, nodes: List, z, *, name: str,
                   attention: Sequence[bool], heads: int, kv_heads: int,
                   hidden: int, generate: int, eps: float,
                   ints: Sequence[Tuple[str, object]], mixer, projections,
                   block, head) -> ModelProto:
    """The generating hybrid decoder (module docstring) over the weights in
    ``w`` and the set-up ``nodes`` already made; layer ``i`` is causal
    attention where ``attention[i]``. ``ints`` are the model's integer
    constants, stored among the graph's own. What the model supplies, each
    given ``z``, its sizes:

    - ``mixer(add, z, p, wp, u[, conv_rows, state_in])``: the recurrent
      mixer over ``u [N, s, hidden]`` (a decode pass gives the carried
      values); names the mix, the state and the rows the next pass reads;
    - ``projections(add, z, p, wp, u)``: names the queries, keys and values;
    - ``block(nodes, z, c, i, x, mixer)``: layer ``i`` in pass ``c`` around
      ``mixer(p, wp, u)``; names its output;
    - ``head(add, c, final)``: names the id and its log-prob."""
    layers = len(attention)
    for const, values in (
            ("zero", 0), ("one", 1), ("index0", [0]), ("index1", [1]),
            ("axes_0", [0]), ("axes_1", [1]), ("axes_last", [-1]),
            ("one_1d", [1]), ("generate_1d", [generate]),
            ("trips", generate - 1), *ints,
            ("cache_pad", [0, 0, 0, 0, generate, 0])):
        w.ints(const, values)
    add = nodes.append
    # sizes from the feed's shape (constants of a trace): N, S, L = S + G
    add(node("Shape", ["input_ids"], ["ids_shape"], name="ids_shape"))
    add(node("Gather", ["ids_shape", "index0"], ["n_1d"], name="n_1d"))
    add(node("Gather", ["ids_shape", "index1"], ["s_1d"], name="s_1d"))
    add(node("Squeeze", ["s_1d", "axes_0"], ["prompt_len"],
             name="prompt_len"))
    add(node("Add", ["s_1d", "generate_1d"], ["total_1d"], name="total_1d"))
    add(node("Squeeze", ["total_1d", "axes_0"], ["total_len"],
             name="total_len"))
    add(node("Sub", ["s_1d", "one_1d"], ["last_1d"], name="last_1d"))
    add(node("Range", ["zero", "total_len", "one"], ["all_positions"],
             name="all_positions"))
    add(node("Concat", ["n_1d", "generate_1d"], ["n_generate_shape"],
             name="n_generate_shape", axis=0))

    # ---- the prompt pass: every position; the states and caches it leaves
    carried = {}  # a layer's two carried values, as the prompt pass names them

    def prompt_mixer(i):
        def mix(p, wp, u):
            if not attention[i]:
                out, *carried[i] = mixer(add, z, p, wp, u)
                return out
            q, k, v = projections(add, z, p, wp, u)
            add(node("Attention", [q, k, v], [p + "_ctx"], name=p + "_att",
                     q_num_heads=heads, kv_num_heads=kv_heads, is_causal=1))
            carried[i] = []
            for rows in (k, v):
                add(node("Pad", [rows, "cache_pad"], [rows + "_cache"],
                         name=rows + "_cache", mode="constant"))
                carried[i].append(rows + "_cache")
            add(node("MatMul", [p + "_ctx", wp + "_o_w"], [p + "_mix"],
                     name=p + "_att_o"))
            return p + "_mix"
        return mix

    add(node("Gather", ["tok_emb", "input_ids"], ["p_tok"], name="p_tok",
             axis=0))
    x = "p_tok"
    for i in range(layers):
        x = block(nodes, z, "p", i, x, prompt_mixer(i))
    add(node("Gather", [x, "last_1d"], ["p_last"], name="p_last", axis=1))
    add(node("RMSNormalization", ["p_last", "norm_f_w"], ["p_final"],
             name="p_norm_f", axis=-1, epsilon=eps))
    starts = first_token(add, head)

    # ---- the body of Loop "decode": one position a row
    # a recurrent layer carries (state, convolution rows), replaced every
    # pass; an attention layer (keys, values), written in place
    of_layers = [t for i in range(layers) for t in (
        [w.bfloat16] * 2 if attention[i] else [np.float32, w.bfloat16])]
    d_carried = {i: [f"d_carried{i}_{j}" for j in range(2)]
                 for i in range(layers)}
    d_nodes: List = []
    d_add = d_nodes.append
    d_add(node("Add", ["trip", "prompt_len"], ["d_position"],
               name="d_position"))
    d_add(node("Expand", ["d_position", "n_1d"], ["d_position_1d"],
               name="d_position_1d"))
    d_add(node("LessOrEqual", ["all_positions", "d_position"],
               ["d_visible_1d"], name="d_visible_1d"))
    d_add(node("Unsqueeze", ["d_visible_1d", "axes_0"], ["d_visible"],
               name="d_visible"))
    d_add(node("Add", ["trip", "one"], ["d_slot"], name="d_slot"))
    d_add(node("Expand", ["d_slot", "n_1d"], ["d_slot_1d"], name="d_slot_1d"))
    d_left = {}

    def decode_mixer(i):
        def mix(p, wp, u):
            if not attention[i]:
                state_in, conv_rows = d_carried[i]
                out, *d_left[i] = mixer(d_add, z, p, wp, u, conv_rows,
                                        state_in)
                return out
            q, k, v = projections(d_add, z, p, wp, u)
            d_left[i] = []
            for rows, cache in zip((k, v), d_carried[i]):
                d_add(node("TensorScatter", [cache, rows, "d_position_1d"],
                           [rows + "_cache"], name=rows + "_cache", axis=1))
                d_left[i].append(rows + "_cache")
            d_add(node("Attention", [q, *d_left[i], "d_visible"],
                       [p + "_ctx"], name=p + "_att", q_num_heads=heads,
                       kv_num_heads=kv_heads))
            d_add(node("MatMul", [p + "_ctx", wp + "_o_w"], [p + "_mix"],
                       name=p + "_att_o"))
            return p + "_mix"
        return mix

    d_add(node("Gather", ["tok_emb", "d_last_id"], ["d_tok"], name="d_tok",
               axis=0))
    x = "d_tok"
    for i in range(layers):
        x = block(d_nodes, z, "d", i, x, decode_mixer(i))
    d_out = decode_tail(d_add, head, x, eps)
    d_in = ["trip", "trip_cond"] + ["d_" + s for s in STATE] \
        + [n for i in range(layers) for n in d_carried[i]]
    d_out += [n for i in range(layers) for n in d_left[i]]
    body = make_graph(d_nodes, "decode_pass",
                      infos(d_in, [np.int64, np.bool_] + KINDS + of_layers),
                      infos(d_out, [np.bool_] + KINDS + of_layers))

    # ---- the loop and the outputs
    outputs = decode_loop(
        add, body, STATE,
        starts + [n for i in range(layers) for n in carried[i]],
        [f"final_carried{i}_{j}" for i in range(layers) for j in range(2)],
        generate, hidden)
    w.fill_all()
    graph = make_graph(nodes, name,
                       [value_info("input_ids", np.int64, ["N", "S"])],
                       outputs, w.store)
    return make_model(graph, opset=24, domains={EXPERT_DOMAIN: 1})
