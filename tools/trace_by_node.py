"""Device time of one cell's program by the ONNX node each device operation
came from: what ``PERF.md`` section 5's "device time by ONNX node" is read
from (``benchmark/trace_reduce.py`` cannot sum by scope yet: ``PERF.md``
section 7).

    python3 tools/trace_by_node.py --workload <cell> --seed <n> [--calls 2]

Sets the cell up exactly as ``benchmark/run.py`` does (its driver, its pool),
makes ``--calls`` calls under the profiler with the Python tracer off, and
joins two things by the HLO instruction's name: the device operations of the
trace (``XLA Ops``: an event's name is its instruction's text) and the
compiled program's own text, whose ``metadata={op_name=...}`` holds the scope
``<op_type>.<node name>`` the executor traced the instruction under. A fusion
carries its root's scope. Layer indices fold (``l3_`` -> ``l#_``); a node
name's first letters keep the pass apart where a builder names them so
(``p_`` prompt, ``b_`` a loop body, ``c_`` a commit pass, ``d_`` a decode
pass). Container
operations (``while``, ``conditional``, ``call``) span their bodies' own
events and are left out of the sums. One JSON line: ms a call by node,
largest first, the sum, and the union (``busy_ms``). Needs a TPU.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "benchmark")):
    if path not in sys.path:
        sys.path.insert(0, path)

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?op_name=\"([^\"]*)\"",
                          re.M)
_NODE = re.compile(r"^[A-Z][A-Za-z0-9]*\.[\w.]+$")
_CONTAINER = re.compile(r"[)\]}] (while|conditional|call)\(")


def node_of(op_name: str) -> str:
    """The innermost ``<op_type>.<node name>`` of a scope path that is not a
    ``Loop``'s own, layer index folded."""
    parts = [p for p in op_name.split("/") if _NODE.match(p)
             and not p.startswith("Loop.")]
    if not parts:
        return "(no node)"
    return re.sub(r"(^|_)l\d+_", r"\1l#_", parts[-1].split(".", 1)[1])


def scopes(compiled_text: str) -> dict:
    return {name: node_of(op_name)
            for name, op_name in _INSTRUCTION.findall(compiled_text)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--calls", type=int, default=2)
    p.add_argument("--top", type=int, default=40)
    p.add_argument("--by-op", action="store_true",
                   help="split a node's time by the kind and result shape "
                        "of its device operations")
    args = p.parse_args(argv)
    import run
    from benchmark import trace_reduce as tr

    cell = run.load_json("workloads", args.workload + ".json")
    config = run.load_json("configs", cell["config"] + ".json")
    run.place_compile_cache()
    run.look_for_chips(cell.get("chips", 1), False)
    import jax

    module = importlib.import_module("benchmark.drivers." + cell["driver"])
    driver = module.Driver(config, cell["traffic"], args.seed)
    driver.setup()
    trace_dir = tempfile.mkdtemp(prefix="by_node_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for i in range(args.calls):
        driver._call(driver.tables[i % len(driver.tables)])
    jax.profiler.stop_trace()
    compiled = [entry.compiled.as_text()
                for entry in driver.model.fn._jit._cache.values()]
    by_name = scopes("\n".join(compiled))
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    planes = tr.load(found[0])
    shutil.rmtree(trace_dir, ignore_errors=True)
    totals, intervals = {}, []
    for name, lines in planes.items():
        if not name.startswith("/device:"):
            continue
        for lo, hi, text in lines.get("XLA Ops", []):
            if _CONTAINER.search(text):
                continue
            instruction = text.split(" = ", 1)[0].strip().lstrip("%")
            key = by_name.get(instruction, "(not in the program)")
            if args.by_op:
                key += " | " + tr.op_label(text)
            tr.add(totals, key, hi - lo)
            intervals.append((lo, hi))
    per_call = {k: 1e3 * v / args.calls for k, v in totals.items()}
    ranked = sorted(per_call.items(), key=lambda kv: -kv[1])
    busy = sum(hi - lo for lo, hi in tr.merge(intervals))
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "calls": args.calls,
        "busy_ms": 1e3 * busy / args.calls,
        "sum_ms": sum(per_call.values()),
        "by_node_ms": [[k, round(v, 3)] for k, v in ranked[:args.top]],
        "rest_ms": round(sum(v for _, v in ranked[args.top:]), 3)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
