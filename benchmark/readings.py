"""Readings a cell's limits are set from: the program's numbers over many
seeds and the control's over a few, in one process (set-up is most of a run).

    python3 benchmark/readings.py --workload <cell> --seeds 12 --control-seeds 3 --seconds 3

Every seed makes a new pool, drives a short window of the timed path at the
cell's own bucket, and compares what it returned with the float32 reference;
on the first ``--control-seeds`` seeds the reference at the control's
precision (``check.control`` of the cell's file) is put in the program's
place and compared the same way. One JSON line a seed, then the lower reading
(largest of the program) and the upper (smallest of the control) a number.
Not part of the benchmark's command: ``PERF.md`` records what it printed.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys

from run import load_json, look_for_chips, place_compile_cache


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=2_200_000_011)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--rehearse-on-cpu", action="store_true")
    args = p.parse_args(argv)

    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    place_compile_cache()
    device = look_for_chips(cell.get("chips", 1), args.rehearse_on_cpu)
    module = importlib.import_module("benchmark.drivers." + cell["driver"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    driver = module.Driver(config, cell["traffic"], seeds[0])
    driver.setup()
    checker = importlib.import_module("benchmark.checks."
                                      + cell["check"]["kind"])
    reference = checker.build_reference(config, driver.model_bytes)
    program, control = [], []
    for i, seed in enumerate(seeds):
        driver.new_pool(seed)
        record = driver.drive(args.seconds, lambda _: None)
        verdict = checker.check(cell, config, driver, record["answers"], seed,
                        with_control=i < args.control_seeds,
                        reference=reference)
        program.append(verdict["numbers"])
        line = {"seed": seed, "calls": len(record["calls"]),
                "check_s": verdict["check_s"], "program": verdict["numbers"]}
        if "control" in verdict:
            control.append(verdict["control"]["numbers"])
            line["control"] = verdict["control"]["numbers"]
        print(json.dumps(line), flush=True)
    summary = {"workload": args.workload, "device": device,
               "lower": {k: max(n[k] for n in program) for k in program[0]},
               "program_min": {k: min(n[k] for n in program)
                               for k in program[0]},
               "upper": {k: min(n[k] for n in control) for k in control[0]}
               if control else {}}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
