"""Run one cell of the benchmark once, in this process, on this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, driver or metric is a
file found by name, so a later PR adds cells and metrics by adding files:

    workloads/<cell>.json      configuration, driver, traffic, check limits
    configs/<config>.json      the model as run, its source and builder
    drivers/<driver>.py        how a window is driven (class ``Driver``)
    metrics/<metric>.json      reader and its parameters
    readers/<reader>.py        ``read(record, params)`` -> number or None
    checks/<kind>.py           what is compared with the reference, and how
    reference/<family>.py      the plain forward pass (class ``Reference``)
    work/<family>.py           operations and bytes from shapes
    peaks.json                 the chip's peaks by ``device_kind``

Which metrics a run prints comes from ``BENCHMARK.json`` at the root of the
checkout: the ``end_to_end`` ones with ``--trace 0``, the ``per_layer`` ones
with ``--trace 1``. The last line of standard output is the result.

A run needs a TPU and fails without one. ``--rehearse-on-cpu`` is for tests
and for trying the command in a sandbox: it skips that look, marks the device
as what it is (``cpu``), and is never part of ``BENCHMARK.json``'s command.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NO_CHIP_EXIT = 3
TRACE_MIN_CALLS, TRACE_MIN_SECONDS = 5, 4.0


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def place_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside this checkout, whatever
    the environment says: the path is part of the cache's key, and a cache
    the machine lends may cap an entry below the size of a model-sized
    program (192 MiB on the chip tool's machine; the programs are 0.4-0.5 GB
    because the weights are literals). Set before jax is imported, so the
    program's own placement (``runtime/compile_cache``) takes the same one."""
    path = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def look_for_chips(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not rehearse and (platform != "tpu" or len(devices) < chips):
        print(f"benchmark: needs {chips} TPU chip(s); jax found "
              f"{len(devices)} x {platform}. No result.", file=sys.stderr)
        sys.exit(NO_CHIP_EXIT)
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak HBM held on the fullest device. This runtime keeps a compiled
    program's temporaries in a reserved region that ``peak_bytes_in_use``
    leaves out (PERF.md section 3, device), so the peak is the sum of the two
    peaks; where the backend reports nothing (the CPU), 0."""
    import jax

    peaks = [0]
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


class Tracer:
    """Profiles the first calls of the window: at least ``TRACE_MIN_CALLS``
    calls and ``TRACE_MIN_SECONDS`` seconds, then stops; the window goes on."""

    def __init__(self, on: bool):
        self.on, self.active, self.dir, self.started = on, False, None, 0.0

    def start(self) -> None:
        if not self.on:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        self.active, self.started = True, time.perf_counter()

    def after_call(self, i: int) -> None:
        if (self.active and i + 1 >= TRACE_MIN_CALLS and
                time.perf_counter() - self.started >= TRACE_MIN_SECONDS):
            self.stop()

    def stop(self) -> None:
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False

    def reduce(self, program: str):
        if self.dir is None:
            return None
        from benchmark.trace_reduce import reduce_trace

        try:
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            return reduce_trace(found[0], program) if found else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def registry_families() -> dict:
    """The program's metric registry as it stands (``ProfiledJit`` keeps its
    compile accounting there)."""
    from synapseml_tpu.observability.metrics import get_registry

    return get_registry().snapshot()["families"]


def metric_entries(trace: bool, cell: str) -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m for m in bench["per_layer" if trace else "end_to_end"]
            if cell in m.get("workloads", [cell])]


def read_metrics(entries: list, record: dict) -> dict:
    out = {}
    for entry in entries:
        spec = load_json("metrics", entry["name"] + ".json")
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        value = reader.read(record, spec.get("params", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, with_control: bool = False) -> dict:
    cell = load_json("workloads", workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    place_compile_cache()
    device = look_for_chips(cell.get("chips", 1), rehearse)
    peaks = load_json("peaks.json").get(device["kind"])
    if peaks is None and not rehearse:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device['kind']!r} in benchmark/peaks.json")

    module = importlib.import_module("benchmark.drivers." + cell["driver"])
    driver = module.Driver(config, cell["traffic"], seed)
    driver.setup()
    families_before = registry_families()
    tracer = Tracer(trace)
    setup_s = time.perf_counter() - _PROCESS_START

    tracer.start()
    record = driver.drive(seconds, tracer.after_call)
    tracer.stop()
    device["memory_peak_bytes"] = memory_peak_bytes()
    answers = record.pop("answers")
    work = importlib.import_module("benchmark.work." + config["work"])
    dims = cell["traffic"].get("dims", {})
    record.update(
        setup_s=setup_s, families_before=families_before,
        families_after=registry_families(), peaks=peaks,
        memory_peak_bytes=device["memory_peak_bytes"],
        flops_per_row=work.flops_per_row(config, dims),
        matmul_least_s=None if peaks is None else work.matmul_least_seconds(
            config, dims, record["bucket"], peaks["bf16_flops_per_s"],
            peaks["hbm_bytes_per_s"])["seconds"],
        trace=tracer.reduce(config["program"]))
    driver.release()

    # a call that raises ends the run with no result, so none has failed here
    checker = importlib.import_module("benchmark.checks."
                                      + cell["check"]["kind"])
    verdict = checker.check(cell, config, driver, answers, seed, with_control)
    result = {
        "correct": bool(verdict["correct"] and answers),
        "attempted": len(answers), "failed": 0,
        "metrics": read_metrics(metric_entries(trace, workload), record),
        "device": device, "workload": workload, "seed": seed,
        "window_s": record["window_s"], "check_s": verdict["check_s"],
        "call_ms": [round((end - start) * 1e3, 3)
                    for start, end, _ in record["calls"]],
    }
    if record["trace"] is not None:
        result["device"].update(busy_s=record["trace"]["busy_s"],
                                window_s=record["trace"]["window_s"])
        result["breakdown"] = {k: record["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    if with_control:
        result["control"] = verdict["control"]
        result["numbers"] = verdict["numbers"]
    result["compared"] = verdict["compared"]
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-on-cpu", action="store_true")
    args = p.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      rehearse=args.rehearse_on_cpu)
    sys.stdout.flush()
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
