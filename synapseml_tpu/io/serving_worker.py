"""Standalone serving worker process.

``python -m synapseml_tpu.io.serving_worker <stage_path> [--host H]
[--port P] [--mode continuous|microbatch]`` loads a saved pipeline stage,
starts a serving engine on its own HTTP server, prints
``ADDRESS http://host:port`` on stdout (the parent's registration
handshake), and serves until the process is terminated.

This is the real-process analogue of the reference's per-executor
``WorkerServer`` (``continuous/HTTPSourceV2.scala:476``): the unit tier can
simulate executors with threads, but the fault story — a worker DYING while
the service keeps answering — only means something across process
boundaries. ``ProcessServingFleet`` spawns these and the RoutingServer's
failover evicts any that stop answering.
"""

from __future__ import annotations

import argparse
import sys
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("stage_path", nargs="?", default=None)
    ap.add_argument("--models-json", default=None,
                    help="multi-tenant worker: JSON dict of "
                         '{"model": {"stage_path": ..., "generation": N}};'
                         " every model loads into one shared server "
                         "behind a MultiTenantServingEngine (the "
                         "stage_path positional is then omitted)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "microbatch"])
    ap.add_argument("--reply-col", default="reply")
    ap.add_argument("--reply-timeout", type=float, default=30.0,
                    help="seconds a handler holds an exchange open for the "
                         "engine's reply (requests carrying "
                         "X-SMT-Deadline-Ms are bounded by the tighter of "
                         "the two)")
    ap.add_argument("--import-module", action="append", default=[],
                    help="module(s) to import before loading the stage "
                         "(registers user-defined stage classes)")
    # flight-recorder (tail-sampling) knobs; defaults come from the
    # SMT_TRACE_* environment, so only pass these to override per worker
    ap.add_argument("--trace-sample-rate", type=float, default=None,
                    help="probability of keeping a fast, error-free trace")
    ap.add_argument("--trace-slow-ms", type=float, default=None,
                    help="latency above which a trace is always retained")
    # float-tolerant (a launcher passing 256.0 must not kill the worker at
    # argparse time); the Tracer constructor truncates to int
    ap.add_argument("--trace-capacity", type=float, default=None,
                    help="total traces kept in the ring")
    ap.add_argument("--generation", type=int, default=0,
                    help="initial pipeline generation (a fleet spawning a "
                         "worker after N rolling swaps passes N so "
                         "/healthz reports the truth)")
    args = ap.parse_args(argv)
    if (args.stage_path is None) == (args.models_json is None):
        ap.error("exactly one of stage_path or --models-json is required")

    import importlib

    from ..core.lazyimport import load_all

    for mod in args.import_module:
        # --import-module exists for registration side effects
        # (STAGE_REGISTRY); PEP 562 lazy packages defer those to attribute
        # access, so force-load their submodules here
        load_all(importlib.import_module(mod))

    from ..core.serialization import load_stage
    from ..observability import tracing
    from .serving import MicroBatchServingEngine, ServingServer
    from .serving_v2 import ContinuousServingEngine, MultiTenantServingEngine

    if (args.trace_sample_rate is not None or args.trace_slow_ms is not None
            or args.trace_capacity is not None):
        tracing.set_tracer(tracing.Tracer(
            capacity=args.trace_capacity,
            sample_rate=args.trace_sample_rate,
            latency_threshold_s=(args.trace_slow_ms / 1e3
                                 if args.trace_slow_ms is not None
                                 else None)))

    import json as _json

    from ..runtime.topology import open_requested_platform

    # a launcher that asked for an accelerator (JAX_PLATFORMS) gets a
    # worker that holds it or jax's own error on stderr — before the
    # address is announced, so the fleet never registers a worker that
    # would answer from a CPU it fell back to
    device = open_requested_platform()
    if device is not None:
        print(f"DEVICE {device.platform} {list(device.device_kinds)} "
              f"x{device.num_devices}", file=sys.stderr, flush=True)

    spec = _json.loads(args.models_json) if args.models_json else None
    if spec is not None:
        models = {m: load_stage(e["stage_path"])
                  for m, e in sorted(spec.items())}
    else:
        pipeline = load_stage(args.stage_path)
    server = ServingServer(args.host, args.port,
                           reply_timeout=args.reply_timeout)
    if spec is not None:
        # multi-tenant worker: one engine per model over ONE shared
        # server/queue, per-model generations in /healthz, and
        # /control/{load,unload,swap} keyed by model id
        engine = MultiTenantServingEngine(
            server, models, reply_col=args.reply_col,
            stage_paths={m: e["stage_path"] for m, e in spec.items()},
            generations={m: int(e.get("generation", 0))
                         for m, e in spec.items()}).start()
    elif args.mode == "continuous":
        engine = ContinuousServingEngine(
            server, pipeline, reply_col=args.reply_col,
            generation=args.generation).start()
    else:
        engine = MicroBatchServingEngine(
            server, pipeline, reply_col=args.reply_col,
            generation=args.generation).start()

    print(f"ADDRESS {server.address}", flush=True)
    try:
        threading.Event().wait()  # serve until killed
    except KeyboardInterrupt:
        pass
    finally:
        engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
