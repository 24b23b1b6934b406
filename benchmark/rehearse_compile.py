"""Compile a cell's one shape for a described v5e chip, with no chip
attached, and print the bytes the compiler reckons (the third rehearsal of
the ``on-chip-measurement`` guide). Nothing runs; the figure goes into
``PERF.md`` beside the ``hbm_peak_gib`` the chip measured.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py --workload <cell>
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from run import load_json  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    args = p.parse_args(argv)
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.traffic import size_of
    from synapseml_tpu.models.zoo import build_model_bytes
    from synapseml_tpu.onnx.importer import OnnxFunction

    jax.config.update("jax_enable_compilation_cache", False)
    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    traffic = cell["traffic"]
    fn = OnnxFunction(build_model_bytes(config["builder"],
                                        **config["builder_kwargs"]),
                      dtype_policy=config["policy"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct(
        (traffic["bucket"],) + tuple(size_of(t, config, traffic.get("dims", {}))
                                     for t in config["feed"][name]["shape"]),
        np.dtype(config["feed"][name]["dtype"]), sharding=one_chip)
        for name in fn.input_names]
    compiled = jax.jit(fn._run_positional).lower(*shapes).compile()
    m = compiled.memory_analysis()
    parts = {k: int(getattr(m, k + "_size_in_bytes")) for k in
             ("argument", "output", "temp", "generated_code", "alias")}
    total = sum(parts[k] for k in ("argument", "output", "temp",
                                   "generated_code"))
    print(json.dumps({"workload": args.workload, "bucket": traffic["bucket"],
                      "bytes": parts, "program_bytes": total,
                      "program_gib": total / 2 ** 30}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
