#!/usr/bin/env python3
"""The mesh paths on real devices — ``python tools/chip_mesh_smoke.py`` on a
host with four TPU chips (one process drives all four).

``chip_smoke.py`` proves the one-chip paths; this is its four-chip sibling
for the claims only a mesh of real devices can check:

- ``gbdt_mesh``: ``LightGBMClassifier(mesh=SpecLayout.build(data=4)).fit`` at
  BASELINE config #2's size grows trees BIT-identical to the one-chip fit
  (the pre-rounded histogram substrate), and every chip holds data;
- ``onnx_placement``: where ``ONNXModel`` puts a model without a layout and
  under ``SpecLayout.build(data=4)`` — reported, not judged (a data-parallel
  ``ONNXModel`` is a feature the repo does not have);
- ``onnx_tp``: ``ONNXModel(sharding_layout=SpecLayout.build(model=4))`` on
  ResNet-50 answers like the unsharded model, its sharded weights sit on four
  distinct devices (``addressable_shards``) and all four chips hold bytes
  (``memory_stats``);
- ``dryrun``: ``__graft_entry__.dryrun_multichip(4)`` natively, flash kernel
  compiled by Mosaic inside the Ulysses wrapper.

Fails when jax finds fewer than four accelerator devices.
``--rehearse-on-cpu`` runs the first three at toy sizes on four virtual CPU
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``); its result
line says so. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": 4}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chip_smoke import SIZES, smoke_rows  # noqa: E402  (same sizes, same rows)


def _bytes_in_use(devices):
    """Per-device live bytes, or None where the backend keeps no count
    (the CPU)."""
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def _report(name, t0, **fields) -> bool:
    ok = fields.pop("ok")
    print("CHECK " + json.dumps(dict(
        check=name, ok=bool(ok), seconds=round(time.perf_counter() - t0, 1),
        **fields)), flush=True)
    return bool(ok)


def check_gbdt_mesh(size, devices, on_chip):
    from synapseml_tpu import Table
    from synapseml_tpu.gbdt import LightGBMClassifier
    from synapseml_tpu.runtime.layout import SpecLayout

    t0 = time.perf_counter()
    g = size["gbdt"]
    x, y = smoke_rows(g)
    table = Table({"features": x, "label": y})
    kw = dict(num_iterations=g["num_iterations"], num_leaves=g["num_leaves"],
              max_bin=g["max_bin"])
    layout = SpecLayout.build(data=4, devices=devices[:4])
    t1 = time.perf_counter()
    meshed = LightGBMClassifier(mesh=layout, **kw).fit(table).booster
    mesh_s = time.perf_counter() - t1
    held = _bytes_in_use(devices[:4])
    t1 = time.perf_counter()
    single = LightGBMClassifier(**kw).fit(table).booster
    single_s = time.perf_counter() - t1
    same = {f: bool(np.array_equal(np.asarray(getattr(meshed, f)),
                                   np.asarray(getattr(single, f))))
            for f in ("parent", "feature", "bin", "leaf_value")}
    return _report(
        "gbdt_mesh", t0, mesh=layout.describe(), trees=int(meshed.num_trees),
        identical=same, mesh_fit_s=round(mesh_s, 2),
        one_chip_fit_s=round(single_s, 2), bytes_in_use_after_mesh_fit=held,
        ok=all(same.values())
        and (not on_chip or all(b for b in held)))


def check_onnx(size, devices, on_chip):
    """``onnx_placement`` (reported) and ``onnx_tp`` (judged) share the
    model, the rows and the unsharded logits."""
    from synapseml_tpu import Table
    from synapseml_tpu.models.zoo import build_model_bytes
    from synapseml_tpu.onnx import ONNXModel
    from synapseml_tpu.runtime.layout import SpecLayout

    t0 = time.perf_counter()
    model_bytes = build_model_bytes(size["cnn"], **size["cnn_kw"])
    rows = np.random.default_rng(5).normal(
        size=(size["cnn_batch"], 3, size["image"], size["image"])
    ).astype(np.float32)
    table = Table({"x": rows})

    def run(layout):
        model = ONNXModel(model_bytes=model_bytes, feed_dict={"data": "x"},
                          fetch_dict={"logits": "logits"},
                          batch_size=size["cnn_batch"],
                          dtype_policy="bfloat16",
                          sharding_layout=layout)
        before = _bytes_in_use(devices[:4])
        logits = np.asarray(model.transform(table)["logits"])
        after = _bytes_in_use(devices[:4])
        grew = [None if a is None else a - b for a, b in zip(after, before)]
        return model, logits, grew

    _, plain, grew_plain = run(None)
    _, dp, grew_dp = run(SpecLayout.build(data=4, devices=devices[:4]))
    ok_place = _report(
        "onnx_placement", t0,
        bytes_grown_without_layout=grew_plain,
        bytes_grown_under_data4_layout=grew_dp,
        data4_logits_equal_plain=bool(np.array_equal(plain, dp)),
        note="without a layout, and under data=4, ONNXModel computes on "
             "device 0 alone; a data-parallel ONNXModel is not implemented",
        ok=True)

    t0 = time.perf_counter()
    tp_layout = SpecLayout.build(model=4, devices=devices[:4])
    model, tp, grew_tp = run(tp_layout)
    fn = model.fn
    shard_devices = sorted({s.device.id
                            for name in fn._const_specs
                            for s in fn.constants[name].addressable_shards})
    rel = float(np.abs(tp - plain).max() / max(np.abs(plain).max(), 1e-6))
    ok_tp = _report(
        "onnx_tp", t0, mesh=tp_layout.describe(),
        sharded_weights=len(fn._const_specs),
        weight_shard_devices=shard_devices, rel_err_vs_unsharded=rel,
        bytes_in_use=_bytes_in_use(devices[:4]), bytes_grown=grew_tp,
        left_profiled_path=bool(fn._jit._left_profiled_path),
        ok=rel < 1e-2 and len(shard_devices) == 4
        and not fn._jit._left_profiled_path
        and (not on_chip or all(b for b in _bytes_in_use(devices[:4]))))
    return ok_place and ok_tp


def check_dryrun(size, devices, on_chip):
    import __graft_entry__ as g
    from synapseml_tpu.native import get_lib

    t0 = time.perf_counter()
    g.dryrun_multichip(4)
    return _report("dryrun", t0, native_hashing=(
        "built from src/hash.cpp" if get_lib() is not None
        else "numpy fallback"), ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy sizes on four virtual CPU devices; the result "
                         "line says it is a rehearsal")
    args = ap.parse_args(argv)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    import jax

    devices = jax.devices()
    on_chip = devices[0].platform != "cpu"
    if len(devices) < 4 or on_chip == args.rehearse_on_cpu:
        print(f"chip_mesh_smoke: needs four accelerator devices (or "
              f"--rehearse-on-cpu), found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 1
    size = SIZES["rehearsal" if args.rehearse_on_cpu else "chip"]
    checks = [check_gbdt_mesh, check_onnx]
    if on_chip:  # tier-1 already runs the dryrun on virtual devices
        checks.append(check_dryrun)
    ok = True
    for check in checks:
        try:
            ok &= check(size, devices, on_chip)
        except Exception:
            traceback.print_exc()
            ok = False
    if not ok:
        print("chip_mesh_smoke: FAILED", file=sys.stderr)
        return 1
    result = {"ok": True, "device": {"platform": devices[0].platform,
                                     "kind": devices[0].device_kind,
                                     "count": len(devices)}}
    if args.rehearse_on_cpu:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
