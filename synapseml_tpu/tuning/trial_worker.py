"""Trial worker subprocess: ``python -m synapseml_tpu.tuning.trial_worker``.

The process half of the tuning subsystem's process-pool executor, built in
the style of ``io/serving_worker``: argparse first, heavy imports after,
and the FIRST stdout line is the handshake. One worker serves many trial
segments over a line protocol on stdin/stdout:

    parent -> worker:  ``TASK {TrialTask json}``        start a segment
    worker -> parent:  ``RUNG {trial_id, iters, metric, t_s}``
    parent -> worker:  ``CONT`` | ``STOP``              the rung decision
    worker -> parent:  ``DONE {segment result + stats}`` | ``FAIL {error}``
    parent -> worker:  ``EXIT``                         clean shutdown

The study directory (``--study-dir``) carries everything heavy out of
band: the estimator template (``core.serialization`` stage dir), the
fitted ``BinMapper`` as JSON, and the raw/binned/label matrices as
``.npy`` files loaded ``mmap_mode="r"`` — the shared-binning design means
a worker never re-runs the binning pass, it just maps the study's binned
matrix into memory. ``SMT_FAULT_PLAN`` arrives via the environment; the
``DONE`` payload reports how many compiles this process has paid.

Jax-free at import: everything heavy loads inside :func:`main` after the
argument parse.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, Optional


def _worker_crash(rule) -> None:
    """A worker's injected crash is a real process death: ``wedge`` holds
    the pipe silent past the parent's deadline first, ``refuse`` dies
    immediately. Exit 23 marks an injected death in the worker log."""
    if rule.kind == "wedge":
        time.sleep((rule.delay_ms / 1e3) if rule.delay_ms else 3600.0)
    os._exit(23)


def _compile_stats() -> Dict[str, Any]:
    """This process's compile count, shipped home in ``DONE`` so the
    study can aggregate fleet-wide compile behavior."""
    from synapseml_tpu.observability.metrics import get_registry

    fam = get_registry().snapshot()["families"].get("smt_compile_seconds")
    series = fam["series"] if fam else []
    return {"compile_samples": sum(int(s.get("count", 0)) for s in series)}


def build_context(study_dir: str):
    """Rehydrate a :class:`~.executor.StudyContext` from the study dir."""
    import numpy as np

    from synapseml_tpu.core.serialization import load_stage
    from synapseml_tpu.core.table import Table
    from synapseml_tpu.gbdt.binning import BinMapper
    from synapseml_tpu.gbdt.dataset import GBDTDataset

    from .executor import StudyContext

    with open(os.path.join(study_dir, "meta.json"), encoding="utf-8") as f:
        meta = json.load(f)

    def _arr(name: str):
        return np.load(os.path.join(study_dir, name + ".npy"), mmap_mode="r")

    x, binned, y = _arr("x"), _arr("binned"), _arr("y")
    with open(os.path.join(study_dir, "mapper.json"), encoding="utf-8") as f:
        mapper = BinMapper.from_dict(json.load(f))
    dataset = GBDTDataset.from_binned(
        binned, mapper, x=x, label=y,
        feature_names=meta.get("feature_names"))
    eval_set = [(np.asarray(_arr("x_val")), np.asarray(_arr("y_val")))]
    template = load_stage(os.path.join(study_dir, "template"))

    # the estimator's tuned fit path reads ONLY label (and weight) from the
    # table; a 1-wide zero vector satisfies the features-column schema
    cols: Dict[str, Any] = {
        meta["features_col"]: np.zeros((len(y), 1), np.float32),
        meta["label_col"]: np.asarray(y, dtype=np.float64),
    }
    if meta.get("weight_col"):
        cols[meta["weight_col"]] = np.asarray(_arr("w"), dtype=np.float64)
    table = Table(cols)
    return StudyContext(template, dataset, table, eval_set,
                        metric=meta["metric"], rungs=meta["rungs"],
                        model_dir=meta["model_dir"])


def _readline() -> str:
    line = sys.stdin.readline()
    if not line:  # parent closed the pipe: nothing left to serve
        raise SystemExit(0)
    return line.strip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="synapseml_tpu.tuning.trial_worker")
    ap.add_argument("--study-dir", required=True,
                    help="study directory written by tuning.study")
    args = ap.parse_args(argv)

    from synapseml_tpu.runtime.topology import open_requested_platform

    # same rule as io/serving_worker: open the accelerator the launcher
    # asked for before READY, or die with jax's error in the worker log
    open_requested_platform()
    ctx = build_context(args.study_dir)

    from .executor import TrialError, TrialTask, run_trial_segment

    print("READY " + json.dumps({"pid": os.getpid()}), flush=True)
    while True:
        line = _readline()
        if not line:
            continue
        if line == "EXIT":
            return 0
        if not line.startswith("TASK "):
            continue
        task = TrialTask.from_json(json.loads(line[5:]))

        def on_rung(trial_id: int, iters: int, metric: Optional[float],
                    t_s: float) -> str:
            print("RUNG " + json.dumps(
                {"trial_id": trial_id, "iters": iters, "metric": metric,
                 "t_s": t_s}), flush=True)
            reply = _readline()
            return "stop" if reply == "STOP" else "cont"

        try:
            result = run_trial_segment(ctx, task, on_rung,
                                       crash=_worker_crash)
        except TrialError as e:
            print("FAIL " + json.dumps({"error": str(e)}), flush=True)
            continue
        except Exception as e:  # anything else is equally terminal for
            # the segment, but the worker itself stays serviceable
            print("FAIL " + json.dumps(
                {"error": f"{type(e).__name__}: {e}"}), flush=True)
            continue
        result["stats"] = _compile_stats()
        print("DONE " + json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
