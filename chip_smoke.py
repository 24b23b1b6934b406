#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                    on a host with one TPU chip
    python chip_smoke.py --rehearse-on-cpu  same code at toy sizes on the CPU

Drives the two paths ROADMAP.md names as the product, through the entry
points a user calls, at the full width of configurations the repo has, with
weights and data made from seeds:

- ``serving``: this jax-free process saves a pipeline around
  ``ONNXModel(ResNet-50, bf16, batch_size=128)`` and starts
  ``ProcessServingFleet(n_workers=1)``; a few POSTs of 224x224 images go
  through the ``RoutingServer`` door to the one worker process, which owns
  the chip and says so in every reply;
- then, in ONE process that owns the chip after the fleet has stopped:
  ``training`` (``LightGBMClassifier.fit`` at BASELINE config #2's size, then
  ``transform``), ``onnx_resnet50`` and ``onnx_bert`` (``ONNXModel.transform``
  on a ``Table``, checked against the float32 policy of the same graph and,
  for ResNet-50, against what the fleet answered), ``sparse`` (a hashed-text
  sparse ``fit``) and ``flash`` (the Pallas kernel compiled by Mosaic at
  both block classes, with and without GQA, against ``dense_attention``).

This process never imports jax: a process that has touched jax holds the
chip, and a child that needs it then fails. The children run one after
another with ``JAX_PLATFORMS=tpu``, so a chip that cannot be opened is an
error in the child and not a quiet CPU run. Every phase prints the device it
ran on, the versions, and the cold and warm wall time of each compile; a
phase that fails, reports another device than ``tpu``, or a ``device_kind``
missing from ``PEAK_BF16_FLOPS`` fails the run. The last line of stdout is
the result, and it is printed only when every phase passed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse-on-cpu`` waives the chip: toy sizes, ``JAX_PLATFORMS=cpu``, the
kernel through the Pallas interpreter. Its last line carries
``"rehearsal": true`` and the CPU device, so it cannot pass for a chip run
(``tests/test_chip_smoke.py`` runs it in tier-1).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

from synapseml_tpu import PipelineModel, Table
from synapseml_tpu.core import Param, Transformer

REPO = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1100.0  # the contract allows 1200 s, compilation included

# BASELINE configs #1-#3 and the two block classes _pick_blocks can return;
# the rehearsal runs the same code where a CPU can finish it in seconds
SIZES = {
    "chip": dict(
        cnn="ResNet50", cnn_kw={}, classes=1000, image=224, cnn_batch=128,
        cnn_rows=136,  # one full bucket and one padded one
        bert="BERTBase", vocab=30522, seq=128, bert_batch=64, bert_rows=72,
        gbdt=dict(rows=32561, cols=14, num_iterations=100, num_leaves=31,
                  max_bin=255),
        sparse_rows=500,
        # (name, B, S, H, H_kv, blocks _pick_blocks must return)
        flash=[("mha_1024", 1, 2048, 8, 8, (1024, 1024)),
               ("gqa_1024", 1, 2048, 8, 2, (1024, 1024)),
               ("mha_2048", 1, 16384, 8, 8, (2048, 1024)),
               ("gqa_2048", 1, 16384, 8, 2, (2048, 1024))],
        flash_interpret=False,  # compiled by Mosaic, for real
        ref_tol=0.05),
    "rehearsal": dict(
        cnn="ResNet18", cnn_kw={"num_classes": 10}, classes=10, image=32,
        cnn_batch=4, cnn_rows=6,
        bert="BERTTiny", vocab=1000, seq=16, bert_batch=4, bert_rows=6,
        gbdt=dict(rows=2000, cols=14, num_iterations=5, num_leaves=7,
                  max_bin=63),
        sparse_rows=200,
        flash=[("mha", 1, 256, 2, 2, (256, 256)),
               ("gqa", 1, 256, 4, 2, (256, 256))],
        flash_interpret=True,  # Mosaic compiles for a TPU and nothing else
        ref_tol=0.05),
}
SERVING_POSTS = 5  # the first is cold, the rest are warm


def smoke_image(i: int, side: int) -> np.ndarray:
    """Image ``i`` of the smoke's data set: the serving client and the
    in-process phase make the same rows from the same seeds."""
    return np.random.default_rng(1000 + i).normal(
        size=(3, side, side)).astype(np.float32)


def smoke_rows(g: dict):
    """The training set at ``g``'s size: float32-representable values, like
    the Adult columns themselves (small integers and category codes) — the
    data decide the binning path, and the phase prints which one ran."""
    rng = np.random.default_rng(2)
    x = rng.normal(size=(g["rows"], g["cols"])).astype(
        np.float32).astype(np.float64)
    y = (x[:, 0] + 0.5 * x[:, 3] - 0.3 * x[:, 7]
         + 0.2 * rng.normal(size=g["rows"]) > 0).astype(np.float64)
    return x, y


# -- the stages the serving worker imports (--import-module chip_smoke) -----

class SmokeDecodeImages(Transformer):
    """Request bodies (raw float32, C x H x W) -> a ``data`` tensor column."""

    side = Param("image height and width", int, default=224)

    def _transform(self, table: Table) -> Table:
        shape = (3, self.side, self.side)
        data = np.stack([np.frombuffer(r.entity, np.float32).reshape(shape)
                         for r in table["request"]])
        return table.with_column("data", data)


def _left_profiled_path(prefix: str = "") -> list:
    """Names of the profiled jits that left the profiled path in this
    process: ``ProfiledJit`` counts each once in
    ``smt_profiled_jit_fallback_total{fn,why}``."""
    from synapseml_tpu.observability.metrics import get_registry

    fam = get_registry().snapshot()["families"].get(
        "smt_profiled_jit_fallback_total") or {}
    return sorted({s["labels"][0] for s in fam.get("series", [])
                   if s["value"] and s["labels"][0].startswith(prefix)})


class SmokeLogitsReply(Transformer):
    """``logits`` -> one JSON reply per request, stamped with the device
    THIS process computes on and whether its profiled jits are still on the
    profiled path."""

    def _transform(self, table: Table) -> Table:
        import jax

        dev = jax.devices()[0]
        stamp = {"platform": dev.platform, "device_kind": dev.device_kind,
                 "device_count": len(jax.devices()), "pid": os.getpid(),
                 "left_profiled_path": _left_profiled_path()}
        replies = np.empty(table.num_rows, dtype=object)
        replies[:] = [dict(stamp, logits=row.tolist())
                      for row in np.asarray(table["logits"])]
        return table.with_column("reply", replies)


# -- what every phase reports ------------------------------------------------

def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def _device_stamp() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _compile_account(families: dict, prefix: str) -> dict:
    """ProfiledJit's compile accounting in a metrics snapshot, for entry
    points whose name starts with ``prefix``: how many compiles, their
    seconds, and for which backend."""
    fam = families.get("smt_compile_seconds") or {}
    out = {"compiles": 0, "compile_s": 0.0, "backends": []}
    for s in fam.get("series", []):
        labels = dict(zip(fam["labelnames"], s["labels"]))
        if labels["fn"].startswith(prefix):
            out["compiles"] += int(s["count"])
            out["compile_s"] = round(out["compile_s"] + float(s["sum"]), 2)
            out["backends"] = sorted({*out["backends"], labels["backend"]})
    return out


def _profiled(prefix: str) -> dict:
    """This process's compile accounting for ``prefix``, and which of those
    wrappers left the profiled path (must be none)."""
    from synapseml_tpu.observability.metrics import get_registry

    out = _compile_account(get_registry().snapshot()["families"], prefix)
    out["left_profiled_path"] = _left_profiled_path(prefix)
    return out


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 3)


def _rel_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6))


def _check(checks: dict, name: str, ok: bool, value=None) -> None:
    checks[name] = {"ok": bool(ok), "value": value}


def _emit(phase: str, t0: float, device: dict, compiles: list, checks: dict,
          error: str = None, **extra) -> bool:
    ok = error is None and all(c["ok"] for c in checks.values())
    print("PHASE " + json.dumps(dict(
        phase=phase, ok=ok, **device, versions=_versions(),
        compiles=compiles, checks=checks, error=error,
        seconds=round(time.perf_counter() - t0, 1), **extra)), flush=True)
    return ok


# -- serving: a jax-free parent, one worker process that owns the chip -------

def phase_serving(size: dict, workdir: str) -> bool:
    import urllib.request

    from synapseml_tpu.io.serving_v2 import ProcessServingFleet
    from synapseml_tpu.models.zoo import build_model_bytes
    from synapseml_tpu.onnx import ONNXModel

    t0 = time.perf_counter()
    checks, compiles, device, error = {}, [], {}, None
    pipeline = PipelineModel([
        SmokeDecodeImages(side=size["image"]),
        ONNXModel(model_bytes=build_model_bytes(size["cnn"], **size["cnn_kw"]),
                  feed_dict={"data": "data"}, fetch_dict={"logits": "logits"},
                  batch_size=size["cnn_batch"], dtype_policy="bfloat16"),
        SmokeLogitsReply()])
    fleet = None
    try:
        # the fleet's own timeouts carry the cold compile: the first reply
        # waits for it, and so does the router's forward
        fleet, start_s = _timed(lambda: ProcessServingFleet(
            pipeline, n_workers=1, import_modules=["chip_smoke"],
            reply_timeout=600.0, startup_timeout=300.0))
        replies, walls = [], []
        for i in [0, 0] + list(range(1, SERVING_POSTS - 1)):
            req = urllib.request.Request(
                fleet.address + "/", method="POST",
                data=smoke_image(i, size["image"]).tobytes(),
                headers={"Content-Type": "application/octet-stream"})
            t1 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=700) as r:
                status, body = r.status, json.loads(r.read())
            walls.append(round(time.perf_counter() - t1, 3))
            replies.append((i, status, body))
        profiled = _compile_account(
            fleet.metrics_snapshot()["families"], "onnx.")
        last = replies[-1][2]
        device = {k: last[k] for k in ("platform", "device_kind",
                                       "device_count")}
        compiles.append({"what": f"first reply, {size['cnn']} batch 1 "
                                 f"(one request per batch)",
                         "cold_s": walls[0], "warm_s": min(walls[1:]),
                         "profiled": profiled})
        logits = [np.asarray(b["logits"], np.float32) for _, _, b in replies]
        _check(checks, "all_200", all(s == 200 for _, s, _ in replies),
               [s for _, s, _ in replies])
        _check(checks, "logits_shape",
               all(v.shape == (size["classes"],) for v in logits),
               list(logits[0].shape))
        _check(checks, "logits_finite",
               all(np.isfinite(v).all() for v in logits))
        _check(checks, "same_image_same_logits",
               bool(np.array_equal(logits[0], logits[1])))
        _check(checks, "other_image_other_logits",
               not np.array_equal(logits[0], logits[2]))
        _check(checks, "one_worker_process_answered",
               len({b["pid"] for _, _, b in replies}) == 1
               and last["pid"] != os.getpid(), last["pid"])
        _check(checks, "compiles_on_the_profiled_path",
               profiled["compiles"] >= 1
               and not last["left_profiled_path"],
               last["left_profiled_path"])
        _check(checks, "compiled_for_the_reported_platform",
               profiled["backends"] == [device["platform"]],
               profiled["backends"])
        np.save(os.path.join(workdir, "serving_logits.npy"),
                np.stack(logits[1:]))  # images 0 .. SERVING_POSTS-2
    except Exception:
        error = traceback.format_exc()[-3000:]
    finally:
        if fleet is not None:
            fleet.stop()
    _check(checks, "this_process_never_imported_jax",
           "jax" not in sys.modules)
    return _emit("serving", t0, device, compiles, checks, error,
                 fleet_start_s=start_s if fleet is not None else None)


# -- the phases that share one chip-owning process ---------------------------

def phase_training(size: dict, workdir: str) -> bool:
    from synapseml_tpu.core import telemetry
    from synapseml_tpu.gbdt import LightGBMClassifier

    t0 = time.perf_counter()
    checks, compiles, error = {}, [], None
    g = size["gbdt"]
    try:
        x, y = smoke_rows(g)
        table = Table({"features": x, "label": y})
        est = LightGBMClassifier(num_iterations=g["num_iterations"],
                                 num_leaves=g["num_leaves"],
                                 max_bin=g["max_bin"])
        telemetry.clear_events()
        model, cold = _timed(lambda: est.fit(table))
        binning = [e["path"] for e in telemetry.recent_events()
                   if e.get("className") == "gbdt"
                   and e.get("method") == "binning"]
        _, warm = _timed(lambda: est.fit(table))
        compiles.append({"what": f"LightGBMClassifier.fit {g['rows']}x"
                                 f"{g['cols']}, {g['num_iterations']} "
                                 f"iterations, {g['num_leaves']} leaves",
                         "cold_s": cold, "warm_s": warm,
                         "profiled": _profiled("gbdt.")})
        out, t_cold = _timed(lambda: model.transform(table))
        _, t_warm = _timed(lambda: model.transform(table))
        compiles.append({"what": f"transform {g['rows']} rows",
                         "cold_s": t_cold, "warm_s": t_warm})
        prob = np.asarray(out["probability"])
        acc = float((np.asarray(out["prediction"]) == y).mean())
        booster = model.booster
        head = x[:512]
        host_dev = float(np.abs(
            booster.raw_predict(head, backend="host")
            - booster.raw_predict(head, backend="device")).max())
        _check(checks, "probability_shape", prob.shape == (g["rows"], 2),
               list(prob.shape))
        _check(checks, "probability_in_unit_interval",
               bool(np.isfinite(prob).all() and (prob >= 0).all()
                    and (prob <= 1).all()))
        _check(checks, "train_accuracy", acc >= 0.85, round(acc, 4))
        _check(checks, "device_scores_match_host_numpy_reference",
               host_dev < 1e-4, host_dev)
        _check(checks, "trees_grown",
               int(booster.num_trees) == g["num_iterations"],
               int(booster.num_trees))
        _check(checks, "binning_path_reported", len(binning) == 1, binning)
        _check(checks, "compiles_on_the_profiled_path",
               compiles[0]["profiled"]["compiles"] >= 1
               and not compiles[0]["profiled"]["left_profiled_path"],
               compiles[0]["profiled"]["left_profiled_path"])
        print(f"training: binning path = {binning}", flush=True)
    except Exception:
        error = traceback.format_exc()[-3000:]
    return _emit("training", t0, _device_stamp(), compiles, checks, error)


def _onnx_phase(phase: str, size: dict, model_name: str, model_kw: dict,
                feeds: np.ndarray, feed: str, batch: int, out_shape: tuple,
                workdir: str, served: bool) -> bool:
    """``ONNXModel.transform`` on a Table at the serving shape (bf16 policy),
    against the float32 policy of the same graph on the first 8 rows."""
    import jax

    from synapseml_tpu.models.zoo import build_model_bytes
    from synapseml_tpu.onnx import ONNXModel

    t0 = time.perf_counter()
    checks, compiles, error = {}, [], None
    try:
        model_bytes = build_model_bytes(model_name, **model_kw)

        def onnx_model(policy, bs):
            return ONNXModel(model_bytes=model_bytes, feed_dict={feed: "x"},
                             fetch_dict={"logits": "logits"}, batch_size=bs,
                             dtype_policy=policy)

        model = onnx_model("bfloat16", batch)
        table = Table({"x": feeds})
        out, cold = _timed(lambda: model.transform(table))
        _, warm = _timed(lambda: model.transform(table))
        logits = np.asarray(out["logits"])
        compiles.append({"what": f"ONNXModel.transform {model_name} bf16, "
                                 f"{len(feeds)} rows in buckets of {batch}",
                         "cold_s": cold, "warm_s": warm,
                         "profiled": _profiled(model.fn._jit.name)})
        head = min(8, len(feeds))
        with jax.default_matmul_precision("highest"):
            ref, ref_s = _timed(lambda: np.asarray(onnx_model(
                "float32", head).transform(Table({"x": feeds[:head]}))
                ["logits"]))
        compiles.append({"what": f"float32 reference, {head} rows",
                         "cold_s": ref_s, "warm_s": None})
        err = _rel_err(logits[:head], ref)
        _check(checks, "logits_shape",
               logits.shape == (len(feeds),) + out_shape, list(logits.shape))
        _check(checks, "logits_finite", bool(np.isfinite(logits).all()))
        _check(checks, "bf16_agrees_with_float32_reference",
               err < size["ref_tol"], round(err, 5))
        _check(checks, "one_compile_for_every_bucket",
               compiles[0]["profiled"]["compiles"] == 1
               and not model.fn._jit._left_profiled_path,
               compiles[0]["profiled"])
        if served:
            path = os.path.join(workdir, "serving_logits.npy")
            if os.path.exists(path):
                got = np.load(path)
                _check(checks, "agrees_with_what_the_fleet_answered",
                       _rel_err(got, logits[:len(got)]) < size["ref_tol"],
                       round(_rel_err(got, logits[:len(got)]), 5))
    except Exception:
        error = traceback.format_exc()[-3000:]
    return _emit(phase, t0, _device_stamp(), compiles, checks, error)


def phase_onnx_resnet50(size: dict, workdir: str) -> bool:
    feeds = np.stack([smoke_image(i, size["image"])
                      for i in range(size["cnn_rows"])])
    return _onnx_phase("onnx_resnet50", size, size["cnn"], size["cnn_kw"],
                       feeds, "data", size["cnn_batch"],
                       (size["classes"],), workdir, served=True)


def phase_onnx_bert(size: dict, workdir: str) -> bool:
    ids = np.random.default_rng(1).integers(
        0, size["vocab"], size=(size["bert_rows"], size["seq"])
    ).astype(np.int64)  # what a tokenizer hands over
    return _onnx_phase("onnx_bert", size, size["bert"], {}, ids, "input_ids",
                       size["bert_batch"], (2,), workdir, served=False)


def phase_sparse(size: dict, workdir: str) -> bool:
    """Hashed text -> CSR -> sparse GBDT (notebook 11's pipeline, cut to a
    few hundred rows): on a TPU the sparse histogram's prefix sums take the
    triangular-matmul form no CPU test reaches (``gbdt/sparse.py``)."""
    from synapseml_tpu import Pipeline
    from synapseml_tpu.gbdt import LightGBMClassifier
    from synapseml_tpu.native import get_lib
    from synapseml_tpu.vw.featurizer import VowpalWabbitFeaturizer

    t0 = time.perf_counter()
    checks, compiles, error, native = {}, [], None, None
    try:
        rng = np.random.default_rng(0)
        words = {1: ["great", "excellent", "wonderful", "superb"],
                 0: ["awful", "terrible", "poor", "dreadful"]}
        filler = [f"word{i}" for i in range(300)]
        texts, labels = [], []
        for _ in range(size["sparse_rows"]):
            y = int(rng.random() < 0.5)
            row = list(rng.choice(words[y], size=2)) + \
                list(rng.choice(filler, size=8))
            rng.shuffle(row)
            texts.append(" ".join(row))
            labels.append(float(y))
        table = Table({"text": np.array(texts, object),
                       "label": np.array(labels)})
        pipe = Pipeline(stages=[
            VowpalWabbitFeaturizer(input_cols=["text"],
                                   string_split_cols=["text"]),
            LightGBMClassifier(num_iterations=10, num_leaves=7,
                               min_data_in_leaf=5, sparse_num_bits=14)])
        model, cold = _timed(lambda: pipe.fit(table))
        _, warm = _timed(lambda: pipe.fit(table))
        native = "built from src/hash.cpp" if get_lib() is not None \
            else "numpy fallback"
        compiles.append({"what": f"hashed-sparse fit, "
                                 f"{size['sparse_rows']} rows x 2^14",
                         "cold_s": cold, "warm_s": warm})
        prob = np.asarray(model.transform(table)["probability"])[:, 1]
        acc = float(((prob > 0.5) == (np.array(labels) > 0.5)).mean())
        _check(checks, "probability_finite", bool(np.isfinite(prob).all()))
        _check(checks, "train_accuracy", acc >= 0.9, round(acc, 4))
        print(f"sparse: murmur hashing = {native}", flush=True)
    except Exception:
        error = traceback.format_exc()[-3000:]
    return _emit("sparse", t0, _device_stamp(), compiles, checks, error,
                 native_hashing=native)


def phase_flash(size: dict, workdir: str) -> bool:
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.core import telemetry
    from synapseml_tpu.parallel.flash import (_pick_blocks, dense_attention,
                                              flash_attention)

    t0 = time.perf_counter()
    checks, compiles, error = {}, [], None
    interpret = size["flash_interpret"]
    try:
        rng = np.random.default_rng(9)
        telemetry.clear_events()
        for name, b, s, h, h_kv, want_blocks in size["flash"]:
            def mk(heads):
                return jnp.asarray(rng.normal(size=(b, s, heads, 64)).astype(
                    np.float32)).astype(jnp.bfloat16)

            q, k, v = mk(h), mk(h_kv), mk(h_kv)
            blocks = _pick_blocks(b * h, s, s)
            run = lambda: jax.block_until_ready(flash_attention(
                q, k, v, causal=True, interpret=interpret))
            out, cold = _timed(run)
            _, warm = _timed(run)
            compiles.append({"what": f"flash {name} B={b} S={s} H={h} "
                                     f"H_kv={h_kv} D=64 blocks={blocks} "
                                     f"interpret={interpret}",
                             "cold_s": cold, "warm_s": warm})
            # dense reference on the first and the last query window: the
            # full (S, S) f32 score tensor at S=16k does not fit the chip
            ke, ve = (jnp.repeat(t, h // h_kv, axis=2) for t in (k, v))
            w = min(s, 1024)
            got = np.asarray(out.astype(jnp.float32))
            err = max(
                float(np.abs(got[:, :w] - np.asarray(dense_attention(
                    q[:, :w], ke[:, :w], ve[:, :w], causal=True
                ).astype(jnp.float32))).max()),
                float(np.abs(got[:, -w:] - np.asarray(dense_attention(
                    q[:, -w:], ke, ve, causal=True
                ).astype(jnp.float32))).max()))
            _check(checks, f"{name}_block_class", blocks == want_blocks,
                   list(blocks))
            _check(checks, f"{name}_matches_dense_attention",
                   bool(np.isfinite(got).all()) and err < 4e-2, err)
        prof = _profiled("flash.")
        compiles.append({"what": "all of the above", "cold_s": None,
                         "warm_s": None, "profiled": prof})
        _check(checks, "kernel_ran_not_a_dense_substitute",
               not [e for e in telemetry.recent_events()
                    if e.get("method") == "dense_substitute"])
        _check(checks, "compiles_on_the_profiled_path",
               prof["compiles"] == len(size["flash"])
               and not prof["left_profiled_path"], prof)
    except Exception:
        error = traceback.format_exc()[-3000:]
    return _emit("flash", t0, _device_stamp(), compiles, checks, error)


IN_PROCESS = [phase_training, phase_onnx_resnet50, phase_onnx_bert,
              phase_sparse, phase_flash]


def run_child(name: str, size: dict, workdir: str) -> int:
    """``--child serving`` or ``--child inprocess``: 0 when every phase it
    ran passed."""
    if name == "serving":
        return 0 if phase_serving(size, workdir) else 1
    ok = [phase(size, workdir) for phase in IN_PROCESS]
    return 0 if all(ok) else 1


# -- the parent: jax-free, one child at a time -------------------------------

def _cache_size(path: str):
    n = total = 0
    for root, _, files in os.walk(path):
        for f in files:
            n += 1
            total += os.path.getsize(os.path.join(root, f))
    return n, total


def _run_child(name: str, args, env: dict, workdir: str, deadline: float):
    """Run one child to its end (or to the deadline), passing its output
    through; returns (exit code, the PHASE reports it printed)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", name,
           "--workdir", workdir]
    if args.rehearse_on_cpu:
        cmd.append("--rehearse-on-cpu")
    proc = subprocess.Popen(cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    reports = []

    def pump():
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.startswith("PHASE "):
                reports.append(json.loads(line[6:]))

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    try:
        rc = proc.wait(timeout=max(deadline - time.monotonic(), 1.0))
    except subprocess.TimeoutExpired:
        rc = None
    finally:
        # the child's whole process group: a fleet worker it started must
        # not outlive it, whatever happened
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    reader.join(timeout=10)
    return rc, reports


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="waive the chip: toy sizes on the CPU; the result "
                         "line says it is a rehearsal")
    ap.add_argument("--child", choices=["serving", "inprocess"],
                    help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    size = SIZES["rehearsal" if args.rehearse_on_cpu else "chip"]
    if args.child:
        return run_child(args.child, size, args.workdir)

    from synapseml_tpu.observability.profiling import PEAK_BF16_FLOPS
    from synapseml_tpu.runtime.compile_cache import place_compile_cache

    t0 = time.monotonic()
    want = "cpu" if args.rehearse_on_cpu else "tpu"
    env = dict(os.environ, JAX_PLATFORMS=want)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    cache = place_compile_cache()  # children inherit it from the environment
    before = _cache_size(cache)
    print(f"chip_smoke: JAX_PLATFORMS={want} for every child; compile cache "
          f"{cache} holds {before[0]} files, {before[1] / 2**20:.1f} MiB",
          flush=True)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    failures, reports = [], []
    try:
        for name in ("serving", "inprocess"):
            rc, got = _run_child(name, args, env, workdir, t0 + BUDGET_S)
            reports += got
            if rc != 0:
                failures.append(
                    f"child {name!r} " + ("ran out of time" if rc is None
                                          else f"exited with code {rc}"))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    expected = ["serving"] + [p.__name__[len("phase_"):] for p in IN_PROCESS]
    seen = [r["phase"] for r in reports]
    if seen != expected:
        failures.append(f"phases reported {seen}, expected {expected}")
    devices = set()
    for r in reports:
        for name, c in r["checks"].items():
            if not c["ok"]:
                failures.append(f"{r['phase']}: check {name} failed "
                                f"(value {c['value']!r})")
        if r["error"]:
            failures.append(f"{r['phase']}: {r['error']}")
        if r.get("platform") != want:
            failures.append(f"{r['phase']}: ran on platform "
                            f"{r.get('platform')!r}, not {want!r}")
        devices.add((r.get("platform"), r.get("device_kind"),
                     r.get("device_count")))
    if len(devices) > 1:
        failures.append(f"phases disagree on the device: "
                        f"{sorted(devices, key=repr)}")
    if not args.rehearse_on_cpu:
        for _, kind, _ in devices:
            flat = (kind or "").lower().replace(" ", "")
            if not any(k in flat for k in PEAK_BF16_FLOPS):
                failures.append(f"device_kind {kind!r} is not in "
                                f"PEAK_BF16_FLOPS")

    after = _cache_size(cache)
    print(f"\nchip_smoke: {time.monotonic() - t0:.0f} s; compile cache now "
          f"{after[0]} files, {after[1] / 2**20:.1f} MiB")
    for r in reports:
        print(f"  {r['phase']:<14} {'ok' if r['ok'] else 'FAILED':<7}"
              f"{r.get('platform')}/{r.get('device_kind')} x"
              f"{r.get('device_count')}  {r['seconds']} s")
        for c in r["compiles"]:
            if c["cold_s"] is not None:
                print(f"      cold {c['cold_s']:>8} s  warm "
                      f"{c['warm_s'] if c['warm_s'] is not None else '-':>8}"
                      f" s  {c['what']}")
    if failures:
        print("chip_smoke: FAILED", file=sys.stderr)
        for f in failures:
            print("  - " + f, file=sys.stderr)
        return 1
    platform, kind, count = devices.pop()
    result = {"ok": True,
              "device": {"platform": platform, "kind": kind, "count": count}}
    if args.rehearse_on_cpu:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
