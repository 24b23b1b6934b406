"""Trial execution backends: in-process threads and a worker-process pool.

The unit of work is a :class:`TrialTask` — "train trial ``trial_id`` from
``from_iter`` to ``to_iter`` boosting iterations" — executed by
:func:`run_trial_segment`, which drives the ordinary estimator ``fit``
through the ``_tuning_overrides`` seam so every trial trains from the
study's ONE shared pre-binned :class:`~..gbdt.dataset.GBDTDataset` and
reports at rung boundaries through the GBDT per-iteration callback (a
demoted trial stops at its rung budget — the callback returns truthy and
``boost.train`` breaks out exactly like early stopping).

Two backends implement ``run(task, on_rung) -> result``:

- :class:`ThreadExecutor` — in-process (the back-compat mode: shares the
  caller's jax runtime and its in-memory jit caches).
- :class:`ProcessExecutor` — persistent worker subprocesses in the style
  of ``io/serving_worker``: one worker per slot, line-oriented
  stdin/stdout protocol (``READY`` handshake, ``TASK``/``RUNG``/``CONT``/
  ``STOP``/``DONE``/``FAIL``), models shipped between segments via
  ``core.serialization`` round-trips. A worker that dies or stops
  answering within ``task_timeout_s`` raises :class:`WorkerCrash`; the
  study retries the task once on a fresh worker, then records the trial
  ``failed``.

Fault injection: the ``"tuning.trial"`` seam (``io/faultinject``) is
consulted at segment start and at every rung boundary with key
``"trial=<id> ... attempt=<n>"`` — ``refuse``/``wedge`` simulate a worker
crash/hang, ``5xx``/``disconnect`` an in-trial error, ``latency`` a
straggler. This module is jax-free at import; jax enters only inside a
running trial via the estimator.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..io import faultinject

__all__ = [
    "TrialTask", "StudyContext", "WorkerCrash", "TrialError",
    "derive_trial_seed", "run_trial_segment",
    "ThreadExecutor", "ProcessExecutor",
]

FAULT_SITE = "tuning.trial"


class WorkerCrash(RuntimeError):
    """The executor lost the trial mid-flight (process died / wedged past
    its deadline / injected crash) — retryable exactly once."""


class TrialError(RuntimeError):
    """The trial itself raised — also retryable once (a transient OOM or
    injected 5xx), then terminal ``failed``."""


def derive_trial_seed(study_seed: int, trial_id: int) -> int:
    """Per-trial RNG seed keyed off ``(study_seed, trial_id)`` — stable
    across executors, schedulers, and resume, so a trial's result never
    depends on WHERE or WHEN it ran."""
    h = hashlib.sha256(f"{study_seed}:{trial_id}".encode()).hexdigest()
    return int(h[:8], 16) % (2 ** 31 - 1)


class TrialTask:
    """One contiguous training segment of a trial."""

    __slots__ = ("trial_id", "params", "seed", "from_iter", "to_iter",
                 "init_model_path", "attempt")

    def __init__(self, trial_id: int, params: Dict[str, Any], seed: int,
                 from_iter: int, to_iter: int,
                 init_model_path: Optional[str] = None, attempt: int = 0):
        self.trial_id = int(trial_id)
        self.params = dict(params)
        self.seed = int(seed)
        self.from_iter = int(from_iter)
        self.to_iter = int(to_iter)
        self.init_model_path = init_model_path
        self.attempt = int(attempt)

    def to_json(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__}

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "TrialTask":
        return cls(**d)


class StudyContext:
    """Everything a trial segment needs, prepared once per study (or once
    per worker process): the estimator template, the shared pre-binned
    dataset, the eval set the rung metric is computed on, and the rung
    ladder."""

    def __init__(self, template, dataset, train_table, eval_set,
                 metric: str, rungs: List[int], model_dir: str,
                 clock: Callable[[], float] = time.monotonic):
        self.template = template
        self.dataset = dataset
        self.train_table = train_table
        self.eval_set = eval_set
        self.metric = metric
        self.rungs = list(rungs)
        self.rung_set = set(self.rungs)
        self.model_dir = model_dir
        self.clock = clock


def _thread_crash(rule) -> None:
    """In-process stand-in for a killed worker: a bounded wedge hold, then
    the crash exception the process backend would surface."""
    if rule.kind == "wedge" and rule.delay_ms:
        time.sleep(rule.delay_ms / 1e3)
    raise WorkerCrash(f"injected {rule.kind} fault")


def maybe_fault(key: str, crash: Callable[[Any], None]) -> None:
    """Consult the ``tuning.trial`` seam; ``crash`` decides what a dead
    worker looks like for this backend (raise vs ``os._exit``)."""
    rule = faultinject.act(FAULT_SITE, key=key)
    if rule is None:
        return
    if rule.kind == "latency":
        time.sleep(rule.delay_ms / 1e3)
        return
    if rule.kind in ("refuse", "wedge"):
        crash(rule)
        raise WorkerCrash(f"injected {rule.kind} fault at {key}")
    raise TrialError(f"injected {rule.kind} fault at {key}")


def run_trial_segment(ctx: StudyContext, task: TrialTask,
                      on_rung: Callable[[int, int, Optional[float], float], str],
                      crash: Callable[[Any], None] = _thread_crash
                      ) -> Dict[str, Any]:
    """Train one segment; ``on_rung(trial_id, iters, metric, t_s)`` is
    called at every INTERIOR rung boundary and must answer ``"cont"`` or
    ``"stop"``. Returns the segment result (cumulative iterations, last
    metric, saved model path, and whether a rung decision stopped it)."""
    import copy

    maybe_fault(f"trial={task.trial_id} start iter={task.from_iter} "
                f"attempt={task.attempt}", crash)
    est = copy.deepcopy(ctx.template)
    for k, v in task.params.items():
        est.set(k, v)

    init_booster = None
    if task.init_model_path:
        from ..core.serialization import load_stage

        init_model = load_stage(task.init_model_path)
        init_booster = init_model.booster
        # the round-tripped mapper is bit-equal to the study's; restoring
        # the IDENTITY lets train() keep the reuse_dataset fast path
        # (mapper-is-dataset.mapper) instead of re-binning
        init_booster.mapper = ctx.dataset.mapper

    state = {"metric": None, "iters": task.from_iter, "stop": False,
             "t0": ctx.clock()}

    def rung_cb(info: Dict[str, Any]):
        it = int(info["iteration"])  # 0-based within this segment
        done = task.from_iter + it + 1  # cumulative trial iterations
        state["iters"] = done
        ev = info.get("evals")
        if ev is not None:
            m = ev.get(f"eval0_{ctx.metric}")
            if m is not None:
                state["metric"] = float(m)
        if done in ctx.rung_set and done < task.to_iter:
            maybe_fault(f"trial={task.trial_id} rung iter={done} "
                        f"attempt={task.attempt}", crash)
            now = ctx.clock()
            decision = on_rung(task.trial_id, done, state["metric"],
                               now - state["t0"])
            state["t0"] = now
            if decision == "stop":
                state["stop"] = True
                return True
        return False

    est._tuning_overrides = {
        "dataset": ctx.dataset,
        "eval_set": ctx.eval_set,
        "callbacks": [rung_cb],
        "init_booster": init_booster,
        "params": {
            "num_iterations": task.to_iter - task.from_iter,
            "metric": ctx.metric,
            "seed": task.seed,
            "bagging_seed": task.seed,
            # the scheduler owns the stopping decisions; trainer-internal
            # early stopping would race it
            "early_stopping_round": 0,
        },
    }
    model = est.fit(ctx.train_table)

    from ..core.serialization import save_stage

    path = os.path.join(ctx.model_dir,
                        f"trial_{task.trial_id:04d}_i{state['iters']}")
    save_stage(model, path)
    t_s = ctx.clock() - state["t0"]
    return {"trial_id": task.trial_id, "iterations": state["iters"],
            "metric": state["metric"], "model_path": path,
            "stopped": state["stop"], "t_s": t_s}


class ThreadExecutor:
    """Back-compat in-process backend: the segment runs on the calling
    slot thread, sharing this process's jax caches."""

    kind = "threads"

    def __init__(self, ctx: StudyContext):
        self.ctx = ctx

    def run(self, task: TrialTask, on_rung) -> Dict[str, Any]:
        return run_trial_segment(self.ctx, task, on_rung)

    def close(self) -> None:
        pass


# ---------------------------------------------------------------------------
# process backend
# ---------------------------------------------------------------------------

class _LineReader:
    """Pump a worker's stdout into a queue so every parent read has a
    deadline (lint SMT011: a wedged worker must not hang the study)."""

    def __init__(self, stream):
        self._q: "queue.Queue[Optional[str]]" = queue.Queue()
        t = threading.Thread(target=self._pump, args=(stream,), daemon=True)
        t.start()

    def _pump(self, stream) -> None:
        try:
            for line in stream:
                self._q.put(line)
        except ValueError:
            pass  # stream closed under us during shutdown
        self._q.put(None)  # EOF marker

    def get(self, timeout: float) -> Optional[str]:
        """Next line, or None at EOF; raises ``queue.Empty`` on deadline."""
        return self._q.get(timeout=timeout)


class _WorkerHandle:
    """One persistent trial-worker subprocess (``tuning/trial_worker.py``),
    mirroring the ``io/serving_worker`` lifecycle: spawn, first-line
    handshake, line protocol, kill on misbehavior."""

    def __init__(self, study_dir: str, slot: int,
                 task_timeout_s: float = 300.0,
                 env: Optional[Dict[str, str]] = None):
        self.study_dir = study_dir
        self.task_timeout_s = float(task_timeout_s)
        wenv = dict(os.environ)
        # the worker must resolve this package even when the parent runs
        # from a source checkout that is not installed
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        wenv["PYTHONPATH"] = pkg_root + os.pathsep + wenv.get("PYTHONPATH", "")
        wenv.update(env or {})
        self._log_path = os.path.join(study_dir, f"worker-{slot}.log")
        self._log = open(self._log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "synapseml_tpu.tuning.trial_worker",
             "--study-dir", study_dir],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._log, env=wenv, text=True, bufsize=1)
        self._reader = _LineReader(self.proc.stdout)
        line = self._read(timeout=self.task_timeout_s)
        if line is None or not line.startswith("READY"):
            self.kill()
            # the worker's own words: with JAX_PLATFORMS asking for an
            # accelerator this is where "the chip is held by another
            # process" surfaces (one worker per chip)
            raise WorkerCrash(f"trial worker failed to start: {line!r}; "
                              f"its log ends:\n{self._log_tail()}")

    def _log_tail(self) -> str:
        try:
            with open(self._log_path, "rb") as f:
                return f.read()[-2000:].decode(errors="replace").strip()
        except OSError:
            return ""

    def alive(self) -> bool:
        return self.proc.poll() is None

    def _read(self, timeout: float) -> Optional[str]:
        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise queue.Empty
            try:
                line = self._reader.get(timeout=min(remaining, 1.0))
            except queue.Empty:
                continue
            if line is None:
                return None  # EOF: the worker died
            line = line.strip()
            if line.startswith(("READY", "RUNG", "DONE", "FAIL")):
                return line
            # anything else is stray library stdout — skip it

    def _send(self, line: str) -> None:
        try:
            self.proc.stdin.write(line + "\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError, ValueError) as e:
            raise WorkerCrash(f"trial worker pipe broken: {e}") from e

    def run_task(self, task: TrialTask, on_rung) -> Dict[str, Any]:
        self._send("TASK " + json.dumps(task.to_json()))
        while True:
            try:
                line = self._read(timeout=self.task_timeout_s)
            except queue.Empty:
                raise WorkerCrash(
                    f"trial worker unresponsive for {self.task_timeout_s}s "
                    f"on trial {task.trial_id}") from None
            if line is None:
                raise WorkerCrash(
                    f"trial worker died (exit {self.proc.poll()}) on trial "
                    f"{task.trial_id}")
            if line.startswith("RUNG "):
                r = json.loads(line[5:])
                decision = on_rung(int(r["trial_id"]), int(r["iters"]),
                                   r.get("metric"), float(r.get("t_s", 0.0)))
                self._send("STOP" if decision == "stop" else "CONT")
            elif line.startswith("DONE "):
                return json.loads(line[5:])
            elif line.startswith("FAIL "):
                err = json.loads(line[5:])
                raise TrialError(err.get("error", "trial failed in worker"))

    def kill(self) -> None:
        try:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            pass
        try:
            self._log.close()
        except OSError:
            pass

    def shutdown(self) -> None:
        try:
            if self.proc.poll() is None:
                self._send("EXIT")
                self.proc.wait(timeout=5)
        except (WorkerCrash, subprocess.TimeoutExpired, OSError):
            pass
        self.kill()


class ProcessExecutor:
    """Process-pool backend: each study slot thread owns one persistent
    worker subprocess (thread-local), respawned lazily after a crash. All
    workers inherit the study's ``SMT_FAULT_PLAN`` (each worker parses its
    own plan)."""

    kind = "processes"

    def __init__(self, study_dir: str, task_timeout_s: float = 300.0,
                 env: Optional[Dict[str, str]] = None):
        self.study_dir = study_dir
        self.task_timeout_s = float(task_timeout_s)
        self.env = dict(env or {})
        self._local = threading.local()
        self._handles: List[_WorkerHandle] = []
        self._lock = threading.Lock()
        self._slot_counter = 0

    def _worker(self) -> _WorkerHandle:
        h = getattr(self._local, "handle", None)
        if h is not None and h.alive():
            return h
        with self._lock:
            slot = self._slot_counter
            self._slot_counter += 1
        h = _WorkerHandle(self.study_dir, slot,
                          task_timeout_s=self.task_timeout_s, env=self.env)
        self._local.handle = h
        with self._lock:
            self._handles.append(h)
        return h

    def run(self, task: TrialTask, on_rung) -> Dict[str, Any]:
        h = self._worker()
        try:
            return h.run_task(task, on_rung)
        except WorkerCrash:
            h.kill()
            self._local.handle = None
            raise

    def worker_stats(self) -> List[Dict[str, Any]]:
        """Per-worker final stats (compile counts etc.) collected from the
        DONE payloads — populated by the study, kept here for symmetry."""
        return []

    def close(self) -> None:
        with self._lock:
            handles, self._handles = self._handles, []
        for h in handles:
            h.shutdown()
