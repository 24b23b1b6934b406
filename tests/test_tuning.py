"""Tuning subsystem tests: scheduler goldens, studies end to end, fault
tolerance, crash-resume bit-identity, shared binning, worker stats.

The process-executor tests spawn real worker subprocesses (the
``trial_worker`` line protocol), so they carry a few seconds of
interpreter + jax import each; they stay in tier-1 because fault
tolerance is the subsystem's contract, not an edge case.
"""

import copy
import json
import os

import numpy as np
import pytest

from synapseml_tpu.core import Table
from synapseml_tpu.io import faultinject
from synapseml_tpu.observability.metrics import get_registry
from synapseml_tpu.tuning import (AshaScheduler, Study, SuccessiveHalving,
                                  derive_trial_seed, leaderboard,
                                  read_journal, rung_ladder)

pytestmark = pytest.mark.filterwarnings("ignore::UserWarning")


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.clear_plan()
    yield
    faultinject.clear_plan()


# ---------------------------------------------------------------------------
# scheduler goldens (pure, no jax)
# ---------------------------------------------------------------------------

def test_rung_ladder_shapes():
    assert rung_ladder(27, eta=3) == [3, 9, 27]
    assert rung_ladder(9, eta=3) == [1, 3, 9]
    assert rung_ladder(10, eta=3) == [1, 3, 9, 10]
    assert rung_ladder(100, min_resource=5, eta=4) == [5, 20, 80, 100]
    assert rung_ladder(1) == [1]
    with pytest.raises(ValueError):
        rung_ladder(0)
    with pytest.raises(ValueError):
        rung_ladder(10, eta=1)


def test_sync_successive_halving_golden():
    sh = SuccessiveHalving(9, eta=3, seed=0)
    metrics = {0: 0.51, 1: 0.92, 2: 0.74, 3: 0.88, 4: 0.60, 5: 0.95}
    for tid, m in metrics.items():
        sh.tell(tid, 0, m)
    # top 6 // 3 = 2 of the rung: trials 5 (.95) and 1 (.92)
    assert sh.select(0) == [5, 1]
    sh.tell(5, 1, 0.96)
    sh.tell(1, 1, 0.97)
    assert sh.select(1) == [1]  # 2 // 3 -> never fewer than one survivor
    assert sh.select(2) == []   # top rung: nothing to promote into
    # failures are excluded even when ranked on top
    sh.mark_failed(5)
    assert sh.select(0) == [1, 3]
    # None / non-finite metrics rank below every number
    sh.tell(6, 0, None)
    sh.tell(7, 0, float("nan"))
    assert 6 not in sh.select(0) and 7 not in sh.select(0)


def test_sync_halving_tie_break_deterministic():
    a = SuccessiveHalving(9, eta=3, seed=4)
    b = SuccessiveHalving(9, eta=3, seed=4)
    for sh in (a, b):
        for tid in range(6):
            sh.tell(tid, 0, 0.5)  # full six-way tie
    assert a.select(0) == b.select(0)
    assert len(a.select(0)) == 2


def test_min_mode_ranks_inverted():
    sh = SuccessiveHalving(9, eta=3, seed=0, mode="min")
    for tid, m in {0: 2.0, 1: 0.5, 2: 1.0}.items():
        sh.tell(tid, 0, m)
    assert sh.select(0) == [1]


def test_asha_promotion_golden():
    """The paper's rule, step by step: promote top ``1/eta`` once quorum
    lands; later arrivals unlock SIDE promotions for paused reporters;
    re-reporting a promoted rung stays promoted (idempotent resume)."""
    sched = AshaScheduler(8, eta=2, seed=0, quorum=2)  # rungs [2, 4, 8]
    r = sched.report(0, 0, 0.50)
    assert r == {"decision": "stop", "promotions": []}  # below quorum
    r = sched.report(1, 0, 0.90)
    assert r["decision"] == "promote" and r["promotions"] == []
    r = sched.report(2, 0, 0.95)  # 3 results, allowed=1, t2 tops the rung
    assert r["decision"] == "promote"
    r = sched.report(3, 0, 0.40)  # allowed=2 but both slots already used
    assert r["decision"] == "stop"
    # rung 1: t1 lands first and pauses; t2's arrival completes the quorum
    # and promotes the PAUSED t1 as a side effect
    r = sched.report(1, 1, 0.93)
    assert r == {"decision": "stop", "promotions": []}
    r = sched.report(2, 1, 0.91)
    assert r["decision"] == "stop" and r["promotions"] == [1]
    # resume-idempotence: t1 re-reporting rung 1 is still promoted
    r = sched.report(1, 1, 0.93)
    assert r["decision"] == "promote"
    # the top rung is always final
    assert sched.report(1, 2, 0.94)["decision"] == "final"


def test_asha_replay_reproduces_decisions():
    feed_rows = [(0, 2, .6), (1, 2, .9), (2, 2, .8), (1, 4, .92), (3, 2, .7)]

    def feed(s):
        return [s.report(tid, s.rung_index(iters), m)["decision"]
                for tid, iters, m in feed_rows]

    live = AshaScheduler(8, eta=2, seed=7, quorum=2)
    decisions = feed(live)
    replayed = AshaScheduler(8, eta=2, seed=7, quorum=2)
    replayed.replay([{"trial_id": t, "iters": i, "metric": m}
                     for t, i, m in feed_rows])
    assert replayed.results == live.results
    assert [set(p) for p in replayed.promoted] == [set(p) for p in live.promoted]
    assert decisions[1] == "promote"


def test_derive_trial_seed_stable():
    s = derive_trial_seed(11, 3)
    assert s == derive_trial_seed(11, 3)
    assert s != derive_trial_seed(11, 4)
    assert 0 <= s < 2 ** 31 - 1


# ---------------------------------------------------------------------------
# study fixtures
# ---------------------------------------------------------------------------

def _toy(n=160, f=6, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    logits = 1.5 * x[:, 0] - x[:, 1] + 0.5 * x[:, 2]
    y = (logits + rng.normal(scale=0.7, size=n) > 0).astype(np.float64)
    cut = int(n * 0.75)
    return x[:cut], y[:cut], x[cut:], y[cut:]


def _template(**kw):
    from synapseml_tpu.gbdt import LightGBMClassifier

    base = dict(num_iterations=9, num_leaves=7, max_bin=15, seed=0)
    base.update(kw)
    return LightGBMClassifier(**base)


_MAPS = [{"learning_rate": lr, "num_leaves": nl}
         for lr in (0.05, 0.1, 0.2) for nl in (3, 7)]


def _run_study(tmp_path, sub, **kw):
    xtr, ytr, xv, yv = _toy()
    wd = os.path.join(str(tmp_path), sub)
    args = dict(metric="auc", mode="max", study_seed=3, max_resource=9,
                executor="threads", parallelism=2, workdir=wd)
    args.update(kw)
    study = Study(_template(), copy.deepcopy(_MAPS), xtr, ytr, xv, yv, **args)
    return study.run()


# ---------------------------------------------------------------------------
# threads end-to-end
# ---------------------------------------------------------------------------

def test_study_threads_end_to_end(tmp_path):
    ticks = iter(range(100000))
    res = _run_study(tmp_path, "e2e", clock=lambda: float(next(ticks)))
    rows = res["leaderboard"]
    assert len(rows) == len(_MAPS)
    states = [r["state"] for r in rows]
    assert states.count("completed") >= 1
    assert "failed" not in states
    assert res["best"] is not None and res["best"]["metric"] > 0.6
    # the halving shape: spent iterations well under everyone-trains-full-R
    assert res["spent_iterations"] < len(_MAPS) * 9
    # rung entries are cumulative-iteration landings on the ladder [1, 3, 9]
    for r in rows:
        assert [e["iters"] for e in r["rungs"]] == sorted(
            e["iters"] for e in r["rungs"])
        assert all(e["iters"] in (1, 3, 9) for e in r["rungs"])
    # journal agrees with the in-memory result
    events = read_journal(res["journal_path"])
    assert any(e["event"] == "study_end" for e in events)
    again = leaderboard(events, mode="max")
    assert json.dumps(again, sort_keys=True) == json.dumps(rows, sort_keys=True)
    # metric families landed (fake clock drives rung_seconds deterministic)
    fams = get_registry().snapshot()["families"]
    assert "smt_tuning_trials_total" in fams
    assert "smt_tuning_best_metric" in fams
    rung_s = fams["smt_tuning_rung_seconds"]
    assert sum(s["count"] for s in rung_s["series"]) > 0


def test_threads_fault_retry_then_success(tmp_path):
    """An injected one-shot fault fails a segment's first attempt; the
    retry succeeds and the study records NO failed trial."""
    faultinject.install_plan([{"site": "tuning.trial", "kind": "5xx",
                               "match": "trial=1 start", "times": 1}])
    res = _run_study(tmp_path, "retry", parallelism=1)
    states = {r["trial_id"]: r["state"] for r in res["leaderboard"]}
    assert "failed" not in states.values()
    assert res["best"] is not None


def test_threads_fault_both_attempts_fails_trial_only(tmp_path):
    """Both attempts crashing marks THAT trial failed; the study still
    completes and crowns a winner from the survivors."""
    faultinject.install_plan([{"site": "tuning.trial", "kind": "refuse",
                               "match": "trial=2 start"}])
    res = _run_study(tmp_path, "fail1", parallelism=1)
    states = {r["trial_id"]: r["state"] for r in res["leaderboard"]}
    assert states[2] == "failed"
    assert sum(1 for s in states.values() if s == "failed") == 1
    assert res["best"] is not None and res["best"]["trial_id"] != 2


def test_journal_resume_bit_identical(tmp_path):
    """Truncate a finished journal mid-study and resume: the re-run
    executes only the remainder and the final leaderboard is
    bit-identical to the uninterrupted run's."""
    golden = _run_study(tmp_path, "full", parallelism=1)
    gold_dump = json.dumps(golden["leaderboard"], sort_keys=True)

    crashed = _run_study(tmp_path, "crashed", parallelism=1)
    jp = crashed["journal_path"]
    lines = open(jp, encoding="utf-8").read().splitlines(keepends=True)
    # cut right after the second terminal event — mid-study, some trials
    # finished, some in flight, some never started
    n_term = 0
    for i, ln in enumerate(lines):
        if '"terminal"' in ln:
            n_term += 1
            if n_term == 2:
                cut = i + 1
                break
    assert n_term == 2
    with open(jp, "w", encoding="utf-8") as f:
        f.writelines(lines[:cut])

    resumed = _run_study(tmp_path, "crashed", parallelism=1)
    assert json.dumps(resumed["leaderboard"], sort_keys=True) == gold_dump
    assert resumed["best"]["params"] == golden["best"]["params"]


def test_budget_caps_spent_iterations(tmp_path):
    res = _run_study(tmp_path, "budget", parallelism=1, budget=12)
    assert res["spent_iterations"] <= 12 + 9  # in-flight segment finishes
    states = [r["state"] for r in res["leaderboard"]]
    assert "pending" not in states  # everything reached a terminal state


# ---------------------------------------------------------------------------
# shared binning
# ---------------------------------------------------------------------------

def test_shared_binning_bit_parity():
    """``from_binned`` (the worker's mmap path) is bit-identical to
    binning from raw: same mapper, same binned matrix, same dtype."""
    from synapseml_tpu.gbdt.binning import BinMapper
    from synapseml_tpu.gbdt.dataset import GBDTDataset

    xtr, ytr, _, _ = _toy()
    ds = GBDTDataset(xtr, label=ytr, max_bin=15, seed=0)
    mapper = BinMapper.from_dict(ds.mapper.to_dict())
    ds2 = GBDTDataset.from_binned(np.array(ds.binned_np), mapper,
                                  x=xtr, label=ytr)
    np.testing.assert_array_equal(ds.binned_np, ds2.binned_np)
    assert ds.binned_np.dtype == ds2.binned_np.dtype
    assert ds.max_bin == ds2.max_bin
    np.testing.assert_array_equal(
        mapper.transform(xtr), ds.binned_np)


# ---------------------------------------------------------------------------
# process executor (real worker subprocesses)
# ---------------------------------------------------------------------------

def test_process_worker_crash_one_failed_trial_and_resume(tmp_path):
    """A fault plan that kills the worker at trial 2's segment start (both
    attempts — respawned workers get fresh counters) yields exactly one
    failed trial; resuming the journal reproduces the same best params
    WITHOUT retrying the failed trial."""
    plan = json.dumps({"rules": [{"site": "tuning.trial", "kind": "refuse",
                                  "match": "trial=2 start"}]})
    res = _run_study(tmp_path, "proc_crash", executor="processes",
                     parallelism=1, task_timeout_s=120.0,
                     worker_env={"SMT_FAULT_PLAN": plan})
    states = {r["trial_id"]: r["state"] for r in res["leaderboard"]}
    assert states[2] == "failed"
    assert sum(1 for s in states.values() if s == "failed") == 1
    assert res["best"] is not None and res["best"]["trial_id"] != 2
    gold_dump = json.dumps(res["leaderboard"], sort_keys=True)

    # resume with NO fault plan: the journaled failure must stick (the
    # study is reproducible, not retried into a different outcome)
    jp = res["journal_path"]
    lines = open(jp, encoding="utf-8").read().splitlines(keepends=True)
    cut = max(i for i, ln in enumerate(lines) if '"terminal"' in ln)
    with open(jp, "w", encoding="utf-8") as f:
        f.writelines(lines[:cut])  # drop the last terminal + study_end
    resumed = _run_study(tmp_path, "proc_crash", executor="processes",
                         parallelism=1, task_timeout_s=120.0)
    assert {r["trial_id"]: r["state"] for r in resumed["leaderboard"]}[2] == "failed"
    assert resumed["best"]["params"] == res["best"]["params"]
    assert json.dumps(resumed["leaderboard"], sort_keys=True) == gold_dump


def test_process_study_ships_worker_stats_home(tmp_path):
    """A process study's workers report the compiles they paid, and a
    second study over the same statics finds the same best metric."""
    maps = [{}, {}]  # identical statics; trial seeds differ (runtime args)
    xtr, ytr, xv, yv = _toy()

    def run(sub):
        wd = os.path.join(str(tmp_path), sub)
        return Study(_template(num_iterations=3), copy.deepcopy(maps),
                     xtr, ytr, xv, yv, metric="auc", study_seed=3,
                     max_resource=3, min_resource=3, executor="processes",
                     parallelism=1, workdir=wd, task_timeout_s=120.0).run()

    first = run("study1")
    stats = first["worker_stats"]
    assert stats, "process study must ship worker compile stats home"
    assert sum(s["compile_samples"] for s in stats) >= 1
    second = run("study2")
    assert second["best"]["metric"] == pytest.approx(
        first["best"]["metric"], abs=1e-12)


# ---------------------------------------------------------------------------
# the SparkML-surface entry: asha vs legacy random (ISSUE acceptance)
# ---------------------------------------------------------------------------

def test_breast_cancer_asha_matches_random_at_half_budget():
    """ASHA + shared binning reaches an equal-or-better best AUC than the
    legacy random search while spending at most HALF the total boosting
    iterations."""
    from sklearn.datasets import load_breast_cancer

    from synapseml_tpu.automl import TuneHyperparameters
    from synapseml_tpu.gbdt import LightGBMClassifier

    x, y = load_breast_cancer(return_X_y=True)
    x = np.asarray(x, np.float64)[:400]
    y = np.asarray(y, np.float64)[:400]
    table = Table({"features": x, "label": y})
    space = {"num_leaves": [3, 7, 15], "learning_rate": [0.05, 0.1, 0.2]}
    n_runs, R = 6, 12

    def tuner(mode, **kw):
        return TuneHyperparameters(
            models=LightGBMClassifier(num_iterations=R, max_bin=31, seed=0),
            hyperparams=dict(space), search_mode=mode, number_of_runs=n_runs,
            evaluation_metric="auc", seed=7, parallelism=2, **kw)

    random_fit = tuner("random").fit(table)
    # first rung at 3 iterations: iteration 1 is a four-way AUC tie on
    # this dataset, too noisy to rank
    asha_fit = tuner("asha", min_resource=3).fit(table)

    random_total = n_runs * R
    asha_total = sum(int(r["iterations"]) for r in asha_fit.history)
    assert asha_total * 2 <= random_total, (
        f"asha spent {asha_total} of random's {random_total}")
    assert float(asha_fit.best_metric) >= float(random_fit.best_metric), (
        f"asha {asha_fit.best_metric} < random {random_fit.best_metric}")


# ---------------------------------------------------------------------------
# tools/tune_report.py (jax-free CLI over the same journal)
# ---------------------------------------------------------------------------

def test_tune_report_renders_and_checks(tmp_path):
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location(
        "tune_report", os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools", "tune_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)

    res = _run_study(tmp_path, "report", parallelism=1)
    jp = res["journal_path"]
    study = tr.reduce_study(tr.load_events(jp))
    # the CLI's reduction is the SAME leaderboard the study returned
    assert json.dumps(study["leaderboard"], sort_keys=True) == \
        json.dumps(res["leaderboard"], sort_keys=True)
    text = tr.render(study)
    assert "study_end" in text and "rung" in text
    # self-check against its own journal passes ...
    assert tr.main([jp, "--check", jp]) == 0
    # ... and a better golden fails the gate
    better = dict(study, best=dict(study["best"],
                                   metric=float(study["best"]["metric"]) + 1))
    assert tr.check(study, better, tol=0.0)
    assert not tr.check(study, better, tol=2.0)


def test_unknown_search_mode_rejected():
    # a typo must not silently degrade to random search now that three
    # modes exist
    from synapseml_tpu.automl import TuneHyperparameters

    xtr, ytr, _, _ = _toy(n=40)
    t = Table({"features": xtr, "label": ytr})
    tuner = TuneHyperparameters(
        models=_template(), hyperparams={"learning_rate": [0.1]},
        search_mode="ahsa", evaluation_metric="auc", seed=0)
    with pytest.raises(ValueError, match="search_mode"):
        tuner.fit(t)
