"""The whole command at toy sizes on the CPU: a sound run is correct; the
control and an answer altered where it is produced are not; BENCHMARK.json,
the cells and the metric files agree. The rehearsal cell's files are also the
proof that a cell is added by files alone: nothing in ``run.py`` names it."""

import json
import os

import numpy as np
import pytest

import run

CELL = "bert_tiny.rehearsal"
ROOT = run.ROOT


@pytest.fixture(scope="module")
def sound():
    return run.run_cell(CELL, seed=2_147_484_001, seconds=0.5, trace=False,
                        rehearse=True, with_control=True)


def test_a_sound_run_is_correct_and_prints_the_contract(sound):
    assert sound["correct"] is True
    assert sound["attempted"] >= 3 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"rows_per_s", "call_p90_ms", "setup_s"}
    for m in sound["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert sound["device"]["platform"] == "cpu"  # marked as what it is
    assert list(sound)[-1] == "compared"
    for row in sound["compared"].values():
        assert row["value"] <= row["limit"]


def test_the_control_comes_out_not_correct(sound):
    """float8 in the reference's place, at the cell's own limits."""
    assert sound["control"]["correct"] is False
    numbers, limits = sound["control"]["numbers"], sound["compared"]
    assert any(numbers[k] > limits[k]["limit"] for k in numbers)


def test_same_seed_same_inputs():
    from benchmark.traffic import make_pool

    cell = run.load_json("workloads", CELL + ".json")
    config = run.load_json("configs", cell["config"] + ".json")
    a, b = (make_pool(config, cell["traffic"], 3_000_000_019) for _ in "ab")
    c = make_pool(config, cell["traffic"], 3_000_000_020)
    assert all(np.array_equal(x["input_ids"], y["input_ids"])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0]["input_ids"], c[0]["input_ids"])
    assert {t["input_ids"].shape for t in a + c} == {(8, 16)}


@pytest.mark.parametrize("fault", ["every_answer_off", "one_row_misplaced",
                                   "answer_changes_between_calls"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, fault):
    """The rest of a run, with the executor broken underneath the timed
    path: ``OnnxFunction.__call__`` is where ``transform`` gets its rows."""
    from synapseml_tpu.onnx.importer import OnnxFunction

    sound_call, n = OnnxFunction.__call__, [0]

    def broken(self, feeds):
        out = {k: np.array(v) for k, v in sound_call(self, feeds).items()}
        n[0] += 1
        for name in ("logits", "pooled"):
            if fault == "every_answer_off":
                out[name] = out[name] * 1.1
            elif fault == "one_row_misplaced":  # the last row answers the first
                out[name][-1] = out[name][0]
            elif n[0] == 5:
                out[name][3] += 1e-3
        return out

    monkeypatch.setattr(OnnxFunction, "__call__", broken)
    result = run.run_cell(CELL, seed=2_147_484_002, seconds=0.5, trace=False,
                          rehearse=True)
    assert result["correct"] is False
    over = [k for k, row in result["compared"].items()
            if row["value"] > row["limit"]]
    assert over and (fault != "answer_changes_between_calls"
                     or over == ["repeat_mismatch"])


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "0.2"])
    assert e.value.code == run.NO_CHIP_EXIT
    assert capsys.readouterr().out == ""


def test_benchmark_json_agrees_with_the_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = {c["name"]: c for c in bench["configs"]}
    for c in configs.values():
        on_disk = run.load_json("configs", c["name"] + ".json")
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert (on_disk["source"], on_disk["reduced"]) == (c["source"],
                                                           c["reduced"])
    for w in bench["workloads"]:
        cell = run.load_json("workloads", w["name"] + ".json")
        assert (cell["config"], cell["chips"], cell["why"]) == \
            (w["config"], w["chips"], w["why"])
        assert w["config"] in configs
        assert cell["check"]["rows_per_table"] % cell["check"]["block_rows"] == 0
        assert all(limit < 1 for limit in cell["check"]["limits"].values())
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        spec = run.load_json("metrics", m["name"] + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec.get(key) == m.get(key), (m["name"], key)
        assert os.path.exists(os.path.join(run.HERE, "readers",
                                           spec["reader"] + ".py"))
        assert m.get("moves", next(iter(e2e))) in e2e
