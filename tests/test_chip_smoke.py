"""``chip_smoke.py`` off the chip: the rehearsal the contract allows, the
refusals it demands, and where the compile cache goes.

The script itself is the chip check (README "Development"); a CPU cannot
vouch for the chip, so what tier-1 holds is everything around it: the same
phases through the same entry points at toy sizes (``--rehearse-on-cpu``,
whose result line cannot pass for a chip run), a non-zero exit with no
result line when no chip is there and none was waived, and the one place
that decides the compile cache directory.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
PHASES = ["serving", "training", "onnx_resnet50", "onnx_bert", "sparse",
          "flash"]


def _results(stdout: str):
    """Lines of stdout that parse as the contract's result object."""
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict) and "ok" in obj:
                out.append(obj)
    return out


def test_rehearsal_runs_every_phase_on_the_cpu_and_says_it_is_one():
    proc = subprocess.run([sys.executable, SMOKE, "--rehearse-on-cpu"],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    # count is whatever XLA_FLAGS gives the CPU here (conftest: 8)
    assert last["device"].pop("count") >= 1
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu"}}
    reports = [json.loads(line[6:]) for line in proc.stdout.splitlines()
               if line.startswith("PHASE ")]
    assert [r["phase"] for r in reports] == PHASES
    for r in reports:
        assert r["ok"] and r["platform"] == "cpu", r
        assert r["versions"]["jax"] and r["versions"]["jaxlib"]
        assert r["compiles"], r["phase"]
        for c in r["compiles"]:
            assert "cold_s" in c and "warm_s" in c
    serving = reports[0]
    # the replies came from ONE worker process, not from the jax-free parent
    assert serving["checks"]["this_process_never_imported_jax"]["ok"]
    assert serving["checks"]["one_worker_process_answered"]["ok"]
    # every profiled entry point stayed on the profiled path
    for r in reports:
        for c in r["compiles"]:
            assert not (c.get("profiled") or {}).get("left_profiled_path")


def test_refuses_without_a_chip_and_prints_no_result():
    """No accelerator, no waiver: every child is told ``JAX_PLATFORMS=tpu``,
    cannot open it, and the run exits non-zero without a result line —
    whatever platform the surrounding environment names."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SMOKE], capture_output=True,
                          text=True, timeout=600, env=env)
    assert proc.returncode != 0
    assert _results(proc.stdout) == []
    assert "Unable to initialize backend 'tpu'" in proc.stdout + proc.stderr


def test_refuses_alone_in_a_directory(tmp_path):
    """The script is a check OF the repo: beside nothing else it fails."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode != 0
    assert _results(proc.stdout) == []


# -- the compile cache is placed in one place, from outside ------------------

# what the package, a fleet worker and a trial worker would each use: the
# launchers are run up to their Popen, whose environment is captured
_PLACEMENT_PROBE = r"""
import json, os, subprocess, sys
sys.path.insert(0, {repo!r})
if {jax_first!r}:
    import jax
import synapseml_tpu
from synapseml_tpu.io.serving_v2 import ProcessServingFleet
from synapseml_tpu.tuning.executor import _WorkerHandle
from tests.serving_fault_stage import PidEchoReply

VAR = "JAX_COMPILATION_CACHE_DIR"
seen = []

class Captured(Exception):
    pass

def fake_popen(cmd, *a, env=None, **kw):
    seen.append((env or os.environ).get(VAR))
    raise Captured()

subprocess.Popen = fake_popen
for launch in (lambda: ProcessServingFleet(PidEchoReply(), n_workers=1),
               lambda: _WorkerHandle({study!r}, 0)):
    try:
        launch()
    except Captured:
        pass
out = {{"package": os.environ.get(VAR), "fleet_worker": seen[0],
       "trial_worker": seen[1]}}
if {jax_first!r}:
    out["jax_config"] = jax.config.jax_compilation_cache_dir
print(json.dumps(out))
"""


def _placement(tmp_path, env_value, jax_first=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    code = _PLACEMENT_PROBE.format(repo=REPO, study=str(tmp_path),
                                   jax_first=jax_first)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_dir_from_outside_is_untouched_and_inherited(tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    got = _placement(tmp_path, placed)
    assert got == {"package": placed, "fleet_worker": placed,
                   "trial_worker": placed}
    # deciding is not creating: nothing was compiled, nothing was written —
    # neither there nor under the checkout
    assert not os.path.exists(placed)


@pytest.mark.parametrize("jax_first", [False, True])
def test_cache_dir_defaults_to_the_checkout_in_all_three(tmp_path, jax_first):
    default = os.path.join(REPO, ".jax_cache")
    existed = os.path.exists(default)
    got = _placement(tmp_path, None, jax_first=jax_first)
    assert got.pop("jax_config", default) == default  # a jax imported first
    assert got == {"package": default, "fleet_worker": default,
                   "trial_worker": default}
    assert os.path.exists(default) == existed


@pytest.mark.parametrize("names, named_in", [
    # the variable and the jax option that place jax's persistent cache
    (("compilation_cache_dir", "JAX_COMPILATION_CACHE_DIR"),
     ["synapseml_tpu/runtime/compile_cache.py"]),
    # a second place for compiled programs (executables pickled under a
    # directory of the package's own) and a second tensor layout in the
    # executor; in halves, so that a grep over tests/ finds neither
    (("serialize_" "executable", "SMT_AOT_" "CACHE_DIR", "channels_" "last"),
     []),
], ids=["jax_cache", "no_private_tier_no_second_layout"])
def test_one_place_decides_the_cache_dir(names, named_in):
    """Acceptance: grep finds ONE module in the package (and no tool or
    entry script) that places a compiled program on disk, and it places
    jax's own cache."""
    hits = []
    roots = [os.path.join(REPO, "synapseml_tpu"), os.path.join(REPO, "tools")]
    files = [os.path.join(REPO, f) for f in ("bench.py", "chip_smoke.py",
                                             "__graft_entry__.py")]
    for root in roots:
        for d, _, found in os.walk(root):
            files += [os.path.join(d, n) for n in found if n.endswith(".py")]
    for path in files:
        with open(path, encoding="utf-8") as f:
            src = f.read()
        if any(name in src for name in names):
            hits.append(os.path.relpath(path, REPO))
    assert hits == named_in


# -- the four-chip sibling ---------------------------------------------------

def test_mesh_smoke_rehearses_on_virtual_devices_and_refuses_without_chips():
    tool = os.path.join(REPO, "tools", "chip_mesh_smoke.py")
    proc = subprocess.run([sys.executable, tool, "--rehearse-on-cpu"],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    checks = {c["check"]: c for c in (
        json.loads(line[6:]) for line in proc.stdout.splitlines()
        if line.startswith("CHECK "))}
    assert set(checks) == {"gbdt_mesh", "onnx_placement", "onnx_tp"}
    assert all(checks["gbdt_mesh"]["identical"].values())  # PR 18's claim
    assert checks["onnx_tp"]["weight_shard_devices"] == [0, 1, 2, 3]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    # no waiver, no accelerator: non-zero, no result
    proc = subprocess.run([sys.executable, tool], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0 and _results(proc.stdout) == []
