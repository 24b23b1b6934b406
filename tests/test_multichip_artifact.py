"""The multi-chip gate's artefact: what ``dryrun_multichip(8)`` stamps.

Since ISSUE 14 the dryrun runs every engine over ONE canonical
``SpecLayout`` mesh (``runtime/layout.py``); since ISSUE 19 that mesh is
the 3-D ``(data, fsdp, model)`` beyond-HBM layout when 8 devices allow
it — ONNX weights store row-sharded over ``fsdp`` and all-gather at each
consumer, and the tail stamps the fsdp decision (``FSDP_ONNX``). These
tests pin the artefact to that shape so a regression back to 2-D (or 1-D
data-parallel-only) dryruns fails CI, not just review.

The artefact is made here — the dryrun runs once per session on the 8
virtual CPU devices (conftest ``dryrun_multichip_8_stdout``) and its output
is written under ``tmp_path`` in the driver's ``MULTICHIP_r{N}.json`` format
— so the tests judge the code as it is, not a committed record of some
earlier round.
"""

import ast
import json
import re

import pytest


@pytest.fixture(scope="module")
def artifact(tmp_path_factory, dryrun_multichip_8_stdout):
    tail = dryrun_multichip_8_stdout
    mesh = re.search(r"^MESH (\{.*\})$", tail, re.M)
    path = tmp_path_factory.mktemp("multichip") / "MULTICHIP_r99.json"
    path.write_text(json.dumps({
        "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
        "mesh": ast.literal_eval(mesh.group(1)) if mesh else None,
        "tail": tail}))
    with open(path) as f:
        return json.load(f)


def test_multichip_artifact_is_ok(artifact):
    assert artifact["ok"] is True
    assert artifact["rc"] == 0
    assert not artifact["skipped"]
    assert artifact["n_devices"] >= 8
    # every result names the device it ran on
    assert re.search(r"^DEVICES platform=cpu kind='cpu' count=8$",
                     artifact["tail"], re.M)


def test_multichip_artifact_exercises_3d_mesh(artifact):
    mesh = artifact.get("mesh")
    assert mesh, "artifact missing the mesh stamp (layout.describe())"
    assert set(mesh) == {"data", "fsdp", "model"}
    assert mesh["model"] >= 2, "model axis unpopulated: not a tp dryrun"
    assert mesh["fsdp"] >= 2, "fsdp axis unpopulated: not a 3-D dryrun"
    assert mesh["data"] * mesh["fsdp"] * mesh["model"] == \
        artifact["n_devices"]


def test_multichip_artifact_stamps_fsdp_storage(artifact):
    # the in-run beyond-HBM proof line: at least one ONNX weight STORED
    # row-sharded over the fsdp axis, with output parity vs the
    # replicated path asserted inside the dryrun itself
    tail = artifact.get("tail", "")
    m = re.search(r"FSDP_ONNX stored=(\d+) bytes=(\d+)", tail)
    assert m, f"dryrun tail missing the FSDP_ONNX stamp: {tail!r}"
    assert int(m.group(1)) > 0
    assert int(m.group(2)) > 0
