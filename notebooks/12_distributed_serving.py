# %% [markdown]
# # Distributed serving: a real OS-process fleet with failover
#
# The serving tier at its full depth (reference: Spark Serving's
# load-balanced continuous server + the `HTTPv2Suite` fault contract —
# kill a worker mid-stream and the service keeps answering): a trained
# pipeline is saved, N worker PROCESSES each load a copy and serve it, and
# a routing front door round-robins requests, evicting dead workers and
# failing requests over.
#
# Delivery contract (r5): timeouts never re-send non-idempotent requests
# (a slow worker may still finish — re-sending a POST would double its side
# effects); worker DEATH fails over, the reference's kill-a-worker
# behavior.

# %%
import json
import urllib.request

import numpy as np

from synapseml_tpu import Table
from synapseml_tpu.core.stage import Transformer
from synapseml_tpu.gbdt import LightGBMClassifier
from synapseml_tpu.io.serving import string_to_response

rng = np.random.default_rng(0)
x = rng.normal(size=(2000, 6))
y = (x[:, 0] - 0.5 * x[:, 3] > 0).astype(np.float64)
model = LightGBMClassifier(num_iterations=15, num_leaves=15).fit(
    Table({"features": x, "label": y}))


class Score(Transformer):
    """request JSON {"features": [...]} -> {"probability": p}"""

    def _transform(self, table):
        reqs = table["request"]
        feats = np.array([json.loads(r.entity)["features"] for r in reqs])
        scored = model.transform(Table({"features": feats}))
        out = np.empty(len(reqs), dtype=object)
        for i in range(len(reqs)):
            out[i] = {"probability": float(scored["probability"][i, 1])}
        return table.with_column("reply", out)


# %% single-process continuous serving first (sub-ms p50)
from synapseml_tpu.io.serving import ServingServer
from synapseml_tpu.io.serving_v2 import ContinuousServingEngine

srv = ServingServer(port=0)
eng = ContinuousServingEngine(srv, Score()).start()


def hit(addr, row):
    req = urllib.request.Request(
        addr, data=json.dumps({"features": list(map(float, row))}).encode(),
        method="POST", headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=15) as r:
        return json.loads(r.read())


print("continuous:", hit(srv.address, x[0]))
eng.stop()

# %% a REAL process fleet behind the routing front door
# (workers are `python -m synapseml_tpu.io.serving_worker` subprocesses,
# each serving a saved copy of the pipeline)
import os
import sys

repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, repo)

from synapseml_tpu.io.serving_v2 import ProcessServingFleet

# ProcessServingFleet needs the stage importable by module path IN THE
# WORKER PROCESS (the fleet puts the repo root on the workers' PYTHONPATH);
# use the pid-echo stage shipped with the repo's tests
from tests.serving_fault_stage import PidEchoReply

# One process per chip: this process trained above, so on a TPU host it
# holds the chip, and worker processes inherit JAX_PLATFORMS — asked for an
# accelerator, each would try to open the chip at start-up and fail (loudly:
# the fleet's error carries the worker's stderr). These workers only echo
# their pid, so they are told to stay on the CPU. A fleet that serves a
# MODEL from the chip is started with n_workers=1 by a process that never
# initialises jax (see chip_smoke.py).
os.environ["JAX_PLATFORMS"] = "cpu"

fleet = ProcessServingFleet(PidEchoReply(), n_workers=3,
                            import_modules=["tests.serving_fault_stage"],
                            reply_timeout=20.0)
try:
    def raw_hit(addr):
        req = urllib.request.Request(addr + "/", data=b"ping", method="POST")
        with urllib.request.urlopen(req, timeout=20) as r:
            return r.read().decode()

    pids = {raw_hit(fleet.address) for _ in range(9)}
    print("requests served by", len(pids), "distinct worker processes")
    assert len(pids) == 3

    # %% kill a worker mid-service: the router evicts it and the service
    # keeps answering (reference HTTPv2Suite kill-a-worker contract)
    dead = fleet.kill_worker(0)
    answers = [raw_hit(fleet.address) for _ in range(9)]
    print("after kill:", len(set(answers)), "workers still answering;",
          "evicted:", fleet.router.workers_evicted)
    assert len(set(answers)) == 2
    assert dead not in fleet.routing_table()["default"]
finally:
    fleet.stop()
print("fleet stopped cleanly")
