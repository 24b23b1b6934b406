"""Worker process for the REAL multi-process rendezvous test.

Spawned by ``tests/test_multiprocess.py`` (one subprocess per simulated
host). Each worker runs ``initialize_distributed`` — a real
``jax.distributed.initialize`` against the coordinator, the analogue of the
reference's driver-socket bootstrap + native network init
(``LightGBMBase.scala:399-437``, ``TrainUtils.scala:237-296``) — builds a
GLOBAL mesh spanning every process's devices, trains one GBDT (histogram
psum) and one VW learner (pass-boundary pmean) across processes, and prints
content hashes of the results so the parent can assert bit-identical models
on every process.
"""

import hashlib
import json
import os
import sys


def main() -> int:
    pid = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    local_devices = int(sys.argv[4])

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={local_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    import numpy as np

    from synapseml_tpu.runtime.topology import (initialize_distributed,
                                                make_mesh)

    initialize_distributed(f"localhost:{port}", num_processes=nproc,
                           process_id=pid)
    assert jax.process_count() == nproc, jax.process_count()
    devs = jax.devices()
    assert len(devs) == nproc * local_devices, devs
    mesh = make_mesh(("data",), devices=devs)

    # -- GBDT: data-parallel histogram psum across PROCESSES -----------------
    rng = np.random.default_rng(0)
    x = rng.normal(size=(96, 6))
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float64)

    from synapseml_tpu.gbdt.boost import train

    booster = train({"objective": "binary", "num_iterations": 2,
                     "num_leaves": 4, "min_data_in_leaf": 2}, x, y,
                    mesh=mesh)
    gbdt_hash = hashlib.sha256(booster.to_json().encode()).hexdigest()

    # -- sparse GBDT: per-shard entry blocks + psum'd child histograms -------
    from synapseml_tpu.gbdt.sparse import CSRMatrix

    k = 3
    idx = rng.integers(0, 32, size=(96, k)).astype(np.int32)
    val = rng.integers(1, 4, size=(96, k)).astype(np.float64)
    csr = CSRMatrix(np.arange(0, 96 * k + 1, k, dtype=np.int64),
                    idx.reshape(-1), val.reshape(-1), (96, 32))
    sparse_booster = train({"objective": "binary", "num_iterations": 2,
                            "num_leaves": 4, "min_data_in_leaf": 2},
                           csr, y, mesh=mesh)
    sparse_hash = hashlib.sha256(sparse_booster.to_json().encode()).hexdigest()

    # -- lambdarank: GROUP-ALIGNED sharding across processes -----------------
    # whole queries per shard (reference repartition-by-group,
    # ``LightGBMRanker.scala:82-109``); the model must be bit-identical on
    # every process AND match the single-replica NDCG
    sizes = rng.integers(3, 9, size=16)
    n_r = int(sizes.sum())
    xr = rng.normal(size=(n_r, 6))
    rel = np.zeros(n_r)
    start = 0
    for sz in sizes:
        sc = xr[start:start + sz, 0]
        rel[start:start + sz] = np.clip(
            np.argsort(np.argsort(sc)) * 3 // sz, 0, 2)
        start += sz
    rank_params = {"objective": "lambdarank", "num_iterations": 2,
                   "num_leaves": 4, "min_data_in_leaf": 2}
    ranker = train(rank_params, xr, rel, group=sizes, mesh=mesh)
    rank_hash = hashlib.sha256(ranker.to_json().encode()).hexdigest()
    from synapseml_tpu.gbdt.boost import _metric_ndcg

    ndcg_mesh = _metric_ndcg(10)(rel, ranker.predict(xr), np.ones(n_r), sizes)
    ranker_one = train(rank_params, xr, rel, group=sizes)
    ndcg_one = _metric_ndcg(10)(rel, ranker_one.predict(xr),
                                np.ones(n_r), sizes)

    # -- VW learner: pass-boundary pmean across processes --------------------
    from synapseml_tpu.core import Table
    from synapseml_tpu.vw import VowpalWabbitClassifier, VowpalWabbitFeaturizer

    t = Table({"a": x[:, 0], "b": x[:, 1], "label": y})
    t = VowpalWabbitFeaturizer(input_cols=["a", "b"],
                               output_col="features").transform(t)
    model = VowpalWabbitClassifier(num_passes=2, num_bits=12,
                                   mesh=mesh).fit(t)
    vw_hash = hashlib.sha256(
        np.ascontiguousarray(np.asarray(model.state.w,
                                        dtype=np.float32)).tobytes()
    ).hexdigest()

    # parent parses the LAST stdout line of each worker
    print(json.dumps({"pid": pid, "process_count": jax.process_count(),
                      "n_devices": len(devs), "gbdt": gbdt_hash,
                      "sparse": sparse_hash, "vw": vw_hash,
                      "rank": rank_hash, "ndcg_mesh": ndcg_mesh,
                      "ndcg_one": ndcg_one}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
