import threading

import numpy as np
import pytest

from synapseml_tpu.runtime import (
    SharedVariable,
    best_mesh_shape,
    clear_shared_pool,
    cluster_info,
    make_mesh,
    shared_singleton,
)


def test_cluster_info_virtual_devices():
    info = cluster_info()
    assert info.num_devices == 8  # conftest forces 8 CPU devices
    assert info.num_hosts == 1
    assert info.platform == "cpu"


def test_make_mesh_default_1d():
    mesh = make_mesh(("data",))
    assert mesh.shape == {"data": 8}


def test_make_mesh_2d():
    mesh = make_mesh(("data", "model"), shape=(4, 2))
    assert mesh.shape == {"data": 4, "model": 2}


def test_make_mesh_too_big_raises():
    with pytest.raises(ValueError, match="devices"):
        make_mesh(("data",), shape=(1000,))


def test_best_mesh_shape():
    assert np.prod(best_mesh_shape(8, 2)) == 8
    assert np.prod(best_mesh_shape(12, 3)) == 12
    assert best_mesh_shape(8, 1) == (8,)


def test_shared_singleton_runs_factory_once():
    clear_shared_pool("t1-")
    calls = []

    def factory():
        calls.append(1)
        return object()

    objs = []
    threads = [
        threading.Thread(target=lambda: objs.append(shared_singleton("t1-key", factory)))
        for _ in range(8)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(calls) == 1
    assert all(o is objs[0] for o in objs)


def test_shared_variable():
    sv = SharedVariable(lambda: [])
    assert sv.get() is sv.get()


def test_psum_over_mesh():
    """Histogram-allreduce pattern the GBDT engine uses: psum over the data axis."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from synapseml_tpu.runtime.topology import shard_map_compat

    mesh = make_mesh(("data",))
    x = jnp.arange(8.0)

    def local_hist(xs):
        return jax.lax.psum(jnp.sum(xs, keepdims=True), "data")

    f = shard_map_compat(local_hist, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_best_mesh_shape_balanced():
    assert best_mesh_shape(12, 3) == (3, 2, 2)
    assert best_mesh_shape(8, 3) == (2, 2, 2)
    assert best_mesh_shape(64, 2) == (8, 8)
    assert best_mesh_shape(7, 2) == (7, 1)


def test_clear_shared_pool_keeps_locks():
    from synapseml_tpu.runtime.shared import _key_locks

    clear_shared_pool("t2-")
    shared_singleton("t2-key", lambda: 1)
    assert "t2-key" in _key_locks
    clear_shared_pool("t2-")
    assert "t2-key" in _key_locks  # lock retained, value cleared
    assert shared_singleton("t2-key", lambda: 2) == 2


# -- canonical sharding layout (runtime/layout.py) ----------------------------------

def test_spec_layout_build_2d():
    from synapseml_tpu.runtime import SpecLayout

    lay = SpecLayout.build(model=2)
    assert lay.describe() == {"data": 4, "model": 2}
    assert lay.data_size == 4 and lay.model_size == 2
    assert not lay.is_single_device


def test_spec_layout_default_is_data_parallel():
    from synapseml_tpu.runtime import SpecLayout

    lay = SpecLayout.build()
    assert lay.describe() == {"data": 8, "model": 1}


def test_spec_layout_degrades_to_single_chip():
    import jax

    from synapseml_tpu.runtime import SpecLayout

    lay = SpecLayout.build(devices=jax.devices()[:1])
    assert lay.describe() == {"data": 1, "model": 1}
    assert lay.is_single_device
    # specs still resolve on the (1, 1) mesh
    x = lay.put(np.arange(4.0), lay.batch())
    np.testing.assert_array_equal(np.asarray(x), np.arange(4.0))


def test_spec_layout_1d_when_model_axis_unpopulated():
    from synapseml_tpu.runtime import SpecLayout

    lay = SpecLayout.build(data_axis="seq", model_axis=None)
    assert lay.axis_names == ("seq",)
    assert lay.model_size == 1
    assert lay.describe() == {"seq": 8}


def test_spec_layout_indivisible_model_raises():
    from synapseml_tpu.runtime import SpecLayout

    with pytest.raises(ValueError, match="divide"):
        SpecLayout.build(model=3)


def test_spec_layout_role_specs():
    from jax.sharding import PartitionSpec as P

    from synapseml_tpu.runtime import SpecLayout, as_layout

    lay = SpecLayout.build(data=4, model=2)
    assert lay.batch() == P("data")
    assert lay.batch(rank=4, dim=1) == P(None, "data", None, None)
    assert lay.replicated() == P()
    assert lay.col_weight() == P(None, "model")
    assert lay.col_weight(rank=2, dim=0) == P("model", None)
    assert lay.conv_weight() == P("model", None, None, None)
    assert lay.feature_blocks() == P("data", "model")
    # 1-D degradation: model-axis roles fall back to replication
    lay1 = as_layout(make_mesh(("data",)))
    assert lay1.model_axis is None
    assert lay1.col_weight() == P(None, None)
    assert lay1.feature_blocks() == P("data")


def test_as_layout_roundtrip_and_from_mesh():
    from synapseml_tpu.runtime import SpecLayout, as_layout

    mesh2d = make_mesh(("data", "model"), shape=(4, 2))
    lay = as_layout(mesh2d)
    assert (lay.data_axis, lay.model_axis) == ("data", "model")
    assert as_layout(lay) is lay
    seq = as_layout(make_mesh(("seq",)), data_axis="seq")
    assert seq.data_axis == "seq" and seq.model_axis is None
    with pytest.raises(ValueError, match="no 'nope' axis"):
        SpecLayout.from_mesh(mesh2d, data_axis="nope")


def test_spec_layout_hashable_for_program_caches():
    from synapseml_tpu.runtime import SpecLayout

    a = SpecLayout.build(data=4, model=2)
    b = SpecLayout.build(data=4, model=2)
    assert a == b and hash(a) == hash(b)
    assert len({a, b}) == 1


def test_spec_layout_shard_map_psum_both_axes():
    """Feature-parallel reduce shape: psum over (data, model) reassembles
    disjoint per-axis partials — the grow_tree histogram contract."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.runtime import SpecLayout

    lay = SpecLayout.build(data=4, model=2)

    def body(x):
        j = jax.lax.axis_index("model")
        part = jnp.where(j == 0, jnp.sum(x), 0.0).reshape(1)
        return jax.lax.psum(part, ("data", "model"))

    f = lay.shard_map(body, in_specs=lay.batch(), out_specs=lay.batch(),
                      check=False)
    # 4 data shards x 1 output row each; every shard sees the global total
    out = np.asarray(f(jnp.arange(8.0)))
    np.testing.assert_allclose(out, np.full(4, 28.0))


def test_spec_layout_persists_through_save_load(tmp_path):
    """A stage carrying a SpecLayout ComplexParam (ONNXModel.sharding_layout,
    estimator mesh=) must save/load: the layout persists as axis names +
    sizes and rebuilds over the LOADING process's devices, degrading to
    what fits (a 1-chip worker can load an 8-chip trainer's pipeline)."""
    from synapseml_tpu.runtime import SpecLayout

    lay = SpecLayout.build(data=4, model=2)
    back = SpecLayout.from_state_dict(lay.state_dict())
    assert back == lay
    seq = SpecLayout.build(data_axis="seq", model_axis=None)
    back_seq = SpecLayout.from_state_dict(seq.state_dict())
    assert back_seq == seq
    # through the real serialization layer, on a stage
    import synapseml_tpu as smt
    from synapseml_tpu.gbdt import LightGBMClassifier

    clf = LightGBMClassifier(num_iterations=2, mesh=lay)
    clf.save(str(tmp_path / "e"))
    clf2 = smt.load_stage(str(tmp_path / "e"))
    assert clf2.mesh == lay
    # degradation: a saved shape bigger than the live device count shrinks
    big = dict(lay.state_dict(), data=16, model=4)
    degraded = SpecLayout.from_state_dict(big)
    assert degraded.n_devices <= 8


def test_spec_layout_build_3d_and_fsdp_specs():
    from jax.sharding import PartitionSpec as P

    from synapseml_tpu.runtime import SpecLayout

    lay = SpecLayout.build(data=2, model=2, fsdp=2)
    assert lay.describe() == {"data": 2, "fsdp": 2, "model": 2}
    assert (lay.data_size, lay.fsdp_size, lay.model_size) == (2, 2, 2)
    assert lay.n_devices == 8
    # STORAGE stacks the fsdp axis onto the point-of-use spec
    assert lay.fsdp_weight(rank=1) == P("fsdp")
    assert lay.fsdp_weight(rank=2, dim=0,
                           use_spec=lay.col_weight(rank=2)) == \
        P("fsdp", "model")
    # a dim already model-sharded stores jointly over (fsdp, model)
    assert lay.fsdp_weight(rank=2, dim=1, use_spec=P(None, "model")) == \
        P(None, ("fsdp", "model"))
    assert lay.embed_weight() == P(("fsdp", "model"), None)
    # use_spec strips exactly the fsdp axis: what the consumer math wants
    assert lay.use_spec(P("fsdp", "model")) == P(None, "model")
    assert lay.use_spec(P(None, ("fsdp", "model"))) == P(None, "model")
    assert lay.use_spec(P("fsdp")) == P(None)
    # 2-D degradation: storage collapses to the use spec, adopting call
    # sites stay correct without a 3-D mesh
    lay2 = SpecLayout.build(data=4, model=2)
    assert lay2.fsdp_size == 1 and lay2.fsdp_axis is None
    assert lay2.fsdp_weight(rank=2, dim=0,
                            use_spec=P(None, "model")) == P(None, "model")
    assert lay2.use_spec(P(None, "model")) == P(None, "model")


def test_spec_layout_fsdp_build_validation():
    from synapseml_tpu.runtime import SpecLayout

    with pytest.raises(ValueError, match="model_axis"):
        SpecLayout.build(model_axis=None, fsdp=2)
    with pytest.raises(ValueError, match="divide"):
        SpecLayout.build(model=2, fsdp=3)


def test_spec_layout_fsdp_gather_parity():
    """Row-sharded storage + all-gather-on-use computes exactly what the
    replicated path computes; the stored argument stays fsdp-sharded."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.runtime import SpecLayout

    lay = SpecLayout.build(data=2, model=2, fsdp=2)
    rng = np.random.default_rng(3)
    w = rng.normal(size=(8, 6)).astype(np.float32)
    x = rng.normal(size=(4, 8)).astype(np.float32)
    stored = lay.fsdp_weight(rank=2, dim=0, use_spec=lay.col_weight(rank=2))
    w_dev = lay.put(w, stored)
    assert w_dev.sharding.spec == stored

    @jax.jit
    def f(xv, wv):
        return xv @ lay.gather_for_use(wv, stored)

    np.testing.assert_array_equal(np.asarray(f(x, w_dev)), x @ w)
    # storage is untouched by use: still row-sharded at rest
    assert w_dev.sharding.spec == stored
    # the explicit eager path lands on the use spec
    g = lay.donated_gather(stored)
    gathered = g(w_dev)
    assert gathered.sharding.spec == lay.use_spec(stored)
    np.testing.assert_array_equal(np.asarray(gathered), w)
    # per-device at-rest residency really is nbytes / (fsdp * model)
    shard_bytes = {s.device.id: s.data.nbytes
                   for s in w_dev.addressable_shards}
    assert max(shard_bytes.values()) == w.nbytes // 4
    # no-op identity on a 2-D layout: same call sites, no fsdp axis
    lay2 = SpecLayout.build(data=4, model=2)
    stored2 = lay2.fsdp_weight(rank=2, dim=0,
                               use_spec=lay2.col_weight(rank=2))
    w2 = lay2.put(w, stored2)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(
            lambda v: lay2.gather_for_use(v, stored2))(w2)), w)


def test_spec_layout_3d_save_load_and_degradation(caplog):
    import logging

    from synapseml_tpu.runtime import SpecLayout

    lay = SpecLayout.build(data=2, model=2, fsdp=2)
    back = SpecLayout.from_state_dict(lay.state_dict())
    assert back == lay
    # pre-fsdp artifacts stay byte-identical: no fsdp keys on 2-D layouts
    assert "fsdp" not in SpecLayout.build(data=4, model=2).state_dict()
    # degradation collapses data first, keeps the storage shape: a saved
    # (4,2,2) on this 8-device host serves as (2,2,2)
    big = dict(lay.state_dict(), data=4)
    with caplog.at_level(logging.WARNING, "synapseml_tpu.layout"):
        degraded = SpecLayout.from_state_dict(big)
    assert degraded.describe() == {"data": 2, "fsdp": 2, "model": 2}
    assert any("degrading" in r.message for r in caplog.records)


def test_spec_layout_3d_degrades_to_single_chip_and_serves(monkeypatch,
                                                           caplog):
    """A (2,2,2)-trained artifact on a ONE-chip worker: the fsdp axis
    collapses entirely (warning logged), the layout lands at (1, 1), and
    the fsdp helpers keep working as no-ops — the stored weight is just
    resident and gather_for_use is the identity, so serving code written
    against the 3-D roles runs unchanged."""
    import logging

    import jax
    import jax.numpy as jnp

    from synapseml_tpu.runtime import SpecLayout

    saved = SpecLayout.build(data=2, model=2, fsdp=2).state_dict()
    one = jax.devices()[:1]
    real_devices = jax.devices
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: one if not a and not k
                        else real_devices(*a, **k))
    with caplog.at_level(logging.WARNING, "synapseml_tpu.layout"):
        degraded = SpecLayout.from_state_dict(saved)
    assert any("degrading" in r.message for r in caplog.records)
    assert degraded.describe() == {"data": 1, "model": 1}
    assert degraded.n_devices == 1 and degraded.fsdp_axis is None
    # the 3-D storage role degrades to the bare use-spec (no fsdp factor;
    # the size-1 model axis is effectively replication)…
    assert degraded.fsdp_weight(rank=2, dim=0,
                                use_spec=degraded.col_weight(rank=2)) == \
        degraded.col_weight(rank=2)
    # …and the gather is the identity, so a serve still computes
    w = degraded.put(jnp.arange(12.0).reshape(4, 3),
                     degraded.fsdp_weight(2, 0, degraded.col_weight(2)))
    x = jnp.ones((2, 4))

    @jax.jit
    def f(x, w):
        return x @ degraded.gather_for_use(
            w, degraded.col_weight(2))

    np.testing.assert_allclose(np.asarray(f(x, w)),
                               np.asarray(x @ jnp.arange(12.0).reshape(4, 3)))


def test_graft_entry_dryrun_multichip_in_process(dryrun_multichip_8_stdout):
    """The driver's multi-chip gate: with 8 visible devices the impl runs
    in-process, every assert inside it holding (the virtual-mesh
    subprocess, taken only when the CPU was asked for, is exercised by the
    driver itself)."""
    assert "DEVICES platform=cpu kind='cpu' count=8" in \
        dryrun_multichip_8_stdout
    assert "ULYSSES_FLASH seq=1024 interpret=True" in \
        dryrun_multichip_8_stdout


def test_graft_entry_dryrun_does_not_slide_onto_a_cpu_mesh(monkeypatch):
    """Too few devices is an error unless the caller asked for the CPU: a
    run on a one-chip host must not re-run itself on virtual CPU devices
    and report success."""
    import sys
    sys.path.insert(0, "/root/repo")
    try:
        import __graft_entry__ as g
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # the chip host's
        with pytest.raises(RuntimeError, match="ask for it: JAX_PLATFORMS=cpu"):
            g.dryrun_multichip(64)
    finally:
        sys.path.remove("/root/repo")


# ---------------------------------------------------------------------------
# loud device acquisition: require_backend + tools/check_device.py
# ---------------------------------------------------------------------------

def test_require_backend_allow_cpu_passes_through():
    from synapseml_tpu.runtime.topology import require_backend

    info = require_backend(allow_cpu=True)  # conftest pins cpu
    assert info.platform == "cpu" and info.num_devices >= 1


def test_require_backend_refuses_cpu_with_diagnostic():
    from synapseml_tpu.runtime.topology import require_backend

    with pytest.raises(RuntimeError) as ei:
        require_backend()
    msg = str(ei.value)
    # the diagnostic must name what was found and where to go next
    assert "'cpu'" in msg
    assert "JAX_PLATFORMS" in msg and "XLA_FLAGS" in msg
    assert "tools/check_device.py" in msg and "allow_cpu" in msg


def test_require_backend_want_pins_platform():
    from synapseml_tpu.runtime.topology import require_backend

    with pytest.raises(RuntimeError, match="tpu"):
        require_backend(want="tpu")


def test_open_requested_platform_leaves_jax_alone_unless_asked(monkeypatch):
    """Worker processes call this before their handshake: no accelerator
    asked for (unset, empty, cpu) -> None and no device work; asked for ->
    the accelerator or an error (here: the conftest CPU is not one)."""
    from synapseml_tpu.runtime.topology import open_requested_platform

    for value in (None, "", "cpu", "cpu,tpu"):
        if value is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", value)
        assert open_requested_platform() is None
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="resolved jax backend is 'cpu'"):
        open_requested_platform()


def _check_device_main(monkeypatch, probe_code, args):
    import importlib
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    monkeypatch.syspath_prepend(tools)
    monkeypatch.setenv("SMT_DEVICE_PROBE_CODE", probe_code)
    check_device = importlib.import_module("check_device")
    return check_device.main(list(args))


_FAKE_CPU = ('import json; print(json.dumps({"platform": "cpu", '
             '"device_kinds": ["cpu"], "num_devices": 1, "num_hosts": 1}))')
_FAKE_TPU = ('import json; print(json.dumps({"platform": "tpu", '
             '"device_kinds": ["TPU v4"], "num_devices": 8, '
             '"num_hosts": 1}))')


def test_check_device_exit_codes(monkeypatch, capsys):
    # accelerator present -> 0; cpu -> 1 unless --allow-cpu; wrong
    # platform under --want -> 1
    assert _check_device_main(monkeypatch, _FAKE_TPU, []) == 0
    assert _check_device_main(monkeypatch, _FAKE_CPU, []) == 1
    assert _check_device_main(monkeypatch, _FAKE_CPU, ["--allow-cpu"]) == 0
    assert _check_device_main(monkeypatch, _FAKE_TPU,
                              ["--want", "gpu"]) == 1
    out = capsys.readouterr()
    assert '"platform": "tpu"' in out.out  # probe JSON relayed


def test_check_device_want_sets_the_platform_for_the_probe_child(
        monkeypatch, capsys):
    """``--want tpu`` makes jax in the probe child open that platform or
    fail (exit 2 with the runtime's own message) — never resolve to
    another backend quietly."""
    code = ('import json, os; print(json.dumps({"platform": '
            'os.environ["JAX_PLATFORMS"], "device_kinds": [], '
            '"num_devices": 1, "num_hosts": 1}))')
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert _check_device_main(monkeypatch, code, ["--want", "tpu"]) == 0
    assert '"platform": "tpu"' in capsys.readouterr().out
    # without --want the child keeps the ambient value
    assert _check_device_main(monkeypatch, code, ["--allow-cpu"]) == 0
    assert '"platform": "cpu"' in capsys.readouterr().out


def test_check_device_probe_crash_is_exit_2(monkeypatch, capsys):
    code = 'import sys; sys.exit("libtpu_discovery failed")'
    assert _check_device_main(monkeypatch, code, []) == 2
    assert "libtpu_discovery failed" in capsys.readouterr().err


def test_check_device_hang_is_exit_3_not_a_hang(monkeypatch, capsys):
    code = "import time; time.sleep(300)"
    assert _check_device_main(monkeypatch, code, ["--timeout", "1"]) == 3
    assert "hung" in capsys.readouterr().err
