"""Importable reply stage for the cross-process serving fault test.

Worker subprocesses resolve saved stages through the stage registry, so the
class must live in an importable module (a test-function-local class
wouldn't exist in the worker's interpreter). The reply carries the worker's
PID so the test can SEE requests moving to a different process after the
kill."""

import os

import numpy as np

from synapseml_tpu.core import Param, Table, Transformer
from synapseml_tpu.io.http_schema import HTTPResponseData
from synapseml_tpu.observability.profiling import profiled_jit


class PidEchoReply(Transformer):
    """Replies 200 with this process's PID — the fault test's tracer dye."""

    reply_col = "reply"

    def _transform(self, table: Table) -> Table:
        n = table.num_rows
        replies = np.empty(n, dtype=object)
        body = str(os.getpid()).encode()
        replies[:] = [HTTPResponseData(200, "OK", entity=body)
                      for _ in range(n)]
        return table.with_column("reply", replies)


class TagEchoReply(Transformer):
    """Replies ``{tag}:{pid}:{body}`` — the hot-swap tests flip ``tag``
    across generations, so a reply PROVES which pipeline generation (and
    which worker process) served it."""

    tag = Param("generation tag echoed in every reply", str, default="g0")

    def _transform(self, table: Table) -> Table:
        n = table.num_rows
        pid = os.getpid()
        reqs = table["request"]
        replies = np.empty(n, dtype=object)
        for i, r in enumerate(reqs):
            body = (r.entity or b"").decode()
            replies[i] = HTTPResponseData(
                200, "OK", entity=f"{self.tag}:{pid}:{body}".encode())
        return table.with_column("reply", replies)


class SlowEchoReply(Transformer):
    """Replies like :class:`TagEchoReply` but sleeps ``delay_ms`` per ROW
    first — the multi-tenant chaos test's hog tenant: under open-loop
    load its queue piles up seconds of simulated service time, so
    tight-deadline requests expire IN THE QUEUE (per-model sheds) while
    the co-resident fast tenants keep answering in milliseconds."""

    tag = Param("generation tag echoed in every reply", str, default="h0")
    delay_ms = Param("simulated service time per request row (ms)", float,
                     default=20.0)

    def _transform(self, table: Table) -> Table:
        import time as _time

        n = table.num_rows
        _time.sleep(self.delay_ms * n / 1000.0)
        pid = os.getpid()
        reqs = table["request"]
        replies = np.empty(n, dtype=object)
        for i, r in enumerate(reqs):
            body = (r.entity or b"").decode()
            replies[i] = HTTPResponseData(
                200, "OK", entity=f"{self.tag}:{pid}:{body}".encode())
        return table.with_column("reply", replies)


def _burn_impl(x):
    import jax.numpy as jnp

    for _ in range(30):
        x = jnp.tanh(x @ x.T) @ x
    return x


# module-level so every process that imports this module names the entry
# point alike in its compile accounting
burn = profiled_jit(_burn_impl, name="test.lifecycle_burn")


class JitBurnReply(Transformer):
    """Runs a deliberately compile-heavy profiled jit once per batch, then
    echoes ``{pid}:{body}`` — the scale-up tests' workload: a fresh
    worker pays a multi-hundred-ms XLA compile on its first batch."""

    reply_col = "reply"

    def _transform(self, table: Table) -> Table:
        x = np.ones((48, 48), np.float32)
        burn(x)
        n = table.num_rows
        pid = os.getpid()
        reqs = table["request"]
        replies = np.empty(n, dtype=object)
        for i, r in enumerate(reqs):
            body = (r.entity or b"").decode()
            replies[i] = HTTPResponseData(
                200, "OK", entity=f"{pid}:{body}".encode())
        return table.with_column("reply", replies)


# ---------------------------------------------------------------------------
# beyond-HBM proof stage (ISSUE 19): an ONNX MLP whose replicated weights
# bust a VIRTUAL per-device HBM budget, served through the normal process
# fleet with the weights STORED row-sharded over the 3-D layout's fsdp
# axis and all-gathered transiently at each consumer
# ---------------------------------------------------------------------------

# the virtual single-device weight budget: the replicated model (~3.0 MB
# of float32 weights) does NOT fit; fsdp-stored over (fsdp=2, model=2)
# (~0.76 MB per device at rest) does
FSDP_DEVICE_BUDGET_BYTES = 2 << 20

_FSDP_D, _FSDP_H = 192, 2048
_fsdp_executors: dict = {}


def _fsdp_onnx_fn(use_fsdp):
    """Build (once per process) the beyond-HBM MLP executor — replicated
    control, or weights fsdp-stored over a ``(1, 2, 2)`` SpecLayout."""
    key = bool(use_fsdp)
    if key not in _fsdp_executors:
        import jax

        from synapseml_tpu.onnx import builder
        from synapseml_tpu.onnx.importer import OnnxFunction
        from synapseml_tpu.onnx.wire import serialize_model
        from synapseml_tpu.runtime.layout import SpecLayout

        d, h = _FSDP_D, _FSDP_H
        rng = np.random.default_rng(11)
        w1 = (rng.normal(size=(d, h)) / np.sqrt(d)).astype(np.float32)
        b1 = np.zeros(h, np.float32)
        w2 = (rng.normal(size=(h, d)) / np.sqrt(h)).astype(np.float32)
        g = builder.make_graph(
            [builder.node("MatMul", ["x", "w1"], ["h0"]),
             builder.node("Add", ["h0", "b1"], ["h1"]),
             builder.node("Relu", ["h1"], ["h2"]),
             builder.node("MatMul", ["h2", "w2"], ["y"])],
            "hbm_proof_mlp",
            [builder.value_info("x", np.float32, [None, d])],
            [builder.value_info("y", np.float32, [None, d])],
            initializers={"w1": w1, "b1": b1, "w2": w2})
        mb = serialize_model(builder.make_model(g))
        kw = {}
        if use_fsdp:
            kw["layout"] = SpecLayout.build(data=1, model=2, fsdp=2,
                                            devices=jax.devices()[:4])
        _fsdp_executors[key] = OnnxFunction(mb, dtype_policy="float32",
                                            **kw)
    return _fsdp_executors[key]


def _fsdp_resident_bytes(fn, n_layout_dev):
    """Max per-device at-rest weight bytes: sharded arrays count their
    local shard, host numpy constants count replicated on every device
    the executor would serve from."""
    per_dev: dict = {}
    for arr in fn.constants.values():
        shards = getattr(arr, "addressable_shards", None)
        if shards:
            for sh in shards:
                did = sh.device.id
                per_dev[did] = per_dev.get(did, 0) + int(sh.data.nbytes)
        else:
            for did in range(n_layout_dev):
                per_dev[did] = per_dev.get(did, 0) + int(
                    getattr(arr, "nbytes", 0))
    return max(per_dev.values())


class FsdpOnnxReply(Transformer):
    """Serves the beyond-HBM MLP and replies ``{resident}:{checksum}`` —
    per-device at-rest weight bytes measured INSIDE the worker process
    that holds them, plus an output checksum so the test can pin
    replicated-vs-fsdp numeric parity across fleets."""

    use_fsdp = Param("store weights row-sharded over the fsdp axis",
                     bool, default=False)

    def _transform(self, table: Table) -> Table:
        fn = _fsdp_onnx_fn(self.use_fsdp)
        x = np.linspace(-1.0, 1.0, 8 * _FSDP_D,
                        dtype=np.float32).reshape(8, _FSDP_D)
        y = np.asarray(fn({"x": x})["y"], np.float32)
        resident = _fsdp_resident_bytes(fn, 4 if self.use_fsdp else 1)
        body = f"{resident}:{float(np.abs(y).sum()):.4f}".encode()
        n = table.num_rows
        replies = np.empty(n, dtype=object)
        replies[:] = [HTTPResponseData(200, "OK", entity=body)
                      for _ in range(n)]
        return table.with_column("reply", replies)
