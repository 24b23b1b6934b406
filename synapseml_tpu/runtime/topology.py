"""Device / host topology discovery — the ``ClusterUtil`` equivalent.

The reference discovers Spark executors and tasks-per-executor to size its native
process groups (``core/.../core/utils/ClusterUtil.scala:20-176``: ``getNumTasksPerExec``,
``getExecutors``, ``getDriverHost``). On TPU the analogous facts come from the JAX
runtime and pod-slice metadata: local/global device counts, process (host) index/count,
and the ICI mesh shape. This module centralizes them and builds ``jax.sharding.Mesh``
objects that the distributed trainers (GBDT histogram ``psum``, linear ``pmean``) and
serving layer consume.

Multi-host bring-up (the reference's driver-socket rendezvous,
``LightGBMBase.scala:399-437``) maps to ``jax.distributed.initialize`` — coordinator
address instead of driver ServerSocket, with the same retry-with-backoff semantics.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "ClusterInfo",
    "cluster_info",
    "make_mesh",
    "best_mesh_shape",
    "initialize_distributed",
    "device_kind",
    "is_tpu",
    "open_requested_platform",
    "requested_platform",
    "require_backend",
    "shard_map_compat",
]


def shard_map_compat(f, mesh, in_specs, out_specs, check: bool = True):
    """``jax.shard_map`` with jax imported at call time, so the modules
    that build mesh-distributed programs stay jax-free at import.

    ``check`` is jax's ``check_vma`` (on by default, as in jax); the
    trainers pass ``check=False`` where the body's collectives are
    known-good and the check costs tracing time."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


_logger = logging.getLogger("synapseml_tpu.topology")


@dataclasses.dataclass(frozen=True)
class ClusterInfo:
    """Snapshot of the accelerator topology (ClusterUtil.getExecutors analogue)."""

    num_devices: int
    local_num_devices: int
    num_hosts: int
    host_index: int
    platform: str
    device_kinds: Tuple[str, ...]

    @property
    def devices_per_host(self) -> int:
        return self.local_num_devices


def cluster_info() -> ClusterInfo:
    import jax

    devs = jax.devices()
    return ClusterInfo(
        num_devices=jax.device_count(),
        local_num_devices=jax.local_device_count(),
        num_hosts=jax.process_count(),
        host_index=jax.process_index(),
        platform=devs[0].platform if devs else "cpu",
        device_kinds=tuple(sorted({d.device_kind for d in devs})),
    )


def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def is_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def require_backend(want: Optional[str] = None, *,
                    allow_cpu: bool = False) -> "ClusterInfo":
    """Assert the resolved jax backend is an accelerator — LOUDLY.

    jax falls back to CPU silently when the TPU runtime is absent,
    unclaimed, or shadowed by ``JAX_PLATFORMS`` — and every benchmark,
    SLO probe, and training job downstream then measures the wrong
    machine while reporting success. This is the fail-fast gate: call it
    once at process start (bench refuses CPU rounds through it) and a
    mis-provisioned environment dies with a diagnostic naming what was
    found and which knobs select the backend, instead of publishing
    CPU numbers.

    ``want`` pins a specific platform (``"tpu"``, ``"gpu"``); the default
    accepts any non-CPU accelerator. ``allow_cpu=True`` turns the check
    into a pass-through (the explicit opt-in path — tests, laptops).
    Returns the :class:`ClusterInfo` snapshot so callers can stamp it.
    """
    info = cluster_info()
    if allow_cpu:
        return info
    plat = info.platform
    if plat == "cpu" or (want is not None and plat != want):
        wanted = want or "an accelerator (tpu/gpu)"
        raise RuntimeError(
            f"resolved jax backend is {plat!r} "
            f"(kinds={list(info.device_kinds)}, "
            f"devices={info.num_devices}) but {wanted} is required.\n"
            f"  JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '<unset>')}\n"
            f"  XLA_FLAGS={os.environ.get('XLA_FLAGS', '<unset>')}\n"
            f"likely causes: TPU runtime not installed / already claimed "
            f"by another process / JAX_PLATFORMS pinning cpu. Probe with "
            f"`python tools/check_device.py`; pass allow_cpu=True (bench: "
            f"--allow-cpu) only to deliberately measure the host.")
    return info


def requested_platform(env=None) -> Optional[str]:
    """The platform ``JAX_PLATFORMS`` (in ``env``, default this process's
    environment) asks jax for first, lower-cased; None when it asks for
    none (unset or empty)."""
    value = (os.environ if env is None else env).get("JAX_PLATFORMS", "")
    return value.split(",")[0].strip().lower() or None


def open_requested_platform() -> Optional["ClusterInfo"]:
    """Open the accelerator ``JAX_PLATFORMS`` asks for — now, not at the
    first jit. Returns its :class:`ClusterInfo`, or None when the variable
    asks for no accelerator (unset, empty or ``cpu``) and jax is left alone.

    With ``JAX_PLATFORMS`` unset jax treats a TPU it cannot open (held by
    another process) as a log line and computes on the CPU. A worker
    process calls this before its start-up handshake: a launcher that asked
    for the chip then gets either a worker that holds it or jax's own
    error, never a worker that answers from a CPU it fell back to."""
    if requested_platform() in (None, "cpu"):
        return None
    return require_backend()


def best_mesh_shape(n_devices: int, n_axes: int) -> Tuple[int, ...]:
    """Factor ``n_devices`` into ``n_axes`` balanced axes, sorted largest-first.

    Greedy prime-factor packing: factors (largest first) go to the axis with the
    smallest current product, so e.g. 12 over 3 axes -> (3, 2, 2), 8 over 3 -> (2, 2, 2).
    Used when the caller asks for e.g. a ('data','model') mesh without specifying the
    split; mirrors how the reference derives numTasksPerExec from cores/taskCpus
    (``ClusterUtil.scala:20-105``) — sensible defaults, overridable.
    """
    factors: List[int] = []
    rem = n_devices
    d = 2
    while d * d <= rem:
        while rem % d == 0:
            factors.append(d)
            rem //= d
        d += 1
    if rem > 1:
        factors.append(rem)
    shape = [1] * n_axes
    for f in sorted(factors, reverse=True):
        shape[int(np.argmin(shape))] *= f
    return tuple(sorted(shape, reverse=True))


def make_mesh(
    axis_names: Sequence[str] = ("data",),
    shape: Optional[Sequence[int]] = None,
    devices: Optional[Sequence] = None,
):
    """Build a ``jax.sharding.Mesh`` over available devices.

    ``shape=None`` puts all devices on the first axis (pure data parallelism — the only
    parallelism the reference's trainers use, SURVEY.md §2.1) and 1 on the rest.
    """
    import jax
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else list(jax.devices())
    axis_names = tuple(axis_names)
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    shape = tuple(int(s) for s in shape)
    total = int(np.prod(shape))
    if total > len(devs):
        raise ValueError(f"mesh shape {shape} needs {total} devices, have {len(devs)}")
    arr = np.array(devs[:total]).reshape(shape)
    return Mesh(arr, axis_names)


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    retries: int = 5,
) -> None:
    """Multi-host rendezvous: ``jax.distributed.initialize`` with backoff retry.

    Replaces the reference's driver-socket rendezvous + exponential-backoff native
    network init (``TrainUtils.scala:237-296``). No-ops when single-host and no
    coordinator is configured.
    """
    import jax

    from ..core.fault import retry_with_backoff

    if coordinator_address is None and "JAX_COORDINATOR_ADDRESS" not in os.environ:
        if num_processes in (None, 1):
            _logger.debug("single-host: skipping jax.distributed.initialize")
            return

    def _init():
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )

    retry_with_backoff(_init, retries=retries, initial_delay_s=1.0, max_delay_s=30.0)
