"""Test harness configuration.

Multi-chip behavior is tested on a *virtual 8-device CPU mesh* (no TPU hardware in unit
CI), mirroring how the reference simulates multi-task distribution with `local[*]`
Spark (reference: ``core/src/test/.../SparkSessionFactory.scala`` — SURVEY.md §4
"Multi-node without a real cluster"). Flags must be set before jax initializes.
"""

import os

# unit tests always run on the virtual 8-device CPU mesh, whatever the
# ambient environment points JAX at
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# no persistent compile cache in a test session (worker subprocesses inherit
# this): the package would place it under <checkout>/.jax_cache
# (runtime/compile_cache.py), entries are model-sized, and the chip tool
# copies the checkout as it stands on disk
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def dryrun_multichip_8_stdout():
    """What ``__graft_entry__.dryrun_multichip(8)`` prints, run ONCE per
    session in this process (8 virtual devices are visible, so it runs
    in-process): test_runtime pins that it runs, test_multichip_artifact
    what it stamps."""
    import contextlib
    import io
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    try:
        import __graft_entry__ as g

        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            g.dryrun_multichip(8)
    finally:
        sys.path.remove(repo)
    return out.getvalue()


@pytest.fixture(scope="session")
def eight_device_mesh():
    import jax
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("data", "model"))
