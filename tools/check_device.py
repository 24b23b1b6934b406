#!/usr/bin/env python3
"""Accelerator probe with a bounded timeout — ``python tools/check_device.py``.

``jax.devices()`` on a mis-provisioned TPU VM has two failure modes and
both are worse than an error: with ``JAX_PLATFORMS`` unset it silently
falls back to CPU when the chip is held by another process (every
downstream number measures the wrong machine), or it HANGS inside a
wedged runtime. This probe runs the device query in a SUBPROCESS with a
hard timeout, and ``--want tpu`` sets ``JAX_PLATFORMS`` for that child so
jax must open the platform or fail: a busy chip is then exit 2 carrying
libtpu's own message, not exit 1 "backend is cpu". The child exits before
the result is printed, so the probe never keeps the chip.

Exit codes: 0 accelerator present (platform/kinds printed as one JSON
line), 1 resolved backend is CPU (or not the ``--want`` platform), 2 the
probe subprocess crashed (the wanted platform could not be opened, import
error — stderr relayed), 3 the probe TIMED OUT (the hang made loud).
``--allow-cpu`` downgrades the CPU case to exit 0 for deliberately
host-only environments.

Import discipline: this tool never imports jax in-process — only the
child does — so a hung TPU runtime cannot hang the probe itself.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the child: resolve devices, report one JSON line. Overridable via env
# for tests that need a hanging/crashing probe without a broken runtime.
_PROBE_CODE = """
import json, sys
sys.path.insert(0, {root!r})
from synapseml_tpu.runtime.topology import cluster_info
info = cluster_info()
print(json.dumps({{"platform": info.platform,
                  "device_kinds": list(info.device_kinds),
                  "num_devices": info.num_devices,
                  "num_hosts": info.num_hosts}}))
"""


def probe(timeout: float = 60.0, want: Optional[str] = None) -> dict:
    """Run the device query in a subprocess; returns the probe dict.

    ``want`` becomes the child's ``JAX_PLATFORMS``: jax then opens that
    platform or raises, instead of quietly choosing another. Raises
    ``subprocess.TimeoutExpired`` on hang and ``RuntimeError`` (with the
    child's stderr) on crash.
    """
    code = os.environ.get("SMT_DEVICE_PROBE_CODE",
                          _PROBE_CODE.format(root=_REPO_ROOT))
    env = dict(os.environ)
    if want:
        env["JAX_PLATFORMS"] = want
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"device probe subprocess failed "
                           f"(exit {r.returncode}):\n{r.stderr.strip()}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/check_device.py",
        description="Bounded-timeout accelerator probe (preflight for "
                    "bench runs and fleet bring-up).")
    ap.add_argument("--timeout", type=float, default=60.0,
                    help="seconds before a hanging device query is "
                         "declared dead (default 60)")
    ap.add_argument("--want", default=None,
                    help="require this platform specifically (tpu/gpu): "
                         "the probe child runs with JAX_PLATFORMS set to it")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="exit 0 even when the backend is cpu")
    args = ap.parse_args(argv)

    try:
        info = probe(timeout=args.timeout, want=args.want)
    except subprocess.TimeoutExpired:
        print(f"error: device query still hung after {args.timeout:.0f}s — "
              f"likely a libtpu claimed by another process or a wedged "
              f"runtime; kill the holder or reprovision", file=sys.stderr)
        return 3
    except (RuntimeError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    print(json.dumps(info))
    plat = info.get("platform", "cpu")
    ok = (plat == args.want) if args.want else (plat != "cpu")
    if ok or (plat == "cpu" and args.allow_cpu and args.want is None):
        return 0
    print(f"error: resolved backend is {plat!r}, wanted "
          f"{args.want or 'an accelerator'} (JAX_PLATFORMS="
          f"{os.environ.get('JAX_PLATFORMS', '<unset>')})", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
