"""Build the native shared library: ``python -m synapseml_tpu.native.build``.

Compiles ``src/*.cpp`` into ``_smt_native.so`` next to this file with g++ (the
image's baked-in toolchain; no pybind11 — the ABI is plain C via ctypes).
"""

from __future__ import annotations

import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC_DIR = os.path.join(HERE, "src")
OUT = os.path.join(HERE, "_smt_native.so")


def build(verbose: bool = True) -> str:
    sources = sorted(
        os.path.join(SRC_DIR, f) for f in os.listdir(SRC_DIR) if f.endswith(".cpp")
    )
    # no -march=native: the binary may be loaded on another host than the
    # one that built it (a copied checkout), and hashing gains nothing
    # from it. Built beside the target and renamed into place, so a
    # process loading the library never sees a half-written file.
    tmp = f"{OUT}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-fPIC", "-shared", "-std=c++17",
        *sources, "-o", tmp,
    ]
    if verbose:
        print(" ".join(cmd), file=sys.stderr)
    try:
        subprocess.run(cmd, check=True)
        os.replace(tmp, OUT)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return OUT


if __name__ == "__main__":
    build()
