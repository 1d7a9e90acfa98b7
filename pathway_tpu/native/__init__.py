"""Native host-runtime kernels (C, built lazily with the toolchain in the
image; every kernel has a bit-identical pure-Python fallback in the caller, so
a missing compiler only costs speed, never correctness).

A build is keyed on the CONTENT of its source: the shared object is named
after the source's digest, so a copied tree (which keeps no mtimes) and an
edited source both get the object that matches what is on disk, built on the
machine that runs it, and one failed compile decides nothing about the next
process."""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sysconfig

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "_build")


def load(name: str):
    """The compiled module for ``<name>.c``, building it first if no object
    for this exact source exists. Raises when the build or the load fails."""
    src = os.path.join(_DIR, f"{name}.c")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_BUILD, f"{name}-{digest}.so")
    if not os.path.exists(so_path):
        os.makedirs(_BUILD, exist_ok=True)
        import numpy as np

        tmp = f"{so_path}.{os.getpid()}.tmp"  # unique: concurrent builders don't clobber
        cmd = [
            os.environ.get("CC", "cc"), "-O2", "-shared", "-fPIC",
            f"-I{sysconfig.get_path('include')}",
            f"-I{np.get_include()}",
            src, "-o", tmp,
        ]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, so_path)  # atomic publish; racing winners are identical
    spec = importlib.util.spec_from_file_location(name, so_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def try_load(name: str):
    """Compiled module or None (any build/load failure falls back to Python)."""
    try:
        return load(name)
    except Exception:
        return None
