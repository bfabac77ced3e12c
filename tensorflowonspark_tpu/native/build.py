"""On-demand g++ build of the native libraries (shared helper).

pybind11 is not available in this environment, so every native component is
a plain C-ABI shared library built with the baked-in compiler and consumed
via ctypes.  Concurrent node processes may race to build: compile into a
temp file and ``os.replace`` (atomic) so every racer ends with a whole
library.

The built library is named after a hash of its source text and compiler
flags, so a library is only ever reused for the exact source it was built
from: the build directory is git-ignored but travels with a copied
checkout, and a file-time comparison cannot tell a library built from
other source.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile

_CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, "_native_build")
_BASE_FLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")


def build_native_lib(src_path: str, lib_name: str,
                     extra_flags: tuple = ()) -> str:
    cache = os.path.abspath(_CACHE_DIR)
    os.makedirs(cache, exist_ok=True)
    flags = (*_BASE_FLAGS, *extra_flags)
    with open(src_path, "rb") as f:
        digest = hashlib.sha256(f.read() + "\0".join(flags).encode())
    stem, ext = os.path.splitext(lib_name)
    lib_path = os.path.join(cache, f"{stem}-{digest.hexdigest()[:16]}{ext}")
    if os.path.exists(lib_path):
        return lib_path
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=cache)
    os.close(fd)
    try:
        subprocess.run(
            ["g++", *_BASE_FLAGS, src_path, "-o", tmp, *extra_flags],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return lib_path
