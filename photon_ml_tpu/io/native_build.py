"""Lazy g++ build + ctypes load for the native runtime pieces.

One cached .so per (source file, content hash) under the user cache dir.
The expected failures (no compiler, bad toolchain, unloadable library)
degrade to ``None`` with a WARNING, so every native component keeps a
pure-Python fallback and a run that took it says so. Set
PHOTON_ML_TPU_NATIVE=0 to force the fallbacks (useful for differential
testing).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import tempfile
import zlib
from typing import Callable, Optional

logger = logging.getLogger(__name__)

NATIVE_ENV = "PHOTON_ML_TPU_NATIVE"

_REPO_NATIVE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)

_cache: dict = {}


def native_enabled() -> bool:
    return os.environ.get(NATIVE_ENV, "1") not in ("0", "false", "no")


def build_cached(source_name: str, extra_flags: tuple = ()) -> str:
    """Compile native/<source_name> once into the content-hashed user cache
    and return the .so path. Raises ``OSError`` (no source, no g++) or
    ``subprocess.CalledProcessError`` (compile error).

    The compiler writes INSIDE the cache directory and the result is
    renamed into place: a rename from a system temp dir fails with EXDEV
    when the two sit on different filesystems."""
    source = os.path.join(_REPO_NATIVE, source_name)
    with open(source, "rb") as f:
        # tag covers source AND flags: a flag fix must invalidate the
        # cached .so even when the source is unchanged
        tag = f"{zlib.crc32(f.read() + repr(extra_flags).encode()):08x}"
    stem = os.path.splitext(source_name)[0]
    cache_dir = os.path.join(
        os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache")),
        "photon_ml_tpu",
    )
    os.makedirs(cache_dir, exist_ok=True)
    lib_path = os.path.join(cache_dir, f"lib{stem}-{tag}.so")
    if os.path.exists(lib_path):
        return lib_path
    fd, tmp_lib = tempfile.mkstemp(
        dir=cache_dir, prefix=f"lib{stem}-", suffix=".so.tmp"
    )
    os.close(fd)
    try:
        # libraries (-lz ...) must FOLLOW the source file or GNU ld
        # drops them and the .so carries undefined symbols
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
             "-o", tmp_lib, source, *extra_flags],
            check=True,
            capture_output=True,
        )
        os.replace(tmp_lib, lib_path)
    finally:
        if os.path.exists(tmp_lib):
            os.unlink(tmp_lib)
    return lib_path


def load_native_lib(
    source_name: str,
    configure: Callable[[ctypes.CDLL], None],
    extra_flags: tuple = (),
) -> Optional[ctypes.CDLL]:
    """Compile native/<source_name> once (content-hashed cache) and load it;
    ``configure`` sets restype/argtypes. Returns None — with a warning —
    when the library cannot be built or loaded."""
    key = source_name
    if key in _cache:
        return _cache[key]
    if not native_enabled():
        _cache[key] = None
        return None
    try:
        lib = ctypes.CDLL(build_cached(source_name, extra_flags))
        configure(lib)
        _cache[key] = lib
    except (OSError, subprocess.CalledProcessError, AttributeError) as e:
        # expected degradations: no source file / no g++ / compile error /
        # CDLL load failure / a library missing an entry point. Anything
        # else (e.g. a ctypes misuse bug in ``configure``) raises.
        detail = e
        if isinstance(e, subprocess.CalledProcessError) and e.stderr:
            detail = e.stderr.decode(errors="replace").strip().splitlines()[-1]
        logger.warning(
            "native %s unavailable (%s: %s); using the pure-Python fallback",
            source_name, type(e).__name__, detail,
        )
        _cache[key] = None
    return _cache[key]
