"""Native runtime components (C++, bound via ctypes).

The wire codec parses the JSON change wire straight into columnar integer
arrays (the engine's native input), skipping per-op Python object
construction — the measured host-side bottleneck of wire ingestion.

The round converter (framecodec.cpp, `wire.changes_frame`) turns a batch's
Change objects into the round's AMW1 frame in one pass over their slots,
through the CPython API.

The shared libraries are built on demand with g++ into this package's _build/
directory; if no toolchain is available the callers fall back to the pure-
Python path transparently (`wire.parse_changes_json` and
`wire.changes_frame` return None).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import sysconfig
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")
_SRC = os.path.join(_HERE, "wirecodec.cpp")
_LIB = os.path.join(_BUILD_DIR, "libamtpuwire.so")

_lock = threading.Lock()
_lib = None
_lib_error: str | None = None


def _build_shared(src: str, lib_path: str,
                  python_api: bool = False) -> str | None:
    """Compile one .cpp into a shared library, atomically installed. A
    source over the CPython API (`python_api`) compiles against the
    running interpreter's headers."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # Compile to a process-unique temp path and rename into place: another
    # process may be loading (or also building) the library concurrently, and
    # rename is atomic while g++'s output writing is not.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp]
    include = sysconfig.get_paths()["include"] if python_api else None
    if include:
        cmd[1:1] = ["-I", include]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as exc:
        return f"toolchain unavailable: {exc}"
    if proc.returncode != 0:
        if python_api and "Python.h" in proc.stderr:
            return (f"compile failed: Python.h not found in {include} "
                    f"(the interpreter's C headers are not installed)")
        return f"compile failed: {proc.stderr[:500]}"
    try:
        os.replace(tmp, lib_path)
    except OSError as exc:
        return f"install failed: {exc}"
    return None


def load_shared(src_name: str, lib_name: str, state: dict,
                python_api: bool = False) -> "ctypes.CDLL | None":
    """Build-if-stale + load a native library; `state` caches the result
    (keys: lib, error) so each library is attempted once per process. A
    library over the CPython API (`python_api`) is loaded as a PyDLL: its
    calls keep the GIL."""
    if state.get("lib") is not None or state.get("error") is not None:
        return state.get("lib")
    src = os.path.join(_HERE, src_name)
    lib_path = os.path.join(_BUILD_DIR, lib_name)
    if not os.path.exists(lib_path) or (
            os.path.exists(src)
            and os.path.getmtime(src) > os.path.getmtime(lib_path)):
        err = _build_shared(src, lib_path, python_api)
        if err is not None:
            state["error"] = err
            return None
    try:
        state["lib"] = (ctypes.PyDLL if python_api else ctypes.CDLL)(lib_path)
    except OSError as exc:
        state["error"] = str(exc)
        return None
    return state["lib"]


def _build() -> str | None:
    return _build_shared(_SRC, _LIB)


def get_lib():
    """Load (building if needed) the native wire codec library, or None."""
    global _lib, _lib_error
    with _lock:
        if _lib is not None or _lib_error is not None:
            return _lib
        if not os.path.exists(_LIB) or (
                os.path.exists(_SRC)
                and os.path.getmtime(_SRC) > os.path.getmtime(_LIB)):
            err = _build()
            if err is not None:
                _lib_error = err
                return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError as exc:
            _lib_error = str(exc)
            return None

        lib.amtpu_parse_changes.restype = ctypes.c_void_p
        lib.amtpu_parse_changes.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
        lib.amtpu_free.argtypes = [ctypes.c_void_p]
        lib.amtpu_sizes.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_int64)]
        lib.amtpu_copy_columns.argtypes = [ctypes.c_void_p] + \
            [ctypes.c_void_p] * 15
        lib.amtpu_copy_table.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                         ctypes.c_char_p,
                                         ctypes.POINTER(ctypes.c_int32)]
        if hasattr(lib, "amtpu_linearize"):
            lib.amtpu_linearize.argtypes = [ctypes.c_int64] + \
                [ctypes.c_void_p] * 5
        if hasattr(lib, "amtpu_place_lists"):
            lib.amtpu_place_lists.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64] + \
                [ctypes.c_void_p] * 5 + [ctypes.c_int64, ctypes.c_void_p]
            lib.amtpu_place_lists.restype = ctypes.c_int64
        _lib = lib
        return _lib


def native_available() -> bool:
    return get_lib() is not None


def native_error() -> str | None:
    get_lib()
    return _lib_error
