"""Loader for the native C++ runtime library (native/*.cc).

Reference analogs: the pybind layer (paddle/fluid/pybind/) binding phi's C++
runtime into python. Here the runtime pieces that must be native (socket
rendezvous, watchdog thread, shm transport) live in
libpaddle_tpu_native.so, bound via ctypes; everything compute-side is XLA.

The library is a build product, never a tracked file: it is built with
`make -C native` on first use from the sources of THIS checkout, and the
digest of those sources is stamped beside it. A library whose stamp does not
match the sources is rebuilt — not judged by mtime, which a copy of the tree
resets. Only where no toolchain exists is a pre-existing library loaded, and
then with a warning; all consumers have pure-python fallbacks for when there
is none at all.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
import warnings

_lib = None
_lock = threading.Lock()
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SO = os.path.join(_NATIVE_DIR, "libpaddle_tpu_native.so")
_STAMP = _SO + ".srchash"


def _source_digest() -> str:
    h = hashlib.sha256()
    for f in sorted(os.listdir(_NATIVE_DIR)):
        if f.endswith((".cc", ".h")) or f == "Makefile":
            h.update(f.encode())
            with open(os.path.join(_NATIVE_DIR, f), "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def _built_from(digest: str) -> bool:
    """True if the .so on disk was built from sources with this digest."""
    try:
        with open(_STAMP) as f:
            return os.path.exists(_SO) and f.read().strip() == digest
    except OSError:
        return False


def _build(digest: str) -> bool:
    """make the library and stamp it; False (with the compiler's words)
    when the build fails."""
    try:
        os.remove(_SO)  # make must not judge a copied tree's .so up to date
    except OSError:
        pass
    r = subprocess.run(["make", "-C", _NATIVE_DIR], capture_output=True,
                       text=True, timeout=120)
    if r.returncode != 0:
        warnings.warn("native runtime build failed; running without it:\n"
                      + (r.stderr or r.stdout)[-600:], RuntimeWarning)
        return False
    with open(_STAMP, "w") as f:
        f.write(digest)
    return True


def load():
    """Return the ctypes lib built from this checkout's sources; None if
    unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        # An exclusive file lock serializes concurrent ranks on one host
        # (all ranks' first load() would otherwise race `make` against a
        # sibling's dlopen); held through CDLL so no sibling truncates the
        # .so mid-map.
        import fcntl

        lock_path = os.path.join(_NATIVE_DIR, ".build.lock")
        try:
            lock_fd = open(lock_path, "w")
            fcntl.flock(lock_fd, fcntl.LOCK_EX)
        except OSError:
            lock_fd = None
        try:
            digest = _source_digest()
            if not _built_from(digest):
                if shutil.which("make") and shutil.which(
                        os.environ.get("CXX", "g++")):
                    if not _build(digest):
                        return None
                elif os.path.exists(_SO):
                    warnings.warn(
                        f"no toolchain to build {_SO} from this checkout's "
                        "sources; loading the pre-existing library, which "
                        "may not match them", RuntimeWarning)
                else:
                    return None
            try:
                lib = ctypes.CDLL(_SO)
            except OSError:
                return None
        finally:
            if lock_fd is not None:
                try:
                    fcntl.flock(lock_fd, fcntl.LOCK_UN)
                except OSError:
                    pass
                lock_fd.close()
        # tcp store
        lib.tcp_store_server_start.restype = ctypes.c_void_p
        lib.tcp_store_server_start.argtypes = [ctypes.c_int]
        lib.tcp_store_server_port.restype = ctypes.c_int
        lib.tcp_store_server_port.argtypes = [ctypes.c_void_p]
        lib.tcp_store_server_stop.argtypes = [ctypes.c_void_p]
        lib.tcp_store_connect.restype = ctypes.c_ssize_t
        lib.tcp_store_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                          ctypes.c_int, ctypes.c_int]
        lib.tcp_store_set.restype = ctypes.c_int
        lib.tcp_store_set.argtypes = [ctypes.c_ssize_t, ctypes.c_char_p,
                                      ctypes.c_char_p, ctypes.c_long]
        lib.tcp_store_get.restype = ctypes.c_long
        lib.tcp_store_get.argtypes = [ctypes.c_ssize_t, ctypes.c_char_p,
                                      ctypes.c_char_p, ctypes.c_long]
        lib.tcp_store_tryget.restype = ctypes.c_long
        lib.tcp_store_tryget.argtypes = [ctypes.c_ssize_t, ctypes.c_char_p,
                                         ctypes.c_char_p, ctypes.c_long]
        lib.tcp_store_add.restype = ctypes.c_int
        lib.tcp_store_add.argtypes = [ctypes.c_ssize_t, ctypes.c_char_p,
                                      ctypes.c_longlong,
                                      ctypes.POINTER(ctypes.c_longlong)]
        lib.tcp_store_wait.restype = ctypes.c_int
        lib.tcp_store_wait.argtypes = [ctypes.c_ssize_t, ctypes.c_char_p]
        lib.tcp_store_delete.restype = ctypes.c_int
        lib.tcp_store_delete.argtypes = [ctypes.c_ssize_t, ctypes.c_char_p]
        lib.tcp_store_close.argtypes = [ctypes.c_ssize_t]
        # watchdog
        lib.watchdog_create.restype = ctypes.c_void_p
        lib.watchdog_create.argtypes = [ctypes.c_long]
        lib.watchdog_destroy.argtypes = [ctypes.c_void_p]
        lib.watchdog_register.restype = ctypes.c_longlong
        lib.watchdog_register.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_long]
        lib.watchdog_complete.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        lib.watchdog_timeout_count.restype = ctypes.c_longlong
        lib.watchdog_timeout_count.argtypes = [ctypes.c_void_p]
        lib.watchdog_drain_report.restype = ctypes.c_long
        lib.watchdog_drain_report.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                              ctypes.c_long]
        lib.watchdog_inflight.restype = ctypes.c_longlong
        lib.watchdog_inflight.argtypes = [ctypes.c_void_p]
        # shm ring
        lib.shm_ring_create.restype = ctypes.c_void_p
        lib.shm_ring_create.argtypes = [ctypes.c_char_p, ctypes.c_long]
        lib.shm_ring_attach.restype = ctypes.c_void_p
        lib.shm_ring_attach.argtypes = [ctypes.c_char_p]
        lib.shm_ring_push.restype = ctypes.c_int
        lib.shm_ring_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_long]
        lib.shm_ring_pop.restype = ctypes.c_long
        lib.shm_ring_pop.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_long]
        lib.shm_ring_peek.restype = ctypes.c_long
        lib.shm_ring_peek.argtypes = [ctypes.c_void_p]
        lib.shm_ring_close.argtypes = [ctypes.c_void_p]
        lib.shm_ring_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None
