"""TCPStore — the rendezvous key-value store, backed by the native C++ server
(native/tcp_store.cc; reference: paddle/phi/core/distributed/store/
tcp_store.h:121 and python create_or_get_global_tcp_store,
python/paddle/distributed/collective.py:342).

The master rank hosts the server; every rank (master included) connects as a
client. Used for multi-host bootstrap (before jax.distributed is up),
barriers, and elastic bookkeeping.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from ..framework import native

__all__ = ["TCPStore", "create_or_get_global_tcp_store"]


def _connect_with_backoff(lib, host, port, timeout_ms, io_timeout_ms):
    """Connect with bounded exponential backoff inside the overall timeout.

    Workers racing the master's bind at pod start is THE common elastic
    failure: on a restart every worker reconnects immediately while rank 0
    is still re-binding the server socket, so the first attempts get
    ECONNREFUSED and must retry, not die. Each attempt gets a FRESH socket:
    the native connect loop reuses its fd across connect() calls, and POSIX
    leaves a socket's state undefined after a failed connect — retrying on
    the same fd can spin to the deadline without ever succeeding even once
    the server is up. Returns (fd, attempts)."""
    deadline = time.monotonic() + timeout_ms / 1000.0
    delay = 0.05
    attempt = 0
    while True:
        attempt += 1
        remaining_ms = max(1, int((deadline - time.monotonic()) * 1000))
        # early attempts are short (ECONNREFUSED returns instantly while the
        # master hasn't bound yet); later attempts get 3s so a SYN dropped by
        # a full listen backlog can ride out the ~1s kernel retransmit
        per_attempt_ms = 500 if attempt <= 3 else 3000
        fd = lib.tcp_store_connect(host.encode(), int(port),
                                   min(remaining_ms, per_attempt_ms),
                                   io_timeout_ms)
        if fd >= 0:
            return fd, attempt
        if time.monotonic() + delay >= deadline:
            return fd, attempt
        if attempt == 1 or attempt % 8 == 0:
            print(f"[tcp_store] connect to {host}:{port} refused "
                  f"(attempt {attempt}), retrying for another "
                  f"{deadline - time.monotonic():.1f}s", file=sys.stderr)
        time.sleep(delay)
        delay = min(delay * 2, 1.0)


class TCPStore:
    def __init__(self, host="127.0.0.1", port=0, is_master=False,
                 world_size=1, timeout=30, io_timeout=900):
        """`timeout` bounds connect(); `io_timeout` bounds each blocking
        GET/WAIT (rendezvous waits legitimately run minutes while stragglers
        start up — reference default is 900s, tcp_store.h:121). A timed-out
        request desynchronizes the connection; treat it as fatal."""
        lib = native.load()
        if lib is None:
            raise RuntimeError(
                "native runtime unavailable (g++ build failed) — TCPStore "
                "requires native/libpaddle_tpu_native.so")
        self._lib = lib
        self._server = None
        self._host = host
        self._timeout_ms = int(timeout * 1000)
        if is_master:
            self._server = lib.tcp_store_server_start(int(port))
            if not self._server:
                raise RuntimeError(f"TCPStore: cannot bind port {port}")
            port = lib.tcp_store_server_port(self._server)
        self._port = int(port)
        self._fd, attempts = _connect_with_backoff(
            lib, host, self._port, self._timeout_ms, int(io_timeout * 1000))
        if self._fd < 0:
            if self._server:
                lib.tcp_store_server_stop(self._server)
                # clear it: __del__→close() on this half-built instance
                # would otherwise stop (and free) the server a second time
                self._server = None
            raise RuntimeError(
                f"TCPStore: cannot connect to {host}:{port} after "
                f"{attempts} attempt(s) over {self._timeout_ms / 1000:.0f}s")
        self._lock = threading.Lock()

    @property
    def port(self):
        return self._port

    def set(self, key: str, value):
        if isinstance(value, str):
            value = value.encode()
        with self._lock:
            rc = self._lib.tcp_store_set(self._fd, key.encode(), value, len(value))
        if rc != 0:
            raise RuntimeError("TCPStore.set failed")

    def get(self, key: str) -> bytes:
        import ctypes

        cap = 1 << 20
        with self._lock:
            for _ in range(8):  # value may grow between round-trips
                buf = ctypes.create_string_buffer(cap)
                n = self._lib.tcp_store_get(self._fd, key.encode(), buf, cap)
                if n <= cap:
                    break
                cap = int(n)
            else:
                raise RuntimeError("TCPStore.get: value kept outgrowing buffer")
        if n < 0:
            raise RuntimeError("TCPStore.get failed")
        return buf.raw[:n]

    def tryget(self, key: str):
        """Non-blocking probe: value bytes, or None when the key is absent
        (used by the elastic liveness watcher — a blocking GET on a dead
        node's heartbeat would stall the whole watch loop)."""
        import ctypes

        cap = 1 << 20
        with self._lock:
            for _ in range(8):
                buf = ctypes.create_string_buffer(cap)
                n = self._lib.tcp_store_tryget(self._fd, key.encode(), buf, cap)
                if n <= cap:
                    break
                cap = int(n)
            else:
                raise RuntimeError("TCPStore.tryget: value kept outgrowing buffer")
        if n == -2:
            return None
        if n < 0:
            raise RuntimeError("TCPStore.tryget failed")
        return buf.raw[:n]

    def add(self, key: str, amount: int) -> int:
        import ctypes

        out = ctypes.c_longlong(0)
        with self._lock:
            rc = self._lib.tcp_store_add(self._fd, key.encode(), int(amount),
                                         ctypes.byref(out))
        if rc != 0:
            raise RuntimeError("TCPStore.add failed")
        return int(out.value)

    def wait(self, keys):
        if isinstance(keys, str):
            keys = [keys]
        for k in keys:
            with self._lock:
                rc = self._lib.tcp_store_wait(self._fd, k.encode())
            if rc != 0:
                raise RuntimeError(f"TCPStore.wait({k}) failed")

    def delete_key(self, key: str):
        with self._lock:
            self._lib.tcp_store_delete(self._fd, key.encode())

    def barrier(self, prefix: str, world_size: int, rank: int):
        """Counter barrier: every rank adds 1, waits for the done key."""
        n = self.add(f"{prefix}/count", 1)
        if n >= world_size:
            self.set(f"{prefix}/done", b"1")
        self.wait(f"{prefix}/done")

    def close(self):
        # getattr guards: __del__ reaches here for instances whose __init__
        # raised before these attributes existed (e.g. failed bind)
        if getattr(self, "_fd", -1) >= 0:
            self._lib.tcp_store_close(self._fd)
            self._fd = -1
        if getattr(self, "_server", None):
            self._lib.tcp_store_server_stop(self._server)
            self._server = None

    def __del__(self):
        try:
            self.close()
        except Exception as e:
            # never raise out of GC, but never swallow silently either — a
            # failed close can leak the server socket and wedge the NEXT
            # rendezvous on this port
            try:
                print(f"[tcp_store] warning: close failed during GC: {e!r}",
                      file=sys.stderr)
            except Exception:  # graftlint: disable=GL003 interpreter teardown: stderr may already be gone
                pass


_global_store = None


def create_or_get_global_tcp_store():
    """reference: python/paddle/distributed/collective.py:342 — master from
    PADDLE_MASTER / MASTER_ADDR:PORT envs, rank 0 hosts."""
    global _global_store
    if _global_store is not None:
        return _global_store
    master = os.environ.get("PADDLE_MASTER") or os.environ.get("MASTER_ADDR", "127.0.0.1")
    if ":" in master:
        host, port = master.rsplit(":", 1)
        port = int(port)
    else:
        host, port = master, int(os.environ.get("MASTER_PORT", "6170"))
    rank = int(os.environ.get("PADDLE_TRAINER_ID", os.environ.get("RANK", "0")))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", os.environ.get("WORLD_SIZE", "1")))
    _global_store = TCPStore(host, port, is_master=(rank == 0), world_size=world)
    return _global_store
