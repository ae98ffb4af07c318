"""ctypes bindings for the native runtime library (csrc/ — recordio,
threaded dataloader, async sparse pserver).

Reference analogs: paddle/fluid/recordio/*, operators/reader/*, go/pserver.
The library is optional: every consumer has a pure-python fallback, so
``lib() is None`` is a supported state (no compiler, failed build).  It is
always built from the sources in the checkout: ``lib()`` runs ``make -C
csrc`` (a no-op when up to date), so a stale binary lying in the tree is
rebuilt, never loaded as it is.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess

_LIB = None
_TRIED = False

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "csrc")
_SO = os.path.join(_CSRC, "build", "libpaddle_tpu_native.so")


def lib():
    """Load (building on first use if possible) the native library, or None."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    try:
        # one builder at a time: test workers start together and would
        # otherwise interleave writes to the same objects
        os.makedirs(os.path.dirname(_SO), exist_ok=True)
        with open(_SO + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            subprocess.run(
                ["make", "-C", _CSRC], check=True, capture_output=True, timeout=120
            )
        L = ctypes.CDLL(_SO)
    except (OSError, subprocess.SubprocessError):
        return None

    u8p = ctypes.POINTER(ctypes.c_uint8)
    L.rio_writer_open.restype = ctypes.c_void_p
    L.rio_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint32]
    L.rio_writer_write.restype = ctypes.c_int
    L.rio_writer_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    L.rio_writer_flush.restype = ctypes.c_int
    L.rio_writer_flush.argtypes = [ctypes.c_void_p]
    L.rio_writer_close.argtypes = [ctypes.c_void_p]
    L.rio_reader_open.restype = ctypes.c_void_p
    L.rio_reader_open.argtypes = [ctypes.c_char_p]
    L.rio_reader_next.restype = ctypes.c_int
    L.rio_reader_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint32)]
    L.rio_reader_close.argtypes = [ctypes.c_void_p]

    L.loader_open.restype = ctypes.c_void_p
    L.loader_open.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.c_uint32,
        ctypes.c_uint32,
        ctypes.c_uint64,
        ctypes.c_int,
    ]
    L.loader_next.restype = ctypes.c_int
    L.loader_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint32)]
    L.loader_close.argtypes = [ctypes.c_void_p]

    L.pserver_start.restype = ctypes.c_void_p
    L.pserver_start.argtypes = [ctypes.c_uint16]
    L.pserver_port.restype = ctypes.c_uint16
    L.pserver_port.argtypes = [ctypes.c_void_p]
    L.pserver_stop.argtypes = [ctypes.c_void_p]

    _LIB = L
    return _LIB


class NativeRecordIOWriter:
    def __init__(self, path, max_chunk_records=1000, compressor=1):
        self._lib = lib()
        self._h = self._lib.rio_writer_open(path.encode(), max_chunk_records, compressor)
        if not self._h:
            raise IOError("cannot open %s for writing" % path)

    def write(self, record_bytes: bytes):
        if not self._lib.rio_writer_write(self._h, record_bytes, len(record_bytes)):
            raise IOError("recordio write failed")

    def write_sample(self, sample):
        import pickle

        self.write(pickle.dumps(sample, protocol=4))

    def flush(self):
        self._lib.rio_writer_flush(self._h)

    def close(self):
        if self._h:
            self._lib.rio_writer_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
        return False


class NativeRecordIOReader:
    def __init__(self, path):
        self._lib = lib()
        self.path = path

    def __iter__(self):
        h = self._lib.rio_reader_open(self.path.encode())
        if not h:
            raise IOError("cannot open %s" % self.path)
        buf = ctypes.POINTER(ctypes.c_uint8)()
        n = ctypes.c_uint32()
        try:
            while True:
                rc = self._lib.rio_reader_next(h, ctypes.byref(buf), ctypes.byref(n))
                if rc == 0:
                    return
                if rc < 0:
                    raise IOError("corrupt recordio chunk in %s" % self.path)
                yield ctypes.string_at(buf, n.value)
        finally:
            self._lib.rio_reader_close(h)


class NativeLoader:
    """Threaded shuffling prefetch over recordio files (csrc/dataloader.cc)."""

    def __init__(self, files, num_threads=2, capacity=1024, shuffle_buf=0, seed=0, epochs=1):
        self._lib = lib()
        if isinstance(files, str):
            files = [files]
        self._h = self._lib.loader_open(
            "\n".join(files).encode(), num_threads, capacity, shuffle_buf, seed, epochs
        )
        if not self._h:
            raise IOError("loader_open failed for %r" % (files,))

    def __iter__(self):
        buf = ctypes.POINTER(ctypes.c_uint8)()
        n = ctypes.c_uint32()
        while True:
            rc = self._lib.loader_next(self._h, ctypes.byref(buf), ctypes.byref(n))
            if rc == 0:
                return
            yield ctypes.string_at(buf, n.value)

    def close(self):
        if self._h:
            self._lib.loader_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class SparsePSClient:
    """Wire-protocol client for the C++ sparse pserver (csrc/pserver.cc;
    reference analog: go/pserver/client).  One TCP connection, blocking
    request/response.  The update rule runs SERVER-side: ``configure``
    selects SGD/Adagrad/Adam per table (reference go/pserver/optimizer.go),
    ``push`` ships raw gradients with the learning rate, ``save``/``load``
    snapshot and restore the table INCLUDING optimizer state so a restarted
    pserver resumes training without losing learned rows."""

    OPT_SGD, OPT_ADAGRAD, OPT_ADAM = 0, 1, 2

    def __init__(self, host, port, timeout=30.0):
        import socket

        self.sock = socket.create_connection((host, int(port)), timeout=timeout)

    def _hdr(self, op, table):
        import struct

        t = table.encode() if isinstance(table, str) else table
        return struct.pack("<BH", op, len(t)) + t

    def _status(self):
        b = self.sock.recv(1)
        if len(b) != 1:
            raise IOError("pserver closed connection")
        return b == b"\x01"

    def init_table(self, table, rows, width):
        import struct

        self.sock.sendall(self._hdr(0, table) + struct.pack("<II", rows, width))
        return self._status()

    def configure(self, table, optimizer="sgd", eps=1e-8, beta1=0.9, beta2=0.999):
        import struct

        opt = {"sgd": 0, "adagrad": 1, "adam": 2}[optimizer]
        self.sock.sendall(
            self._hdr(5, table) + struct.pack("<Bfff", opt, eps, beta1, beta2))
        return self._status()

    def push(self, table, row_ids, grads, lr):
        import struct

        import numpy as np

        g = np.ascontiguousarray(grads, dtype=np.float32)
        ids = np.ascontiguousarray(row_ids, dtype=np.uint32).reshape(-1)
        n, width = g.shape if g.ndim == 2 else (1, g.shape[0])
        g = g.reshape(n, width)
        assert len(ids) == n, (len(ids), n)
        msg = self._hdr(1, table) + struct.pack("<fII", float(lr), width, n)
        parts = [msg]
        for i in range(n):
            parts.append(struct.pack("<I", int(ids[i])) + g[i].tobytes())
        self.sock.sendall(b"".join(parts))
        return self._status()

    def pull(self, table, row_ids, width):
        import struct

        import numpy as np

        ids = np.ascontiguousarray(row_ids, dtype=np.uint32).reshape(-1)
        self.sock.sendall(
            self._hdr(2, table) + struct.pack("<I", len(ids)) + ids.tobytes())
        if not self._status():
            raise KeyError("unknown table %r" % table)
        need = len(ids) * width * 4
        buf = b""
        while len(buf) < need:
            chunk = self.sock.recv(need - len(buf))
            if not chunk:
                raise IOError("pserver closed connection mid-pull")
            buf += chunk
        return np.frombuffer(buf, np.float32).reshape(len(ids), width).copy()

    def save(self, table, path):
        import struct

        p = path.encode()
        self.sock.sendall(self._hdr(3, table) + struct.pack("<H", len(p)) + p)
        return self._status()

    def load(self, table, path):
        import struct

        p = path.encode()
        self.sock.sendall(self._hdr(6, table) + struct.pack("<H", len(p)) + p)
        return self._status()

    def shutdown_server(self):
        try:
            self.sock.sendall(self._hdr(4, ""))
            self._status()
        except OSError:
            pass

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass
