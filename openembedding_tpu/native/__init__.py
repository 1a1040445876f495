"""Native (C++) data-pipeline bindings via ctypes.

The reference ships native code for its data path (`test/criteo_preprocess.cpp`) and
runtime (pico-core); here the TSV parse/hash/batch producer is C++
(`oetpu_data.cpp`) bound with ctypes (no pybind11 in this image). The library is
built on demand with g++ and cached next to the source under a name that is a
hash of the source and the compile command (`liboetpu_data.<hash>.so`) — a
binary that arrives with a copied tree is used only if it was built from this
source with these flags, never because its mtime looks new. No `-march=native`:
the tree may be copied to a host with another CPU. Everything degrades
gracefully to the pure-Python reader when no compiler is available
(`data/criteo.py` falls back automatically).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, Iterator, Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "oetpu_data.cpp")
_CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]
# tried in order: hosts without zlib dev libs keep every plain-file path with
# the gzip support compiled out (.gz opens then fail loudly at read time)
_VARIANTS = (["-lz"], ["-DOETPU_NO_ZLIB"])

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None

NUM_DENSE = 13
NUM_SPARSE = 26


def _lib_paths() -> list:
    """One library path per entry of `_VARIANTS`, each named by a hash of the
    source and that variant's compile command."""
    with open(_SRC, "rb") as f:
        src = f.read()
    return [os.path.join(_DIR, "liboetpu_data.%s.so" % hashlib.sha256(
        src + " ".join(_CXX + extra).encode()).hexdigest()[:16])
        for extra in _VARIANTS]


def build(force: bool = False) -> str:
    """Compile the shared library unless the one for this source + command
    is already there; returns its path."""
    with _lock:
        paths = _lib_paths()
        if not force:
            for path in paths:
                if os.path.exists(path):
                    return path
        for path, extra in zip(paths, _VARIANTS):
            tmp = f"{path}.tmp.{os.getpid()}"  # unique per builder: concurrent
            # processes (multi-host launch, pytest-xdist) must not share a tmp
            proc = subprocess.run(_CXX + [_SRC, "-o", tmp] + extra,
                                  capture_output=True, text=True)
            if proc.returncode == 0:
                os.replace(tmp, path)
                return path
        raise RuntimeError(f"native build failed:\n{proc.stderr}")


def load() -> ctypes.CDLL:
    """Build (if needed) and load the library; raises on failure."""
    global _lib, _build_error
    if _lib is not None:
        return _lib
    if _build_error is not None:
        raise RuntimeError(_build_error)
    try:
        path = build()
        lib = ctypes.CDLL(path)
    except (RuntimeError, OSError) as e:
        _build_error = str(e)
        raise
    lib.oetpu_reader_create.restype = ctypes.c_void_p
    lib.oetpu_reader_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_uint64, ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.oetpu_reader_next.restype = ctypes.c_int
    lib.oetpu_reader_next.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
    lib.oetpu_reader_destroy.restype = None
    lib.oetpu_reader_destroy.argtypes = [ctypes.c_void_p]
    lib.oetpu_hash_category.restype = ctypes.c_int64
    lib.oetpu_hash_category.argtypes = [ctypes.c_uint64, ctypes.c_uint64,
                                        ctypes.c_uint64]
    lib.oetpu_preprocess.restype = ctypes.c_int64
    lib.oetpu_preprocess.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
    lib.oetpu_tfr_create.restype = ctypes.c_void_p
    lib.oetpu_tfr_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.oetpu_tfr_next.restype = ctypes.c_int
    lib.oetpu_tfr_next.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
    lib.oetpu_tfr_destroy.restype = None
    lib.oetpu_tfr_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def available() -> bool:
    try:
        load()
        return True
    except (RuntimeError, OSError):
        return False


class NativeCriteoReader:
    """Streaming batches from Criteo TSV files via the C++ pipeline.

    Yields the same dict batches as `data.criteo.read_criteo_tsv` (bit-identical ids
    and labels; dense within float rounding of the numpy transform)."""

    def __init__(self, paths: Sequence[str], batch_size: int, *,
                 id_space: int = 1 << 25, host_id: int = 0, num_hosts: int = 1,
                 num_threads: int = 4, drop_remainder: bool = True,
                 repeat: bool = False):
        if isinstance(paths, str):
            paths = [paths]
        for p in paths:
            if not os.path.exists(p):
                raise FileNotFoundError(p)
        self.paths = [os.fspath(p) for p in paths]
        self.batch_size = batch_size
        self.id_space = id_space
        self.host_id = host_id
        self.num_hosts = num_hosts
        self.num_threads = num_threads
        self.drop_remainder = drop_remainder
        self.repeat = repeat
        self._lib = load()

    def _one_pass(self) -> Iterator[Dict]:
        lib = self._lib
        arr = (ctypes.c_char_p * len(self.paths))(
            *[p.encode() for p in self.paths])
        handle = lib.oetpu_reader_create(
            arr, len(self.paths), self.batch_size, self.id_space,
            self.host_id, self.num_hosts, self.num_threads)
        try:
            while True:
                labels = np.empty((self.batch_size,), np.float32)
                dense = np.empty((self.batch_size, NUM_DENSE), np.float32)
                sparse = np.empty((self.batch_size, NUM_SPARSE), np.int64)
                n = lib.oetpu_reader_next(handle, labels, dense, sparse)
                if n < 0:
                    raise IOError(
                        f"native reader failed (unreadable input?) on "
                        f"{self.paths}")
                if n == 0:
                    return
                if n < self.batch_size:
                    if self.drop_remainder:
                        return
                    labels, dense, sparse = labels[:n], dense[:n], sparse[:n]
                yield {"sparse": {"categorical": sparse}, "dense": dense,
                       "label": labels}
                if n < self.batch_size:
                    return
        finally:
            lib.oetpu_reader_destroy(handle)

    def __iter__(self) -> Iterator[Dict]:
        while True:
            yield from self._one_pass()
            if not self.repeat:
                return


class NativeCriteoTFRecordReader:
    """Streaming batches from the reference's TFRecord benchmark format
    (`test/benchmark/criteo_tfrecord.py` schema) with NO TensorFlow
    dependency: C++ record framing (masked-CRC32C verified) + a proto-wire
    parser for the fixed Example schema, round-robin across files like the
    tf.data interleave. Yields RAW columns; callers fold the categorical ids
    (`data.criteo.read_criteo_tfrecord(engine="native")` applies the same
    `_fold_int_ids` as the tf path, so batches are bit-identical)."""

    def __init__(self, paths: Sequence[str], batch_size: int, *,
                 host_id: int = 0, num_hosts: int = 1, num_threads: int = 4,
                 drop_remainder: bool = True, repeat: bool = False):
        if isinstance(paths, str):
            paths = [paths]
        for p in paths:
            if not os.path.exists(p):
                raise FileNotFoundError(p)
        self.paths = [os.fspath(p) for p in paths]
        self.batch_size = batch_size
        self.host_id = host_id
        self.num_hosts = num_hosts
        self.num_threads = num_threads
        self.drop_remainder = drop_remainder
        self.repeat = repeat
        self._lib = load()

    def _one_pass(self) -> Iterator[Dict]:
        lib = self._lib
        arr = (ctypes.c_char_p * len(self.paths))(
            *[p.encode() for p in self.paths])
        handle = lib.oetpu_tfr_create(arr, len(self.paths), self.batch_size,
                                      self.host_id, self.num_hosts,
                                      self.num_threads)
        try:
            while True:
                labels = np.empty((self.batch_size,), np.float32)
                dense = np.empty((self.batch_size, NUM_DENSE), np.float32)
                sparse = np.empty((self.batch_size, NUM_SPARSE), np.int64)
                n = lib.oetpu_tfr_next(handle, labels, dense, sparse)
                if n < 0:
                    raise IOError(f"native TFRecord reader failed (corrupt "
                                  f"frame or malformed Example) on "
                                  f"{self.paths}")
                if n == 0:
                    return
                if n < self.batch_size:
                    if self.drop_remainder:
                        return
                    labels, dense, sparse = labels[:n], dense[:n], sparse[:n]
                yield {"sparse": {"categorical": sparse}, "dense": dense,
                       "label": labels}
                if n < self.batch_size:
                    return
        finally:
            lib.oetpu_tfr_destroy(handle)

    def __iter__(self) -> Iterator[Dict]:
        while True:
            yield from self._one_pass()
            if not self.repeat:
                return


def preprocess(in_path: str, out_path: str, min_count: int = 10) -> np.ndarray:
    """Frequency relabel (reference `test/criteo_preprocess.cpp`): rewrites the TSV
    with each categorical column renumbered by descending frequency (0 = rare).
    Returns the per-column vocab sizes (26,)."""
    lib = load()
    vocab = np.zeros((NUM_SPARSE,), np.int64)
    rows = lib.oetpu_preprocess(in_path.encode(), out_path.encode(),
                                min_count, vocab)
    if rows < 0:
        raise IOError(f"preprocess failed with code {rows} "
                      f"({in_path!r} -> {out_path!r})")
    return vocab
