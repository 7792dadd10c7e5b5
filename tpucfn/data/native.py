"""ctypes binding for the native tpurecord reader (native/tpurecord.cc).

The C++ library owns the hot read path (offset indexing, CRC validation,
batched contiguous copies, GIL released during calls); this module loads
it, building with g++ when the library is missing or older than its
source, and degrades to the pure-Python reader in
:mod:`tpucfn.data.records` when no toolchain is available — same format,
same errors, ~10× slower; ``_lib_error`` records why.
"""

from __future__ import annotations

import ctypes
import subprocess
from pathlib import Path
from typing import Iterator, Sequence

import numpy as np

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "libtpurecord.so"
_lib = None
_lib_error: str | None = None


def _lib_is_stale() -> bool:
    """The library is built from what git holds: missing, or older than
    its source or build script, means rebuild (before the first dlopen,
    so the loader never sees two images of one path)."""
    if not _LIB_PATH.exists():
        return True
    built = _LIB_PATH.stat().st_mtime_ns
    return any((_NATIVE_DIR / src).stat().st_mtime_ns > built
               for src in ("tpurecord.cc", "build.sh"))


def _load_lib():
    global _lib, _lib_error
    if _lib is not None or _lib_error is not None:
        return _lib
    try:
        if _lib_is_stale():
            subprocess.run(["sh", str(_NATIVE_DIR / "build.sh")], check=True,
                           capture_output=True, text=True, timeout=120)
        lib = ctypes.CDLL(str(_LIB_PATH))
        lib.tpurec_open.restype = ctypes.c_void_p
        lib.tpurec_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
        lib.tpurec_count.restype = ctypes.c_long
        lib.tpurec_count.argtypes = [ctypes.c_void_p]
        # (tpurec_length / tpurec_read / tpurec_read_batch are the
        # copy-out C embedding API — unused by this zero-copy binding.)
        lib.tpurec_index.restype = None
        lib.tpurec_index.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.tpurec_validate.restype = ctypes.c_long
        lib.tpurec_validate.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_long), ctypes.c_long,
        ]
        lib.tpurec_close.restype = None
        lib.tpurec_close.argtypes = [ctypes.c_void_p]
        _lib = lib
    except Exception as e:  # no g++ / build failure → Python fallback,
        # never silent: _lib_error says why (chip_smoke.py fails on it)
        _lib_error = f"{e!r} {getattr(e, 'stderr', None) or ''}".strip()
    return _lib


def native_available() -> bool:
    return _load_lib() is not None


class NativeShardReader:
    """CRC-validated reader over one tpurecord shard, backed by C++."""

    def __init__(self, path: str | Path):
        lib = _load_lib()
        if lib is None:
            raise RuntimeError(f"native reader unavailable: {_lib_error}")
        err = ctypes.create_string_buffer(256)
        self._lib = lib
        self._h = lib.tpurec_open(str(path).encode(), err, len(err))
        if not self._h:
            raise ValueError(f"{path}: {err.value.decode()}")
        self.path = str(path)
        # Zero-copy read path: C++ owns the validated index and the CRC
        # scan (GIL released); payload bytes are served as memoryviews
        # over this mapping — no per-record copy anywhere.
        n = int(lib.tpurec_count(self._h))
        self._offs = np.zeros(n, np.int64)
        self._lens = np.zeros(n, np.int64)
        if n:
            lib.tpurec_index(
                self._h,
                self._offs.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
                self._lens.ctypes.data_as(ctypes.POINTER(ctypes.c_long)))
        if n == 0:
            self._mm = None
        else:
            try:
                self._mm = memoryview(np.memmap(self.path, np.uint8, mode="r"))
            except (OSError, ValueError):
                # Filesystems without mmap (some FUSE/network mounts):
                # one read()-copy at open, views served over it — the
                # same behavior the C++ side falls back to.
                self._mm = memoryview(np.fromfile(self.path, np.uint8))

    def __len__(self) -> int:
        return int(self._lib.tpurec_count(self._h))

    def read(self, idx: int) -> memoryview:
        if idx < 0 or idx >= len(self._offs):
            raise IndexError(f"record {idx} out of range in {self.path}")
        return self.read_batch([idx])[0]

    def read_batch(self, indices: Sequence[int]) -> list[memoryview]:
        """Zero-copy batch read: ONE FFI call CRC-validates the records
        in place (C++, GIL released), then payloads are returned as
        memoryviews straight over the file mapping — no data copy on
        either side of the boundary. (The earlier copy-out design lost
        to the pure-Python reader on large records: its crc+memcpy was
        two memory passes against Python's one — data_bench history.)
        Views are bytes-compatible for every consumer (decode_example
        wraps them in BytesIO); they keep the mapping alive."""
        n = len(indices)
        if n == 0:
            return []
        idx_arr = (ctypes.c_long * n)(*indices)
        bad = int(self._lib.tpurec_validate(self._h, idx_arr, n))
        if bad == -3:
            raise IndexError(f"batch indices out of range in {self.path}")
        if bad >= 0:
            raise ValueError(f"{self.path}: CRC mismatch at record {bad}")
        mm, offs, lens = self._mm, self._offs, self._lens
        return [mm[offs[i]:offs[i] + lens[i]] for i in indices]

    _ITER_CHUNK = 1024  # validate-call granularity (no buffers involved)

    def __iter__(self) -> Iterator[memoryview]:
        n = len(self)
        for start in range(0, n, self._ITER_CHUNK):
            yield from self.read_batch(range(start, min(start + self._ITER_CHUNK, n)))

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.tpurec_close(self._h)
            self._h = None
            self._mm = None  # outstanding views keep the mapping alive

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def read_record_shard_native(path: str | Path) -> Iterator[bytes]:
    """Drop-in for :func:`tpucfn.data.records.read_record_shard`."""
    r = NativeShardReader(path)
    try:
        yield from r
    finally:
        r.close()


def decode_batch(reader: NativeShardReader, indices: Sequence[int]) -> list[dict[str, np.ndarray]]:
    from tpucfn.data.records import decode_example

    return [decode_example(p) for p in reader.read_batch(indices)]
