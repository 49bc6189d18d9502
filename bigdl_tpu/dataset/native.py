"""ctypes bindings for the native (C++) host data plane.

Reference parity: the reference backs its hot paths with native code
behind JNI (BigDL-core mkl/mkldnn/bigquant shared objects, SURVEY.md
§2.1). On TPU the device math belongs to XLA, so our native layer lives
where native still pays: the host input pipeline (native/dataplane.cpp —
threaded decode/augment/normalize + a prefetching ring buffer that keeps
the chips fed, SURVEY.md §7).

The library is compiled on first use with g++ (no pybind11 — plain C ABI
via ctypes) from native/dataplane.cpp AS THE CHECKOUT HAS IT: the cached
object under native/build/ carries the source's hash in its name, so a
library left on disk by another source tree is never loaded. Every entry
point has a pure-Python plane so the package works without a toolchain;
when the build fails the reason is logged once and `available()` /
`.native` report which plane is active.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_ROOT, "native", "dataplane.cpp")

logger = logging.getLogger("bigdl_tpu.dataset.native")

_lib = None
_lib_lock = threading.Lock()
_unavailable = False    # a failed build is logged once, not retried


def _build() -> str:
    """Path of the library built from THIS checkout's source, building
    it if absent. Raises OSError / SubprocessError when there is no
    source or no working toolchain."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_ROOT, "native", "build",
                      f"libbigdl_dataplane-{digest}.so")
    if not os.path.exists(so):
        os.makedirs(os.path.dirname(so), exist_ok=True)
        tmp = f"{so}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-Wall", "-pthread",
             "-shared", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)     # atomic: a concurrent builder is harmless
    return so


def _load():
    global _lib, _unavailable
    if _lib is not None or _unavailable:
        return _lib
    with _lib_lock:
        if _lib is not None or _unavailable:
            return _lib
        try:
            so = _build()
        except (OSError, subprocess.SubprocessError) as e:
            _unavailable = True
            detail = getattr(e, "stderr", None) or b""
            logger.warning(
                "native data plane unavailable (%s %s) — running the "
                "pure-Python plane", e,
                detail.decode(errors="replace")[-400:])
            return None
        lib = ctypes.CDLL(so)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f32p = ctypes.POINTER(ctypes.c_float)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.bdl_normalize_u8.argtypes = [u8p, f32p, ctypes.c_int64,
                                         ctypes.c_int, f32p, f32p,
                                         ctypes.c_int]
        lib.bdl_hflip.argtypes = [f32p, u8p] + [ctypes.c_int] * 4
        lib.bdl_shift_crop.argtypes = [f32p, f32p,
                                       ctypes.POINTER(ctypes.c_int),
                                       ctypes.POINTER(ctypes.c_int)] + \
            [ctypes.c_int] * 4
        lib.bdl_decode_idx_images.argtypes = [u8p, ctypes.c_int64, u8p,
                                              i64p, i64p, i64p]
        lib.bdl_decode_idx_images.restype = ctypes.c_int
        lib.bdl_decode_idx_labels.argtypes = [u8p, ctypes.c_int64, u8p,
                                              i64p]
        lib.bdl_decode_idx_labels.restype = ctypes.c_int
        lib.bdl_decode_cifar10.argtypes = [u8p, ctypes.c_int64, u8p, u8p,
                                           i64p]
        lib.bdl_decode_cifar10.restype = ctypes.c_int
        lib.bdl_prefetcher_create.argtypes = [
            u8p, i32p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_int, f32p, f32p]
        lib.bdl_prefetcher_create.restype = ctypes.c_void_p
        lib.bdl_prefetcher_next.argtypes = [ctypes.c_void_p, f32p, i32p]
        lib.bdl_prefetcher_destroy.argtypes = [ctypes.c_void_p]
        lib.bdl_resize_bilinear.argtypes = [f32p, f32p] + \
            [ctypes.c_int] * 6
        lib.bdl_file_prefetcher_create.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, f32p, f32p, i64p,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int)]
        lib.bdl_file_prefetcher_create.restype = ctypes.c_void_p
        lib.bdl_prefetcher_next_u8.argtypes = [ctypes.c_void_p, u8p,
                                               i32p]
        _lib = lib
        return _lib


def available() -> bool:
    """True if the native library is (or can be) loaded."""
    return _load() is not None


def _u8(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _f32(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _i32(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _per_channel(vals, c, what) -> np.ndarray:
    """Validate/broadcast a per-channel vector to exactly c entries —
    the C++ side reads exactly c floats, so a short array would be an
    out-of-bounds read, not a broadcast."""
    arr = np.asarray(vals, np.float32).reshape(-1)
    if arr.size == 1:
        arr = np.full((c,), float(arr[0]), np.float32)
    if arr.size != c:
        raise ValueError(
            f"{what} has {arr.size} entries for {c} channels")
    return np.ascontiguousarray(arr)


def normalize_u8(images: np.ndarray, mean: Sequence[float],
                 std: Sequence[float], n_threads: int = 4) -> np.ndarray:
    """u8 (..., C) → f32 (x - mean[c]) / std[c]; native when possible."""
    images = np.ascontiguousarray(images, np.uint8)
    c = images.shape[-1]
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    lib = _load()
    if lib is None:
        return (images.astype(np.float32) - mean) / std
    out = np.empty(images.shape, np.float32)
    lib.bdl_normalize_u8(_u8(images), _f32(out),
                         images.size // c, c, _f32(mean), _f32(std),
                         n_threads)
    return out


def resize_bilinear(img: np.ndarray, out_h: int, out_w: int,
                    n_threads: int = 1) -> Optional[np.ndarray]:
    """f32 HWC bilinear resize (align_corners=False) in C++, or None
    when the native plane is unavailable (caller falls back to numpy —
    measured 12x slower per core for 256→224, PROFILE_r04)."""
    lib = _load()
    if lib is None:
        return None
    img = np.ascontiguousarray(img, np.float32)
    if img.ndim == 2:
        img = img[:, :, None]
    h, w, c = img.shape
    out = np.empty((out_h, out_w, c), np.float32)
    lib.bdl_resize_bilinear(_f32(img), _f32(out), h, w, c, out_h, out_w,
                            n_threads)
    return out


def decode_idx_images(raw: bytes) -> np.ndarray:
    lib = _load()
    buf = np.frombuffer(raw, np.uint8)
    if lib is None:
        import struct
        magic, n, rows, cols = struct.unpack(">IIII", raw[:16])
        if magic != 2051:
            raise ValueError(f"bad IDX magic {magic}")
        return buf[16:16 + n * rows * cols].reshape(n, rows, cols).copy()
    n = ctypes.c_int64()
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    rc = lib.bdl_decode_idx_images(_u8(buf), len(raw), None,
                                   ctypes.byref(n), ctypes.byref(rows),
                                   ctypes.byref(cols))
    if rc:
        raise ValueError(f"IDX image decode failed ({rc})")
    out = np.empty((n.value, rows.value, cols.value), np.uint8)
    lib.bdl_decode_idx_images(_u8(buf), len(raw), _u8(out),
                              ctypes.byref(n), ctypes.byref(rows),
                              ctypes.byref(cols))
    return out


def decode_idx_labels(raw: bytes) -> np.ndarray:
    lib = _load()
    buf = np.frombuffer(raw, np.uint8)
    if lib is None:
        import struct
        magic, n = struct.unpack(">II", raw[:8])
        if magic != 2049:
            raise ValueError(f"bad IDX magic {magic}")
        return buf[8:8 + n].copy()
    n = ctypes.c_int64()
    rc = lib.bdl_decode_idx_labels(_u8(buf), len(raw), None,
                                   ctypes.byref(n))
    if rc:
        raise ValueError(f"IDX label decode failed ({rc})")
    out = np.empty((n.value,), np.uint8)
    lib.bdl_decode_idx_labels(_u8(buf), len(raw), _u8(out),
                              ctypes.byref(n))
    return out


def decode_cifar10(raw: bytes) -> Tuple[np.ndarray, np.ndarray]:
    """CIFAR-10 binary records → (images u8 NHWC, labels u8)."""
    lib = _load()
    buf = np.frombuffer(raw, np.uint8)
    rec = 1 + 3072
    if len(raw) % rec:
        raise ValueError(
            f"CIFAR decode failed: {len(raw)} bytes is not a whole "
            f"number of {rec}-byte records")
    if lib is None:
        n = len(raw) // rec
        recs = buf.reshape(n, rec)
        labels = recs[:, 0].copy()
        chw = recs[:, 1:].reshape(n, 3, 32, 32)
        return chw.transpose(0, 2, 3, 1).copy(), labels
    n = ctypes.c_int64()
    rc = lib.bdl_decode_cifar10(_u8(buf), len(raw), None, None,
                                ctypes.byref(n))
    if rc:
        raise ValueError(f"CIFAR decode failed ({rc})")
    images = np.empty((n.value, 32, 32, 3), np.uint8)
    labels = np.empty((n.value,), np.uint8)
    lib.bdl_decode_cifar10(_u8(buf), len(raw), _u8(images), _u8(labels),
                           ctypes.byref(n))
    return images, labels


class Prefetcher:
    """Multithreaded native batch producer over an in-memory u8 dataset.

    Yields (images f32 (B,H,W,C), labels i32 (B,)) batches: shuffled
    every epoch, normalized, optionally shift-crop/hflip augmented —
    produced by C++ worker threads into a bounded ring buffer. Falls
    back to a Python thread if the native library is unavailable
    (`.native` tells which plane is running).
    """

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 batch_size: int, mean: Sequence[float],
                 std: Sequence[float], pad: int = 0, hflip: bool = False,
                 n_threads: int = 2, capacity: int = 4, seed: int = 0):
        self.images = np.ascontiguousarray(images, np.uint8)
        if self.images.ndim == 3:  # greyscale → add channel dim
            self.images = self.images[..., None]
        self.labels = np.ascontiguousarray(labels, np.int32)
        self.batch_size = batch_size
        n, h, w, c = self.images.shape
        self.shape = (h, w, c)
        self.mean = _per_channel(mean, c, "mean")
        self.std = _per_channel(std, c, "std")
        self.pad, self.hflip = pad, hflip
        self._lib = _load()
        self.native = self._lib is not None
        if self.native:
            self._handle = self._lib.bdl_prefetcher_create(
                _u8(self.images), _i32(self.labels), n, h, w, c,
                batch_size, capacity, n_threads, seed, pad,
                1 if hflip else 0, _f32(self.mean), _f32(self.std))
        else:
            import queue

            self._q = queue.Queue(maxsize=capacity)
            self._stop = threading.Event()
            self._rng = np.random.RandomState(seed)
            self._t = threading.Thread(target=self._py_worker, daemon=True)
            self._t.start()

    # ---- python fallback -------------------------------------------------
    def _py_worker(self):
        n = len(self.labels)
        h, w, c = self.shape
        while not self._stop.is_set():
            order = self._rng.permutation(n)
            for i in range(0, n - self.batch_size + 1, self.batch_size):
                if self._stop.is_set():
                    return
                idx = order[i:i + self.batch_size]
                img = (self.images[idx].astype(np.float32) - self.mean) \
                    / self.std
                if self.pad:
                    out = np.zeros_like(img)
                    for j in range(len(idx)):
                        dy, dx = self._rng.randint(-self.pad, self.pad + 1,
                                                   2)
                        y0, y1 = max(0, dy), min(h, h + dy)
                        x0, x1 = max(0, dx), min(w, w + dx)
                        out[j, y0:y1, x0:x1] = \
                            img[j, y0 - dy:y1 - dy, x0 - dx:x1 - dx]
                    img = out
                if self.hflip:
                    flips = self._rng.rand(len(idx)) < 0.5
                    img[flips] = img[flips, :, ::-1]
                self._q.put((img, self.labels[idx].copy()))

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        h, w, c = self.shape
        if self.native:
            if getattr(self, "_handle", None) is None:
                raise RuntimeError("Prefetcher used after close()")
            img = np.empty((self.batch_size, h, w, c), np.float32)
            lbl = np.empty((self.batch_size,), np.int32)
            self._lib.bdl_prefetcher_next(self._handle, _f32(img),
                                          _i32(lbl))
            return img, lbl
        return self._q.get()

    def __iter__(self):
        while True:
            yield self.next()

    def close(self):
        if self.native:
            if getattr(self, "_handle", None):
                self._lib.bdl_prefetcher_destroy(self._handle)
                self._handle = None
        else:
            self._stop.set()
            try:
                while True:
                    self._q.get_nowait()
            except Exception:
                pass

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass


class FilePrefetcher:
    """Disk-resident batch producer over BDLS shard files
    (dataset/records.py format). The native plane mmap()s every shard
    and streams records through C++ worker threads — datasets larger
    than RAM ride the OS page cache. Python fallback uses np.memmap
    with one producer thread (`.native` tells which plane runs)."""

    def __init__(self, paths, batch_size: int, mean: Sequence[float],
                 std: Sequence[float], pad: int = 0, hflip: bool = False,
                 n_threads: int = 4, capacity: int = 3, seed: int = 0,
                 out_dtype: str = "f32"):
        """out_dtype="u8" skips host normalization and yields raw u8
        batches — 4x less host->device wire; normalize on device (the
        TPU-idiomatic split: bytes over the wire, elementwise math on
        the chip where it is free)."""
        from bigdl_tpu.dataset.records import read_header

        self.paths = [os.fspath(p) for p in paths]
        self.batch_size = batch_size
        # channel count from the first shard header (Python-side read;
        # the native create would read exactly c floats of mean/std, so
        # validation must happen first)
        _, _, _, chans = read_header(self.paths[0])
        self.mean = _per_channel(mean, chans, "mean")
        self.std = _per_channel(std, chans, "std")
        self.pad, self.hflip = pad, hflip
        assert out_dtype in ("f32", "u8"), out_dtype
        self.out_dtype = out_dtype
        self._lib = _load()
        self.native = self._lib is not None
        if self.native:
            arr = (ctypes.c_char_p * len(self.paths))(
                *[p.encode() for p in self.paths])
            n = ctypes.c_int64()
            h = ctypes.c_int()
            w = ctypes.c_int()
            c = ctypes.c_int()
            self._handle = self._lib.bdl_file_prefetcher_create(
                arr, len(self.paths), batch_size, capacity, n_threads,
                seed, pad, 1 if hflip else 0,
                1 if out_dtype == "u8" else 0, _f32(self.mean),
                _f32(self.std), ctypes.byref(n), ctypes.byref(h),
                ctypes.byref(w), ctypes.byref(c))
            if not self._handle:
                raise ValueError(
                    f"native shard open failed (bad/missing BDLS files "
                    f"or mismatched shapes): {self.paths[:3]}...")
            self.n = n.value
            self.shape = (h.value, w.value, c.value)
        else:
            from bigdl_tpu.dataset.records import read_header

            import queue

            metas = [read_header(p) for p in self.paths]
            if len({m[1:] for m in metas}) != 1:
                raise ValueError("shards disagree on (h, w, c)")
            self.n = sum(m[0] for m in metas)
            self.shape = metas[0][1:]
            h, w, c = self.shape
            rec = 4 + h * w * c
            self._maps = []
            self._starts = [0]
            for p, m in zip(self.paths, metas):
                self._maps.append(np.memmap(p, np.uint8, mode="r",
                                            offset=32).reshape(m[0], rec))
                self._starts.append(self._starts[-1] + m[0])
            self._q = queue.Queue(maxsize=capacity)
            self._stop = threading.Event()
            self._rng = np.random.RandomState(seed)
            self._t = threading.Thread(target=self._py_worker, daemon=True)
            self._t.start()

    # ---- python fallback ------------------------------------------------
    def _record_batch(self, idx):
        h, w, c = self.shape
        starts = np.asarray(self._starts)
        out = np.empty((len(idx), 4 + h * w * c), np.uint8)
        for j, i in enumerate(idx):
            s = int(np.searchsorted(starts, i, side="right")) - 1
            out[j] = self._maps[s][i - starts[s]]
        lbl = out[:, :4].copy().view("<i4")[:, 0].astype(np.int32)
        img = out[:, 4:].reshape(len(idx), h, w, c)
        return img, lbl

    def _py_worker(self):
        h, w, c = self.shape
        while not self._stop.is_set():
            order = self._rng.permutation(self.n)
            for i in range(0, self.n - self.batch_size + 1,
                           self.batch_size):
                if self._stop.is_set():
                    return
                raw, lbl = self._record_batch(order[i:i + self.batch_size])
                img = raw.copy() if self.out_dtype == "u8" else \
                    (raw.astype(np.float32) - self.mean) / self.std
                if self.pad:
                    if self.out_dtype == "u8":
                        # mean-byte fill: borders normalize to 0.0 on
                        # device, matching the f32 plane's zero-fill
                        shifted = np.empty_like(img)
                        shifted[:] = np.clip(self.mean + 0.5, 0,
                                             255).astype(np.uint8)
                    else:
                        shifted = np.zeros_like(img)
                    for j in range(len(img)):
                        dy, dx = self._rng.randint(-self.pad,
                                                   self.pad + 1, 2)
                        y0, y1 = max(0, dy), min(h, h + dy)
                        x0, x1 = max(0, dx), min(w, w + dx)
                        shifted[j, y0:y1, x0:x1] = \
                            img[j, y0 - dy:y1 - dy, x0 - dx:x1 - dx]
                    img = shifted
                if self.hflip:
                    flips = self._rng.rand(len(img)) < 0.5
                    img[flips] = img[flips, :, ::-1]
                self._q.put((img, lbl))

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        h, w, c = self.shape
        if self.native:
            if getattr(self, "_handle", None) is None:
                raise RuntimeError("FilePrefetcher used after close()")
            lbl = np.empty((self.batch_size,), np.int32)
            if self.out_dtype == "u8":
                img = np.empty((self.batch_size, h, w, c), np.uint8)
                self._lib.bdl_prefetcher_next_u8(self._handle, _u8(img),
                                                 _i32(lbl))
            else:
                img = np.empty((self.batch_size, h, w, c), np.float32)
                self._lib.bdl_prefetcher_next(self._handle, _f32(img),
                                              _i32(lbl))
            return img, lbl
        if self._stop.is_set():
            # mirror the native-path guard; without it get() would
            # block forever on a queue whose producer has exited
            raise RuntimeError("FilePrefetcher used after close()")
        return self._q.get()

    def __iter__(self):
        while True:
            yield self.next()

    def close(self):
        if self.native:
            if getattr(self, "_handle", None):
                self._lib.bdl_prefetcher_destroy(self._handle)
                self._handle = None
        else:
            self._stop.set()
            try:
                while True:
                    self._q.get_nowait()
            except Exception:
                pass

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass
