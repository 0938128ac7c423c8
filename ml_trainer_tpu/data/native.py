"""ctypes bindings for the native batch worker (csrc/batch_worker.cpp).

``NativeLoader`` is a drop-in alternative to the Python ``Loader`` for
uint8-image array datasets: batch assembly (gather + crop + flip +
normalize) runs in C++ threads that stay ``queue_cap`` batches ahead of the
training loop — the torch DataLoader worker-pool role (SURVEY.md §2B)
without worker processes or pickling.  The shared library is built with g++
on first use if missing.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from ml_trainer_tpu.data.datasets import ArrayDataset
from ml_trainer_tpu.data.sampler import ShardedSampler

_CSRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "csrc")
)
_SOURCES = ("batch_worker.cpp", "jpeg_decoder.cpp")
_CXX = ("g++", "-O3", "-std=c++17", "-fPIC", "-pthread", "-Wall", "-shared")
_lib = None
_lib_lock = threading.Lock()


def _library_path() -> str:
    """The built library's path, keyed by the CONTENT of its sources and
    the compile line: a binary built from other sources has another name
    and is never loaded.  (File times say nothing — a copied tree does
    not keep them in order.)"""
    h = hashlib.sha256(" ".join(_CXX).encode())
    for name in _SOURCES:
        with open(os.path.join(_CSRC, name), "rb") as f:
            h.update(f.read())
    return os.path.join(_CSRC, f"libbatch_worker.{h.hexdigest()[:16]}.so")


def _build_library(lib_path: str) -> None:
    # Compile to a private temp path, then atomically publish: concurrent
    # processes (parallel pytest, multi-process workers) may build at
    # the same time, and one must never dlopen a half-written .so.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    subprocess.run(
        [*_CXX, "-o", tmp, *(os.path.join(_CSRC, s) for s in _SOURCES)],
        check=True,
        capture_output=True,
    )
    os.replace(tmp, lib_path)
    # Binaries of earlier source versions are dead weight now.
    for stale in glob.glob(os.path.join(_CSRC, "libbatch_worker.*.so")):
        if stale != lib_path:
            with contextlib.suppress(OSError):
                os.remove(stale)


def load_library() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib_path = _library_path()
        if not os.path.exists(lib_path):
            _build_library(lib_path)
        lib = ctypes.CDLL(lib_path)
        lib.batch_worker_create_sharded.restype = ctypes.c_void_p
        lib.batch_worker_create_sharded.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ]
        lib.batch_worker_start_epoch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_uint64,
        ]
        lib.batch_worker_next.restype = ctypes.c_int64
        lib.batch_worker_next.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.batch_worker_destroy.argtypes = [ctypes.c_void_p]
        lib.batch_worker_create_jpeg.restype = ctypes.c_void_p
        lib.batch_worker_create_jpeg.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ]
        lib.batch_worker_decode_errors.restype = ctypes.c_int64
        lib.batch_worker_decode_errors.argtypes = [ctypes.c_void_p]
        lib.jpeg_decode_expect.restype = ctypes.c_int
        lib.jpeg_decode_expect.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int,
        ]
        _lib = lib
        return lib


def native_available() -> bool:
    try:
        load_library()
        return True
    except Exception:
        return False


def jpeg_decode_np(data, shape) -> Optional[np.ndarray]:
    """Decode one baseline-JPEG byte buffer to uint8 [H, W, 3] through
    the NATIVE decoder — the same code path the C++ worker threads run,
    so Python-side decodes are bit-equal to worker batches.  Returns
    None when the native library is unavailable (callers fall back to
    PIL) and raises on a corrupt stream."""
    try:
        lib = load_library()
    except Exception:
        return None
    data = np.ascontiguousarray(np.frombuffer(bytes(data), np.uint8))
    out = np.empty(shape, np.uint8)
    rc = lib.jpeg_decode_expect(
        data.ctypes.data_as(ctypes.c_void_p), len(data),
        out.ctypes.data_as(ctypes.c_void_p), out.size,
        int(shape[1]), int(shape[0]),
    )
    if rc != 0:
        raise ValueError(f"jpeg_decode failed (rc={rc})")
    return out


def native_plan(dataset) -> Optional[dict]:
    """NativeLoader kwargs if this dataset can run through the fused C++
    pipeline with IDENTICAL semantics to the Python Loader + its transform:
    uint8 NHWC array data whose transform is the reference augmentation
    (RandomCrop(p=4)? + RandomHorizontalFlip? + ToFloat + Normalize,
    ref: src/utils/functions.py:5-12).  Returns None when the Python path
    must be used (foreign/no transform, float data, non-default flip p,
    crop size != image size)."""
    from ml_trainer_tpu.data.transforms import (
        Compose,
        Normalize,
        RandomCrop,
        RandomHorizontalFlip,
        ToFloat,
    )

    from ml_trainer_tpu.data.sharded import (
        ShardedImageDataset,
        ShardedJpegDataset,
    )

    data = getattr(dataset, "data", None)
    if isinstance(dataset, (ShardedImageDataset, ShardedJpegDataset)):
        # Memory-mapped shards: the native worker gathers from the mapped
        # segments directly (the beyond-RAM path); jpeg shards decode on
        # the worker threads first.
        if len(dataset.shape) != 3:
            return None
        h, w = dataset.shape[0], dataset.shape[1]
    elif (
        isinstance(data, np.ndarray)
        and data.dtype == np.uint8
        and data.ndim == 4
    ):
        h, w = data.shape[1], data.shape[2]
    else:
        return None
    t = getattr(dataset, "transform", None)
    if t is None:
        return None
    ts = list(t.transforms) if isinstance(t, Compose) else [t]
    i, pad, flip = 0, 0, False
    if i < len(ts) and isinstance(ts[i], RandomCrop):
        if ts[i].size != h or h != w:
            return None
        pad, i = ts[i].padding, i + 1
    if i < len(ts) and isinstance(ts[i], RandomHorizontalFlip):
        if ts[i].p != 0.5:
            return None
        flip, i = True, i + 1
    if not (i < len(ts) and isinstance(ts[i], ToFloat)):
        return None
    i += 1
    if not (i < len(ts) and isinstance(ts[i], Normalize)):
        return None
    normalize = (tuple(ts[i].mean.tolist()), tuple(ts[i].std.tolist()))
    i += 1
    if i != len(ts):
        return None
    return dict(pad=pad, flip=flip, normalize=normalize)


class NativeLoader:
    """C++-threaded Loader for uint8 NHWC image datasets.

    Mirrors the Python ``Loader`` iteration contract (len, set_epoch,
    yields (images, labels) numpy batches) with the reference's CIFAR-10
    augmentation fused into the native pass (crop pad 4 / flip / normalize,
    ref: src/utils/functions.py:5-12).
    """

    def __init__(
        self,
        dataset: ArrayDataset,
        batch_size: int,
        shuffle: bool = True,
        sampler: Optional[ShardedSampler] = None,
        pad: int = 4,
        flip: bool = True,
        normalize: Optional[Tuple[Tuple[float, ...], Tuple[float, ...]]] = None,
        num_threads: int = 4,
        queue_cap: int = 8,
        seed: int = 0,
        drop_last: bool = True,
    ):
        from ml_trainer_tpu.data.sharded import (
            ShardedImageDataset,
            ShardedJpegDataset,
        )

        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self._sampler = sampler
        self.seed = seed
        self.drop_last = drop_last
        self._epoch = 0
        self._jpeg = isinstance(dataset, ShardedJpegDataset)
        # Decode-error accounting baseline: the C++ counter is CUMULATIVE
        # across epochs, so every check compares against this snapshot
        # (taken at each epoch start) rather than the raw value —
        # otherwise an early ``break`` defers one epoch's corrupt samples
        # into a later epoch's raise.
        self._err_base = 0
        if self._jpeg:
            # Compressed path: segments are the mapped JPEG byte blobs;
            # per-segment offset tables locate each sample's stream.
            # Worker threads decode (csrc/jpeg_decoder.cpp) before the
            # fused augmentation — pixels exist only for in-flight
            # batches.
            if len(dataset.shape) != 3 or dataset.shape[2] != 3:
                raise ValueError("jpeg NativeLoader requires HWC RGB")
            self._segments = list(dataset.byte_maps)
            self._offsets = [
                np.ascontiguousarray(o, np.int64)
                for o in dataset.offset_tables
            ]
            h, w, c = dataset.shape
            seg_starts = dataset.shard_starts[:-1]
        elif isinstance(dataset, ShardedImageDataset):
            # Beyond-RAM path: the worker gathers straight from the
            # memory-mapped shard segments — the dataset is never copied
            # into process RAM.  (np.ascontiguousarray on a C-contiguous
            # memmap is a no-copy passthrough; keep references so the
            # mappings outlive the C++ worker.)
            if len(dataset.shape) != 3:
                raise ValueError("NativeLoader requires uint8 NHWC images")
            self._segments = [
                np.ascontiguousarray(m) for m in dataset.shard_maps
            ]
            h, w, c = dataset.shape
            seg_starts = dataset.shard_starts[:-1]
        else:
            if dataset.data.dtype != np.uint8 or dataset.data.ndim != 4:
                raise ValueError("NativeLoader requires uint8 NHWC image data")
            self._segments = [np.ascontiguousarray(dataset.data)]
            _, h, w, c = self._segments[0].shape
            seg_starts = [0]
        self._labels = np.ascontiguousarray(dataset.targets.astype(np.int32))
        self._shape = (h, w, c)
        if normalize is None:
            from ml_trainer_tpu.utils.functions import CIFAR10_MEAN, CIFAR10_STD

            normalize = (CIFAR10_MEAN, CIFAR10_STD)
        mean = (ctypes.c_float * c)(*normalize[0][:c])
        std = (ctypes.c_float * c)(*normalize[1][:c])
        lib = load_library()
        self._lib = lib
        n_segs = len(self._segments)
        seg_ptrs = (ctypes.c_void_p * n_segs)(
            *[s.ctypes.data for s in self._segments]
        )
        starts = (ctypes.c_int64 * n_segs)(*[int(s) for s in seg_starts])
        if self._jpeg:
            off_ptrs = (ctypes.c_void_p * n_segs)(
                *[o.ctypes.data for o in self._offsets]
            )
            self._handle = lib.batch_worker_create_jpeg(
                ctypes.cast(seg_ptrs, ctypes.POINTER(ctypes.c_void_p)),
                ctypes.cast(off_ptrs, ctypes.POINTER(ctypes.c_void_p)),
                ctypes.cast(starts, ctypes.POINTER(ctypes.c_int64)),
                n_segs,
                self._labels.ctypes.data_as(ctypes.c_void_p),
                len(dataset), h, w, c, pad, int(flip), 1, mean, std,
                self.batch_size, num_threads, queue_cap, seed + 1,
            )
        else:
            self._handle = lib.batch_worker_create_sharded(
                ctypes.cast(seg_ptrs, ctypes.POINTER(ctypes.c_void_p)),
                ctypes.cast(starts, ctypes.POINTER(ctypes.c_int64)),
                n_segs,
                self._labels.ctypes.data_as(ctypes.c_void_p),
                len(dataset), h, w, c, pad, int(flip), 1, mean, std,
                self.batch_size, num_threads, queue_cap, seed + 1,
            )
        if not self._handle:
            raise RuntimeError("native batch worker creation failed")

    @property
    def sampler(self):
        from ml_trainer_tpu.data.loader import _TrivialSampler

        return self._sampler if self._sampler is not None else _TrivialSampler(
            len(self.dataset)
        )

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch
        if self._sampler is not None:
            self._sampler.set_epoch(epoch)

    def __len__(self) -> int:
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def _indices(self) -> np.ndarray:
        if self._sampler is not None:
            return np.asarray(self._sampler.indices(), np.int64)
        if self.shuffle:
            rng = np.random.default_rng((self.seed, self._epoch))
            return rng.permutation(len(self.dataset)).astype(np.int64)
        return np.arange(len(self.dataset), dtype=np.int64)

    def __iter__(self):
        n_batches = len(self)
        need = n_batches * self.batch_size
        idx = self._indices().astype(np.int64, copy=False)
        if idx.size < need:
            # drop_last=False with a ragged tail: the C++ side
            # unconditionally copies n_batches*batch_size indices
            # (csrc/batch_worker.cpp start_epoch), so pad by wrapping —
            # same convention as ShardedSampler — rather than hand it a
            # short buffer (out-of-bounds read).  The final batch then
            # repeats leading samples instead of being short.
            idx = np.resize(idx, need)
        idx = np.ascontiguousarray(idx[:need], np.int64)
        if self._jpeg:
            # Re-baseline BEFORE the epoch runs: errors left unobserved by
            # a prior epoch's early break belong to that epoch, not this
            # one (stop()/__del__ surface them instead).
            self._err_base = self._lib.batch_worker_decode_errors(
                self._handle
            )
        self._lib.batch_worker_start_epoch(
            self._handle,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n_batches,
            self._epoch,
        )
        h, w, c = self._shape
        for _ in range(n_batches):
            images = np.empty((self.batch_size, h, w, c), np.float32)
            labels = np.empty((self.batch_size,), np.int32)
            got = self._lib.batch_worker_next(
                self._handle,
                images.ctypes.data_as(ctypes.c_void_p),
                labels.ctypes.data_as(ctypes.c_void_p),
            )
            if got < 0:
                return
            yield images, labels
        errs = self._decode_error_delta()
        # decode_error injection hook (resilience/faults.py): exercise the
        # corrupt-sample accounting path deterministically in tests —
        # identical semantics to real C++-counted decode failures.
        from ml_trainer_tpu.resilience.faults import active_plan

        plan = active_plan()
        if plan is not None and plan.fire(
            "decode_error", epoch=self._epoch
        ) is not None:
            errs += 1
        if errs:
            # Corrupt streams were zero-filled to keep shapes; fail
            # the epoch loudly rather than train on silent zeros.
            raise RuntimeError(
                f"{errs} sample(s) failed JPEG decode this epoch"
            )

    def _decode_error_delta(self) -> int:
        """New decode errors since the last check (delta against the
        cumulative C++ counter; consumes what it reports)."""
        if not self._jpeg or not getattr(self, "_handle", None):
            return 0
        errs = int(self._lib.batch_worker_decode_errors(self._handle))
        delta = errs - self._err_base
        self._err_base = errs
        return delta

    def stop(self) -> None:
        """Tear down the C++ worker now (idempotent).  Raises if decode
        errors accumulated since the last check — a consumer that broke
        out of an epoch early still hears about its corrupt samples."""
        handle = getattr(self, "_handle", None)
        if not handle:
            return
        errs = self._decode_error_delta()
        self._lib.batch_worker_destroy(handle)
        self._handle = None
        if errs:
            raise RuntimeError(
                f"{errs} sample(s) failed JPEG decode since the last check"
            )

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            errs = self._decode_error_delta()
            self._lib.batch_worker_destroy(handle)
            self._handle = None
            if errs:
                # Raising in __del__ is unraisable noise; warn instead so
                # the corruption is at least visible.
                import warnings

                warnings.warn(
                    f"NativeLoader destroyed with {errs} unreported JPEG "
                    "decode error(s)"
                )
