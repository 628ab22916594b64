"""ctypes bridge to the native JPEG fast path (jpeg_loader.cc).

Compiles the C++ source on demand with g++ (``-O3 -shared -fPIC -ljpeg``)
into a shared object next to the source, named after a hash of that
source — so the only binary that can ever load is one built from the
``jpeg_loader.cc`` sitting beside it — then exposes:

* :func:`available` — True when the toolchain + libjpeg exist and the
  library compiled; every consumer must branch on this and fall back to
  the PIL path (the framework never *requires* the native library).
* :func:`decode_jpeg` — bytes -> uint8 ``[S, S, 3]`` via scaled decode +
  fused resize/crop (modes: ``"squash"`` / ``"shorter_crop"``, matching
  ``transforms.Resize`` / ``ResizeShorter+CenterCrop``).
* :func:`decode_jpeg_file` — same, from a path.

Thread-safe: compilation is locked; the C call releases the GIL (ctypes
default), so DataLoader threads decode truly in parallel.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_SRC = Path(__file__).parent / "jpeg_loader.cc"
_MODES = {"squash": 0, "shorter_crop": 1}

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _so_path() -> Optional[Path]:
    """Where the object built from the current source lives:
    ``_jpeg_loader.<sha256 of jpeg_loader.cc>.so``. A binary from an
    edited, older or foreign source has another name and is never
    opened (mtimes say nothing after a checkout or a copy)."""
    try:
        digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    except OSError:
        return None
    return _SRC.with_name(f"_jpeg_loader.{digest}.so")


def _compile(so: Path) -> bool:
    # Build to a process-unique temp name and rename into place: rename is
    # atomic on POSIX, so concurrent first-use compiles (multi-host runs
    # over a shared checkout) never dlopen a half-written file.
    tmp = so.with_name(f".{so.name}.{os.getpid()}.tmp")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-o", str(tmp), str(_SRC),
           "-ljpeg"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
        if proc.returncode != 0 or not tmp.is_file():
            return False
        os.replace(tmp, so)
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        tmp.unlink(missing_ok=True)
    for old in so.parent.glob("_jpeg_loader*.so"):
        if old != so:  # objects of earlier sources: never loadable again
            old.unlink(missing_ok=True)
    return so.is_file()


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("PSR_TPU_NO_NATIVE"):
            return None
        so = _so_path()
        if so is None or (not so.is_file() and not _compile(so)):
            return None
        lib = _open(so)
        if lib is None:
            return None
        lib.psr_decode_jpeg.restype = ctypes.c_int
        lib.psr_decode_jpeg.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_uint8)]
        lib.psr_resize_crop.restype = ctypes.c_int
        lib.psr_resize_crop.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_uint8)]
        lib.psr_resize_crop_f32.restype = ctypes.c_int
        lib.psr_resize_crop_f32.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float)]
        lib.psr_u8_to_f32.restype = ctypes.c_int
        lib.psr_u8_to_f32.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float)]
        _lib = lib
        return _lib


_ABI = 3


def _open(path: Path) -> Optional[ctypes.CDLL]:
    try:
        lib = ctypes.CDLL(str(path))
        if lib.psr_abi_version() != _ABI:
            return None
        return lib
    except (OSError, AttributeError):
        # Unloadable file, or a foreign .so without our probe symbol —
        # fall back to PIL rather than crash (the module contract).
        return None


def available() -> bool:
    """Whether the native decoder compiled and loaded on this host."""
    return _load() is not None


def resize_crop(arr: np.ndarray, top: int, left: int, crop_h: int,
                crop_w: int, target: int) -> Optional[np.ndarray]:
    """Bilinear-resize a crop box of a uint8 HWC RGB array to
    ``[target, target, 3]`` in one native pass (PIL crop+resize affine).
    None when unavailable or the box/array is unsupported.

    No antialiasing: point-sampled bilinear matches PIL closely up to
    ~1.5x reductions (the RandomResizedCrop-on-packed-shards regime,
    where reduction <= pack_size/image_size) but aliases beyond that —
    for heavy downscales use the PIL path.
    """
    lib = _load()
    if (lib is None or arr.dtype != np.uint8 or arr.ndim != 3
            or arr.shape[2] != 3):
        return None
    arr = np.ascontiguousarray(arr)
    out = np.empty((target, target, 3), np.uint8)
    rc = lib.psr_resize_crop(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        arr.shape[0], arr.shape[1], top, left, crop_h, crop_w, target,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if rc != 0:
        return None
    return out


def _f3(v) -> "np.ndarray":
    """Broadcast a scalar or [3] vector to a contiguous float32 [3]."""
    out = np.ascontiguousarray(np.broadcast_to(
        np.asarray(v, np.float32), (3,)))
    return out


def resize_crop_f32(arr: np.ndarray, top: int, left: int, crop_h: int,
                    crop_w: int, target: int, *, hflip: bool = False,
                    scale=1.0 / 255.0, offset=0.0) -> Optional[np.ndarray]:
    """Fused RandomResizedCrop(+flip)+normalize: one native pass from a
    uint8 HWC frame to float32 ``[target, target, 3]`` with
    ``out = round_u8(bilinear) * scale + offset`` per channel. Bit-equal
    to :func:`resize_crop` + flip + the numpy affine, ~4x faster (it never
    materializes the uint8 intermediate or re-reads it for conversion).
    None when unavailable/unsupported (callers fall back)."""
    lib = _load()
    if (lib is None or arr.dtype != np.uint8 or arr.ndim != 3
            or arr.shape[2] != 3):
        return None
    arr = np.ascontiguousarray(arr)
    s, o = _f3(scale), _f3(offset)
    out = np.empty((target, target, 3), np.float32)
    rc = lib.psr_resize_crop_f32(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        arr.shape[0], arr.shape[1], top, left, crop_h, crop_w, target,
        1 if hflip else 0,
        s.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        o.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    if rc != 0:
        return None
    return out


def u8_to_f32(arr: np.ndarray, scale=1.0 / 255.0,
              offset=0.0) -> Optional[np.ndarray]:
    """uint8 HWC RGB -> float32 with a fused per-channel affine
    (``x * scale + offset``) — the ToFloatArray conversion, natively.
    None when unavailable/unsupported."""
    lib = _load()
    if (lib is None or arr.dtype != np.uint8 or arr.ndim != 3
            or arr.shape[2] != 3):
        return None
    arr = np.ascontiguousarray(arr)
    s, o = _f3(scale), _f3(offset)
    out = np.empty(arr.shape, np.float32)
    rc = lib.psr_u8_to_f32(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        arr.shape[0] * arr.shape[1],
        s.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        o.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    if rc != 0:
        return None
    return out


def decode_jpeg(data: bytes, target: int, mode: str = "squash",
                resize: Optional[int] = None) -> Optional[np.ndarray]:
    """Decode a JPEG byte stream to uint8 ``[target, target, 3]`` RGB.

    ``mode="squash"`` is ``Resize((target, target))``; ``"shorter_crop"``
    is ``ResizeShorter(resize) + CenterCrop(target)`` (``resize`` defaults
    to ``target``). Returns None when the native library is unavailable or
    the stream cannot be decoded (corrupt data, exotic color space) —
    callers fall back to PIL, which handles the long tail.
    """
    lib = _load()
    if lib is None:
        return None
    out = np.empty((target, target, 3), np.uint8)
    rc = lib.psr_decode_jpeg(
        data, len(data), resize if resize is not None else target, target,
        _MODES[mode], out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if rc != 0:
        return None
    return out


def decode_jpeg_file(path, target: int, mode: str = "squash",
                     resize: Optional[int] = None) -> Optional[np.ndarray]:
    """:func:`decode_jpeg` from a file path (None on any failure)."""
    try:
        data = Path(path).read_bytes()
    except OSError:
        return None
    return decode_jpeg(data, target, mode, resize)
