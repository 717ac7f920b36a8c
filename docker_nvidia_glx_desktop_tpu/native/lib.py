"""Build-on-demand loader for the native entropy library.

Compiles ``*.cpp`` in this directory into one shared object with g++ (cached
by source mtime under ``~/.cache/tpudesktop``), then exposes ctypes bindings.
If no C++ toolchain is available the callers fall back to the pure-Python
reference implementations in :mod:`..bitstream` — same bytes, just slower.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import pathlib
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

_SRC_DIR = pathlib.Path(__file__).parent
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _cache_dir() -> pathlib.Path:
    d = pathlib.Path(os.environ.get("TPUDESKTOP_CACHE",
                                    os.path.expanduser("~/.cache/tpudesktop")))
    d.mkdir(parents=True, exist_ok=True)
    return d


# Sources with external library deps build separately (see open_xcapture;
# X11 headers exist only in the container image) — never into the entropy
# library, whose build must succeed on bare TPU VMs.
_STANDALONE = {"xcapture.cpp"}


def _build() -> Optional[pathlib.Path]:
    sources = sorted(s for s in _SRC_DIR.glob("*.cpp")
                     if s.name not in _STANDALONE)
    if not sources:
        return None
    # Extra flags (e.g. "-fsanitize=undefined -fno-sanitize-recover=all"
    # for the CI UBSan smoke) come from the environment and participate
    # in the cache tag so sanitized and plain builds never collide.
    extra = os.environ.get("TPUDESKTOP_CXXFLAGS", "").split()
    tag = hashlib.sha256()
    tag.update(" ".join(extra).encode())
    for s in sources:
        tag.update(s.name.encode())
        tag.update(s.read_bytes())
    so_path = _cache_dir() / f"libtpudesktop_entropy_{tag.hexdigest()[:16]}.so"
    if so_path.exists():
        return so_path
    # Build to a private temp name and rename into place: a crashed or
    # concurrent build must never leave a truncated .so at the cache path
    # (ctypes would then fail on every later run).
    tmp_path = so_path.with_suffix(f".tmp{os.getpid()}")
    # -ffp-contract=off: colour.cpp's float32 chroma must round a product
    # and a sum apart, as cv2.transform does (no fused multiply-add)
    cmd = ["g++", "-O3", "-march=native", "-ffp-contract=off", "-shared",
           "-fPIC", "-std=c++17", "-pthread"] + extra + \
          ["-o", str(tmp_path)] + \
          [str(s) for s in sources]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp_path, so_path)
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        log.warning("native entropy build failed (%s); using Python fallback", e)
        tmp_path.unlink(missing_ok=True)
        return None
    return so_path


def get_lib() -> Optional[ctypes.CDLL]:
    """The compiled library, or None if unavailable (Python fallback)."""
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(str(so))
        except OSError as e:
            log.warning("native entropy load failed (%s); using Python "
                        "fallback", e)
            return None
        lib.tpudesktop_entropy_abi_version.restype = ctypes.c_int32
        if lib.tpudesktop_entropy_abi_version() != 1:
            log.warning("native entropy ABI mismatch; using Python fallback")
            return None

        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")

        lib.jpeg_component_histogram.argtypes = [i32p, ctypes.c_int64, i64p, i64p]
        lib.jpeg_component_histogram.restype = None
        lib.jpeg_encode_scan.argtypes = [
            i32p, i32p, i32p, ctypes.c_int64,
            u32p, u8p, u32p, u8p, u32p, u8p, u32p, u8p,
            u8p, ctypes.c_int64,
        ]
        lib.jpeg_encode_scan.restype = ctypes.c_int64
        lib.h264_emulation_prevention.argtypes = [
            u8p, ctypes.c_int64, u8p, ctypes.c_int64]
        lib.h264_emulation_prevention.restype = ctypes.c_int64
        lib.h264_annexb_rows.argtypes = [
            u8p, ctypes.c_int64, i64p, i64p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int32,
            u8p, ctypes.c_int64]
        lib.h264_annexb_rows.restype = ctypes.c_int64
        global _CABAC_OK
        if hasattr(lib, "h264_cabac_intra_slices"):
            lib.tpudesktop_cabac_abi_version.restype = ctypes.c_int32
            if lib.tpudesktop_cabac_abi_version() != 1:
                log.warning("native CABAC ABI mismatch; Python fallback")
                _LIB = lib
                return _LIB
            _CABAC_OK = True
            i8p = np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS")
            i64ap = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            lib.h264_cabac_intra_slices.argtypes = [
                i32p, i32p, i32p, i32p, i32p, i32p,     # levels
                i32p, u8p, i32p, i32p,                  # modes/i4
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
                i8p, u8p, u8p, u8p,                     # tables
                u8p, i64ap, ctypes.c_int64,
            ]
            lib.h264_cabac_intra_slices.restype = ctypes.c_int64
            lib.h264_cabac_p_slices.argtypes = [
                i32p, i32p, i32p, i32p, i32p, i32p,     # mv + levels
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
                i8p, u8p, u8p, u8p,                     # tables
                u8p, i64ap, ctypes.c_int64,
            ]
            lib.h264_cabac_p_slices.restype = ctypes.c_int64
            global _ENGINE_OK
            if hasattr(lib, "h264_cabac_engine_rows"):
                _ENGINE_OK = True
                lib.h264_cabac_engine_rows.argtypes = [
                    np.ctypeslib.ndpointer(np.uint32,
                                           flags="C_CONTIGUOUS"),
                    i64ap, i64ap, ctypes.c_int64, ctypes.c_int32,
                    i8p, u8p, u8p, u8p,                 # tables
                    u8p, i64ap, ctypes.c_int64,
                ]
                lib.h264_cabac_engine_rows.restype = ctypes.c_int64
        global _COLOUR_OK
        if hasattr(lib, "rgb_to_yuv420_bands"):
            lib.tpudesktop_colour_abi_version.restype = ctypes.c_int32
            if lib.tpudesktop_colour_abi_version() == 1:
                _COLOUR_OK = True
                lib.rgb_to_yuv420_bands.argtypes = [
                    u8p, ctypes.c_int64, ctypes.c_int64,
                    u8p, ctypes.c_int64, u8p, u8p, ctypes.c_int64,
                    np.ctypeslib.ndpointer(np.float32,
                                           flags="C_CONTIGUOUS"),
                    ctypes.c_int32]
                lib.rgb_to_yuv420_bands.restype = None
        global _LEVELPACK_OK
        if hasattr(lib, "level_unpack_rows"):
            lib.tpudesktop_levelpack_abi_version.restype = ctypes.c_int32
            if lib.tpudesktop_levelpack_abi_version() == 1:
                _LEVELPACK_OK = True
                u32cp = np.ctypeslib.ndpointer(np.uint32,
                                               flags="C_CONTIGUOUS")
                i64cp = np.ctypeslib.ndpointer(np.int64,
                                               flags="C_CONTIGUOUS")
                lib.level_unpack_rows.argtypes = [
                    u32cp, i64cp, ctypes.c_int64, ctypes.c_int64,
                    np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ]
                lib.level_unpack_rows.restype = None
        _LIB = lib
        return _LIB


def available() -> bool:
    return get_lib() is not None


_CABAC_OK = False
_ENGINE_OK = False
_LEVELPACK_OK = False
_COLOUR_OK = False


def has_cabac() -> bool:
    """CABAC entry points present AND their ABI version checked."""
    return get_lib() is not None and _CABAC_OK


def has_cabac_engine() -> bool:
    """Engine-only entry (device-binarized record streams) present."""
    return get_lib() is not None and _CABAC_OK and _ENGINE_OK


def cabac_engine_rows(payload: np.ndarray, row_off: np.ndarray,
                      row_bits: np.ndarray, rows: int, qp: int,
                      ctx_init, rng, tmps, tlps, cap: int,
                      out: np.ndarray):
    """Run the arithmetic engine over per-row record streams, into the
    CALLER's ``out`` (C-contiguous uint8, at least ``rows * cap`` long;
    ``bitstream/h264_cabac._out_buffer`` owns it and keeps it between
    frames: nothing but ``lens`` is allocated here).

    Returns ``lens``: row ``r``'s slice payload is the first ``lens[r]``
    bytes of ``out`` at ``r * cap`` (:func:`annexb_rows` frames it where
    it lies; bytes of ``out`` behind ``rows * cap`` are not touched), or
    the int failure code:
    -1 = output cap overflow (caller may retry with a larger cap),
    -2 = malformed record stream (retrying cannot help — the caller
    should fall back dense and name the real failure)."""
    lib = get_lib()
    assert lib is not None and _ENGINE_OK
    assert out.dtype == np.uint8 and out.size >= rows * cap
    lens = np.zeros(rows, np.int64)
    rc = lib.h264_cabac_engine_rows(
        np.ascontiguousarray(payload, np.uint32),
        np.ascontiguousarray(row_off, np.int64),
        np.ascontiguousarray(row_bits, np.int64),
        rows, int(qp), ctx_init, rng, tmps, tlps, out, lens, cap)
    if rc != 0:
        return int(rc)
    return lens


def has_level_unpack() -> bool:
    return get_lib() is not None and _LEVELPACK_OK


def level_unpack(payload: np.ndarray, row_off: np.ndarray, rows: int,
                 slots_per_row: int) -> np.ndarray:
    """Threaded C decode of the level-pack transport (rows parallel)."""
    lib = get_lib()
    assert lib is not None and _LEVELPACK_OK
    out = np.empty(rows * slots_per_row, np.int32)
    lib.level_unpack_rows(
        np.ascontiguousarray(payload, np.uint32),
        np.ascontiguousarray(row_off, np.int64),
        rows, slots_per_row, out)
    return out


# ---------------------------------------------------------------------------
# High-level helpers
# ---------------------------------------------------------------------------

def jpeg_histograms(y_flat: np.ndarray, cb: np.ndarray, cr: np.ndarray):
    """DC/AC histograms per table id (0=luma, 1=chroma) via C."""
    lib = get_lib()
    assert lib is not None
    dc_hist = [np.zeros(17, np.int64), np.zeros(17, np.int64)]
    ac_hist = [np.zeros(256, np.int64), np.zeros(256, np.int64)]
    lib.jpeg_component_histogram(np.ascontiguousarray(y_flat, np.int32),
                                 y_flat.shape[0], dc_hist[0], ac_hist[0])
    for comp in (cb, cr):
        lib.jpeg_component_histogram(np.ascontiguousarray(comp, np.int32),
                                     comp.shape[0], dc_hist[1], ac_hist[1])
    return dc_hist, ac_hist


def _table_arrays(table):
    """HuffmanTable -> dense (codes uint32[256], lens uint8[256]) arrays."""
    codes = np.zeros(256, np.uint32)
    lens = np.zeros(256, np.uint8)
    n = len(table.codes)
    codes[:n] = table.codes.astype(np.uint32)
    lens[:n] = table.lengths.astype(np.uint8)
    return codes, lens


def emulation_prevention(rbsp: bytes) -> bytes:
    """H.264 EPB escaping via C (falls back at the call site if no lib)."""
    lib = get_lib()
    assert lib is not None
    src = np.frombuffer(rbsp, np.uint8)
    out = np.empty(len(src) * 3 // 2 + 16, np.uint8)
    n = lib.h264_emulation_prevention(src, len(src), out, len(out))
    assert n >= 0
    return out[:n].tobytes()


def annexb_rows(src: np.ndarray, row_off: np.ndarray, row_len: np.ndarray,
                nal_header: int, cap: int, *, prefix: bytes = b"",
                mb_step: int = 0, hdr_tail: int = 0,
                hdr_tail_nbits: int = 0):
    """A frame's row slices as Annex-B NAL units behind ``prefix``, in ONE
    C call (native/entropy.cpp ``h264_annexb_rows``; the caller and the
    Python road it must equal: bitstream/h264.py ``annexb_rows``).

    ``cap`` bounds the NALs' bytes.  Returns the bytes, or -1 where
    ``cap`` was short (the caller retries with the worst case)."""
    lib = get_lib()
    assert lib is not None
    src = np.ascontiguousarray(src, np.uint8).reshape(-1)
    row_off = np.ascontiguousarray(row_off, np.int64)
    row_len = np.ascontiguousarray(row_len, np.int64)
    rows = len(row_off)
    if len(row_len) != rows or not 0 <= rows * mb_step < 1 << 31 \
            or not 0 <= hdr_tail_nbits <= 64 or hdr_tail >> hdr_tail_nbits:
        raise ValueError("annexb_rows: malformed rows or slice header")
    out = np.empty(len(prefix) + cap, np.uint8)
    out[:len(prefix)] = np.frombuffer(prefix, np.uint8)
    n = lib.h264_annexb_rows(src, src.size, row_off, row_len, rows,
                             nal_header, mb_step, hdr_tail, hdr_tail_nbits,
                             out[len(prefix):], cap)
    if n == -2:
        raise ValueError("annexb_rows: a row lies outside its buffer")
    if n < 0:
        return int(n)
    return out[:len(prefix) + n].tobytes()


def has_colour() -> bool:
    """The fused colour pass (native/colour.cpp) is built and its ABI
    version checked."""
    return get_lib() is not None and _COLOUR_OK


def rgb_to_yuv420_bands(rgb: np.ndarray, y: np.ndarray, u: np.ndarray,
                        v: np.ndarray, m: np.ndarray, bands: int) -> None:
    """(h, w, 3) uint8 RGB into the top-left (h, w) of ``y`` and
    (h/2, w/2) of ``u`` and ``v`` (planes at least that large: the pad is
    the caller's), as ``bands`` row bands on the library's own pool, in
    ONE C call (native/colour.cpp; the caller and the cv2 road it must
    equal byte for byte: utils/hostcolor.py).  ``m``: the 2x4 chroma
    matrix as float32."""
    lib = get_lib()
    assert lib is not None and _COLOUR_OK
    h, w = rgb.shape[:2]
    if any(a.dtype != np.uint8 or not a.flags.c_contiguous
           for a in (rgb, y, u, v)) \
            or rgb.shape != (h, w, 3) or h % 2 or w % 2 \
            or y.ndim != 2 or y.shape[0] < h or y.shape[1] < w \
            or u.shape != v.shape or u.ndim != 2 \
            or u.shape[0] < h // 2 or u.shape[1] < w // 2 \
            or m.dtype != np.float32 or m.shape != (2, 4) \
            or not m.flags.c_contiguous or bands < 1:
        raise ValueError("rgb_to_yuv420_bands: malformed picture or planes")
    lib.rgb_to_yuv420_bands(rgb, h, w, y, y.shape[1], u, v, u.shape[1], m,
                            bands)


# ---------------------------------------------------------------------------
# X display capture (container runtime only; needs libX11/libXext headers)
# ---------------------------------------------------------------------------

_XCAP_LIB: Optional[ctypes.CDLL] = None
_XCAP_TRIED = False


class XCapture:
    """Handle over the xcapture.cpp shim: grab the root window as RGB."""

    def __init__(self, lib: ctypes.CDLL, handle):
        self._lib = lib
        self._h = handle
        self._w = lib.xcap_width(handle)
        self._hgt = lib.xcap_height(handle)
        self._buf = np.empty((self._hgt, self._w, 3), np.uint8)

    def size(self):
        return self._w, self._hgt

    def grab(self) -> np.ndarray:
        rc = self._lib.xcap_grab(self._h, self._buf)
        if rc != 0:
            raise RuntimeError("XShmGetImage/XGetImage failed")
        return self._buf

    def close(self) -> None:
        if self._h is not None:
            self._lib.xcap_close(self._h)
            self._h = None


def _xcap_lib() -> Optional[ctypes.CDLL]:
    global _XCAP_LIB, _XCAP_TRIED
    with _LOCK:
        if _XCAP_TRIED:
            return _XCAP_LIB
        _XCAP_TRIED = True
        src = _SRC_DIR / "xcapture.cpp"
        if not src.exists():
            return None
        tag = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
        so_path = _cache_dir() / f"libtpudesktop_xcap_{tag}.so"
        if not so_path.exists():
            tmp = so_path.with_suffix(f".tmp{os.getpid()}")
            cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                   "-o", str(tmp), str(src), "-lX11", "-lXext"]
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               timeout=120)
                os.replace(tmp, so_path)
            except (subprocess.SubprocessError, FileNotFoundError,
                    OSError) as e:
                log.info("xcapture build unavailable (%s): no X11 dev "
                         "libraries on this host", e)
                pathlib.Path(tmp).unlink(missing_ok=True)
                return None
        try:
            lib = ctypes.CDLL(str(so_path))
        except OSError as e:
            log.info("xcapture load failed (%s)", e)
            return None
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.xcap_open.argtypes = [ctypes.c_char_p]
        lib.xcap_open.restype = ctypes.c_void_p
        lib.xcap_width.argtypes = [ctypes.c_void_p]
        lib.xcap_width.restype = ctypes.c_int
        lib.xcap_height.argtypes = [ctypes.c_void_p]
        lib.xcap_height.restype = ctypes.c_int
        lib.xcap_grab.argtypes = [ctypes.c_void_p, u8p]
        lib.xcap_grab.restype = ctypes.c_int
        lib.xcap_close.argtypes = [ctypes.c_void_p]
        lib.xcap_close.restype = None
        _XCAP_LIB = lib
        return _XCAP_LIB


def open_xcapture(display: str = ":0") -> Optional[XCapture]:
    """Open the X display for capture; None when the shim/display is
    unavailable (callers fall back to the synthetic source)."""
    lib = _xcap_lib()
    if lib is None:
        return None
    handle = lib.xcap_open(display.encode())
    if not handle:
        return None
    return XCapture(lib, handle)


def jpeg_encode_scan(y_flat, cb, cr, tables) -> bytes:
    """Emit the interleaved scan via C.  ``tables`` = (dc_l, ac_l, dc_c, ac_c)."""
    lib = get_lib()
    assert lib is not None
    nmcu = cb.shape[0]
    args = []
    for t in tables:
        args.extend(_table_arrays(t))
    # Worst case ~ 2x raw samples; grow on overflow.
    cap = max(1 << 16, int(y_flat.size + cb.size + cr.size) * 4)
    while True:
        out = np.empty(cap, np.uint8)
        n = lib.jpeg_encode_scan(
            np.ascontiguousarray(y_flat, np.int32),
            np.ascontiguousarray(cb, np.int32),
            np.ascontiguousarray(cr, np.int32),
            nmcu, *args, out, cap)
        if n >= 0:
            return out[:n].tobytes()
        cap *= 2
