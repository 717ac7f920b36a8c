"""Host-side RGB -> BT.601 studio-range YUV 4:2:0 (the capture path).

One implementation shared by every encoder's host-color path (H.264, VP8)
so the conversion cannot drift between codecs.  Its bytes are defined by
three cv2 calls:

- Y from the fused fixed-point SIMD ``cv2.COLOR_RGB2YUV_I420`` call
  (matches ops/color ``matrix="video"`` within 1 LSB — the call's
  top-left-picked chroma is discarded),
- chroma from the 2x2-averaged half-res RGB (the color matrix is affine,
  so average-then-transform == transform-then-average within rounding):
  an ``INTER_AREA`` resize, then ``cv2.transform`` with the two chroma
  rows over the quarter-size picture.

``cvtColor`` and ``resize`` run on cv2's own thread pool; ``cv2.transform``
does NOT: it is a generic float matrix pass on ONE thread whatever
``cv2.getNumThreads()`` says.  On a chip's host (13 cores, PR 41) the
three read 0.17 + 0.13 + 2.2 ms at 1080p (and 1.1 ms of ``np.pad`` copies
then), 0.27 + 0.20 + 4.4 at 2560x1600, 0.5 + 0.35 + 8.9 at 4K: the
single-threaded step was 60-90% of the conversion (PERF.md section 7).
All three are per pixel or per 2x2 block, so a picture cut on even rows
converts to the same bytes band by band.
Two roads, chosen from what the function can see (the picture, the cores
the process may run on, whether the native library was built):

- ``bands``: ONE call into native/colour.cpp, which makes Y, the 2x2 mean
  and both chroma samples in one sweep over each band, the bands on its
  own small thread pool, straight into planes allocated at the padded
  size: the same integer and float32 arithmetic as the three calls, byte
  for byte (tests/test_hostcolor_bands.py, every RGB triple included);
  0.56 / 0.78 / 1.5-2.0 ms at those sizes on that host, where the three
  calls and the pad took 3.5 / 5.1 / 10-12.6.
- ``whole``: the three cv2 calls over the whole picture on the calling
  thread: one core (the single-core capture host keeps its path), a
  picture too small to be worth a hand-off, or no C++ toolchain.

``dngd_encoder_colour_total{road=}`` says which a conversion took.
The float fallback (no cv2) keeps the same matrix and chroma siting.
"""

from __future__ import annotations

import os

import numpy as np

from ..native import lib as native_lib
from ..obs import metrics as obsm

# BT.601 studio-range chroma rows (Cb, Cr) with offsets — the same matrix
# as ops/color.rgb_to_yuv420(matrix="video").
_CBCR_M = np.array(
    [[-37.797 / 255, -74.203 / 255, 112.0 / 255, 128.0],
     [112.0 / 255, -93.786 / 255, -18.214 / 255, 128.0]], np.float64)

_Y_M = np.array([65.481 / 255, 128.553 / 255, 24.966 / 255], np.float64)

# what cv2.transform makes of _CBCR_M for 8-bit input, for native/colour.cpp
_CBCR_M32 = np.ascontiguousarray(_CBCR_M, np.float32)

# More bands than this buy nothing and the process has other pools: cv2's
# own, the CABAC engine's (native/cabac.cpp: a worker a core), XLA's.
# native/colour.cpp keeps one worker fewer (the caller takes a band).
_MAX_BANDS = 8
# A band under this many pixels costs more to hand to another thread than
# to convert (1080p is 7 bands; under 1024x512 a picture is one).
_MIN_BAND_PIXELS = 1 << 18

_M_COLOUR = obsm.counter(
    "dngd_encoder_colour_total",
    "Host RGB -> YUV 4:2:0 conversions (utils/hostcolor, one a frame): "
    "bands = one fused pass of native/colour.cpp over row bands on its "
    "thread pool; whole = the cv2 calls (or the float fallback) over the "
    "whole picture on the calling thread (one core, a small picture, or "
    "no native library)",
    ("road",))
_M_COLOUR_BANDS = _M_COLOUR.labels("bands")
_M_COLOUR_WHOLE = _M_COLOUR.labels("whole")
_M_BANDS = obsm.gauge(
    "dngd_encoder_colour_bands",
    "Row bands of the last host colour conversion (1 = the whole picture "
    "on the calling thread)")


def _cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):       # not Linux
        return os.cpu_count() or 1


def _bands(h: int, w: int) -> int:
    """Row bands for an (h, w) picture: as many as the cores, the cap and
    the picture's size allow; 1 without the native pass."""
    n = min(_MAX_BANDS, _cores(), h * w // _MIN_BAND_PIXELS, h // 2)
    return n if n > 1 and native_lib.has_colour() else 1


def _convert_whole(cv2, rgb, y, u, v) -> None:
    """The picture into the top-left of ``y``, ``u`` and ``v`` (which may
    be wider and taller: the pad is the caller's) by the three cv2 calls
    that define the conversion."""
    h, w = rgb.shape[:2]
    y[:h, :w] = cv2.cvtColor(rgb, cv2.COLOR_RGB2YUV_I420)[:h]
    half = cv2.resize(rgb, (w // 2, h // 2), interpolation=cv2.INTER_AREA)
    cbcr = cv2.transform(half, _CBCR_M)
    u[:h // 2, :w // 2] = cbcr[..., 0]
    v[:h // 2, :w // 2] = cbcr[..., 1]


def _edge_pad(p: np.ndarray, h: int, w: int) -> None:
    """Replicate column w-1 to the right and row h-1 below, in place:
    what ``np.pad(mode="edge")`` gives the (h, w) picture in ``p``."""
    if w < p.shape[1]:
        p[:h, w:] = p[:h, w - 1:w]
    if h < p.shape[0]:
        p[h:] = p[h - 1]


def rgb_to_yuv420_host(rgb: np.ndarray, pad_h: int, pad_w: int,
                       float_fallback: bool = True):
    """(H, W, 3) uint8 RGB -> (y, cb, cr) uint8 planes, edge-padded to
    (pad_h, pad_w).  H and W must be even (callers gate).  Every call
    returns planes of its own: callers keep them (the damage chain's
    luma) and hand them to asynchronous device copies.

    With ``float_fallback=False``, returns None when cv2 is unavailable —
    for callers whose device-side conversion beats a host float path."""
    rgb = np.ascontiguousarray(rgb)
    h, w = rgb.shape[:2]
    try:
        import cv2
    except Exception:
        cv2 = None
    if cv2 is None and not float_fallback:
        return None
    y = np.empty((pad_h, pad_w), np.uint8)
    u = np.empty((pad_h // 2, pad_w // 2), np.uint8)
    v = np.empty((pad_h // 2, pad_w // 2), np.uint8)
    bands = 1
    if cv2 is not None:
        # runtime cv2 errors propagate loudly — only a MISSING cv2 selects
        # a fallback (a transient error must not silently flip the whole
        # process to a different conversion path)
        bands = _bands(h, w)
        if bands == 1:
            _convert_whole(cv2, rgb, y, u, v)
        else:
            native_lib.rgb_to_yuv420_bands(rgb, y, u, v, _CBCR_M32, bands)
    else:
        f = rgb.astype(np.float64)
        y[:h, :w] = np.clip(np.round(f @ _Y_M + 16.0), 0, 255)
        hf = f.reshape(h // 2, 2, w // 2, 2, 3).mean(axis=(1, 3))
        cbcr = hf @ _CBCR_M[:, :3].T + _CBCR_M[:, 3]
        cbcr = np.clip(np.round(cbcr), 0, 255)
        u[:h // 2, :w // 2] = cbcr[..., 0]
        v[:h // 2, :w // 2] = cbcr[..., 1]
    if (pad_h, pad_w) != (h, w):
        _edge_pad(y, h, w)
        _edge_pad(u, h // 2, w // 2)
        _edge_pad(v, h // 2, w // 2)
    (_M_COLOUR_WHOLE if bands == 1 else _M_COLOUR_BANDS).inc()
    _M_BANDS.set(bands)
    return y, u, v
