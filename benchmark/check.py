"""What decides ``correct``, and the picture-side metrics.

cv2 (its bundled ffmpeg) is the independent decoder.  With
``CAP_PROP_CONVERT_RGB`` off it hands back the decoder's own luma plane, so
nothing here goes through a colour conversion of cv2's: a decoded picture is
compared with the encoder's reference picture bit for bit, and PSNR is taken
in the decoder's output space against the luma the stream's matrix and range
give the source frame (BT.601, studio range: the SPS carries no VUI, which is
what decoders assume then, and what ``utils/hostcolor`` feeds the encoder).
"""

from __future__ import annotations

import bisect
import struct

import cv2
import numpy as np

from benchmark import barcode, stats


def source_luma(rgb: np.ndarray) -> np.ndarray:
    """BT.601 studio-range luma of an RGB frame, as cv2's fixed-point I420
    conversion rounds it (the encoder's host colour path uses the same call)."""
    h = rgb.shape[0]
    return cv2.cvtColor(np.ascontiguousarray(rgb), cv2.COLOR_RGB2YUV_I420)[:h]


def decode_luma(path: str, width: int, height: int):
    """Yield the luma plane of every picture the decoder gives for ``path``
    (an Annex-B or fMP4 file)."""
    cap = cv2.VideoCapture(path)
    cap.set(cv2.CAP_PROP_CONVERT_RGB, 0)
    try:
        while True:
            ok, img = cap.read()
            if not ok:
                return
            plane = np.asarray(img).reshape(-1)[:width * height]
            yield plane.reshape(height, width)
    finally:
        cap.release()


def fragment_is_idr(frag: bytes) -> bool:
    """moof+mdat with one AVCC sample: any NAL of type 5 is an IDR
    (after chip_smoke.py)."""
    moof_len = struct.unpack(">I", frag[:4])[0]
    if frag[4:8] != b"moof" or frag[moof_len + 4:moof_len + 8] != b"mdat":
        raise ValueError("media message is not moof+mdat")
    pos = moof_len + 8
    while pos + 4 <= len(frag):
        n = struct.unpack(">I", frag[pos:pos + 4])[0]
        if frag[pos + 4] & 0x1F == 5:
            return True
        pos += 4 + n
    return False


def longest_p_run(frags) -> int:
    """Longest run of fragments without an IDR (GOP 60 allows 59)."""
    run = longest = 0
    for frag in frags:
        run = 0 if fragment_is_idr(frag) else run + 1
        longest = max(longest, run)
    return longest


def read_stream(path: str, width: int, height: int, render_luma, psnr_every: int,
                in_window):
    """Decode the whole stream once.  Returns ``(ks, psnr)``: the barcode of
    every decoded picture in order (None where it does not read), and
    ``{k: dB}`` for every ``psnr_every``-th picture whose index ``in_window``
    accepts, against ``render_luma(k)``."""
    ks, psnr, n_in = [], {}, 0
    for i, luma in enumerate(decode_luma(path, width, height)):
        k = barcode.read(luma)
        ks.append(k)
        if k is not None and in_window(i):
            if n_in % psnr_every == 0:
                psnr[k] = stats.psnr_db(luma, render_luma(k))
            n_in += 1
    return ks, psnr


def order_faults(ks, stamps, handed) -> list:
    """The pictures whose frame index does not read, does not rise strictly,
    was never handed out by the display, or arrived before it was handed out;
    ``handed`` is the display's ``(k, time)`` of every frame it gave out, in
    order.  One dict a fault, ``len()`` of the list is the number compared:
    ``picture`` (its place in the stream), ``k`` (read from it), ``after``
    (the highest ``k`` read before it), ``handed_k`` (the last ``k`` the
    display had handed out when the picture arrived: a ``k`` above it means
    the picture was AHEAD of the display, so the display's buffer changed
    under the session; one at or under ``after`` is a picture sent again or
    out of order, which is the program's doing), ``stamp`` and ``why``."""
    handed_at = dict(handed)
    times = [t for _, t in handed]
    faults, last = [], -1
    for i, (k, stamp) in enumerate(zip(ks, stamps)):
        if k is None:
            why = "the barcode does not read"
        elif k <= last:
            why = "k does not rise"
        elif k not in handed_at:
            why = "the display never handed this k out"
        elif stamp < handed_at[k]:
            why = "arrived before the display handed it out"
        else:
            why = None
        if why:
            n = bisect.bisect_right(times, stamp)
            faults.append({"picture": i, "k": k, "after": last,
                           "handed_k": handed[n - 1][0] if n else None,
                           "stamp": stamp, "why": why})
        if k is not None:
            last = max(last, k)
    return faults


def closed_loop_maxdiff(encoder, frames, path: str, width: int,
                        height: int) -> int:
    """One IDR and the P frames after it through ``encoder`` (the object the
    window drove, with its compiled programs): the largest difference between
    the decoder's luma and the encoder's own reference picture after each
    frame (``export_state()["ref"]``, the continuity checkpoint's copy: the
    loop-filtered picture the next P frame predicts from).  0 = the two
    pictures are the same, which is what H.264 guarantees."""
    encoder.request_keyframe()
    data, refs = encoder.headers(), []
    for rgb in frames:
        data += encoder.encode(rgb).data
        refs.append(np.array(encoder.export_state()["ref"][0][:height, :width]))
    with open(path, "wb") as f:
        f.write(data)
    worst, n = 0, 0
    for luma, ref in zip(decode_luma(path, width, height), refs):
        worst = max(worst, int(np.abs(luma.astype(np.int16) - ref).max()))
        n += 1
    if n != len(refs):
        return 255          # the decoder gave fewer pictures than were coded
    return worst
