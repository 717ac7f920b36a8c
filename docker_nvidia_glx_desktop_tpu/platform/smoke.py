"""In-image runtime smoke test — the product-artifact gate.

The round-4 ship-stopper: the normative tables are recovered at runtime
from system codec libraries (bitstream/cabac_tables, ops/h264_deblock,
bitstream/vp8_tables), and the shipped container did not install them —
the default GOP+deblock path crashed at boot while CI only *built* the
image (the reference's own quality bar, reference
container-publish.yml:44-55).  This module is run BY CI INSIDE the built
image (``python3 -m docker_nvidia_glx_desktop_tpu.platform.smoke``) and
exercises every runtime-recovery path plus one encode per codec family:

1. table recovery: CABAC engine + context-init, deblock alpha/beta/tc0,
   VP8 probabilities/quant lookups;
2. one H.264 GOP (IDR + P) with in-loop deblocking, device entropy —
   the stock-env default path — decoded by the system FFmpeg (cv2);
3. one H.264 CABAC slice (Main profile), decoded;
4. one VP8 keyframe, decoded by the system libvpx;
5. native C/C++ shims compile in-image (entropy coder, CABAC).

Exit status 0 = the artifact can serve with stock env.  Keep geometry
small: CI runs this on CPU jax (JAX_PLATFORMS=cpu) where XLA compile
time scales with the macroblock grid.
"""

from __future__ import annotations

import sys
import tempfile

import numpy as np

W, H = 320, 240


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _test_frame(seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 255, (H // 8, W // 8, 3), np.uint8)
    frame = np.kron(base, np.ones((8, 8, 1), np.uint8)).astype(np.uint8)
    return np.ascontiguousarray(frame[:H, :W])


def _decode_h264(data: bytes, n: int):
    import cv2

    with tempfile.NamedTemporaryFile(suffix=".h264") as f:
        f.write(data)
        f.flush()
        cap = cv2.VideoCapture(f.name)
        out = []
        for _ in range(n):
            ok, img = cap.read()
            if not ok:
                raise RuntimeError("system decoder rejected the stream")
            out.append(cv2.cvtColor(img, cv2.COLOR_BGR2RGB))
        cap.release()
    return out


def _psnr(a: np.ndarray, b: np.ndarray) -> float:
    d = a.astype(np.float64) - b.astype(np.float64)
    mse = float((d * d).mean())
    return 99.0 if mse == 0 else 10 * np.log10(255.0 * 255.0 / mse)


def check_tables() -> None:
    from ..bitstream import cabac_tables, vp8_tables
    from ..ops import h264_deblock

    rng, tm, tl = cabac_tables.engine_tables()
    assert rng.shape == (64, 4) and tm.shape == (64,) and tl.shape == (64,)
    ctx = cabac_tables.context_init_tables()
    assert ctx.shape == (4, 1024, 2)
    _log("CABAC engine + context-init tables recovered")

    alpha, beta, tc0 = h264_deblock.load_tables()
    assert alpha.shape == (52,) and beta.shape == (52,) and tc0.shape == (52, 3)
    _log("deblock alpha/beta/tc0 tables recovered")

    vp8_tables.load_tables()
    _log("VP8 probability/quant tables recovered")


def check_native() -> None:
    from ..native import lib

    assert lib.available(), "native entropy library failed to build"
    assert lib.has_cabac(), "native CABAC entry points missing"
    _log("native entropy/CABAC shims built and loaded")


def check_h264_gop_deblock() -> None:
    from ..models.h264 import H264Encoder

    enc = H264Encoder(W, H, qp=28, entropy="device",
                      gop=2, deblock=True)
    f0, f1 = _test_frame(0), _test_frame(1)
    data = enc.headers() + enc.encode(f0).data + enc.encode(f1).data
    dec = _decode_h264(data, 2)
    p0, p1 = _psnr(dec[0], f0), _psnr(dec[1], f1)
    assert p0 > 28 and p1 > 28, f"GOP decode quality too low: {p0:.1f}/{p1:.1f}"
    _log(f"H.264 IDR+P with in-loop deblock decoded (PSNR {p0:.1f}/{p1:.1f} dB)")


def check_h264_cabac() -> None:
    from ..models.h264 import H264Encoder

    enc = H264Encoder(W, H, qp=28, entropy="cabac")
    f0 = _test_frame(2)
    data = enc.headers() + enc.encode(f0).data
    dec = _decode_h264(data, 1)
    p = _psnr(dec[0], f0)
    assert p > 28, f"CABAC decode quality too low: {p:.1f}"
    _log(f"H.264 CABAC (Main profile) slice decoded (PSNR {p:.1f} dB)")


def check_vp8() -> None:
    from ..models.vp8 import Vp8Encoder
    from ..native import vpx

    enc = Vp8Encoder(W, H, q_index=24, gop=10)
    f0 = _test_frame(3)
    f1 = np.ascontiguousarray(np.roll(f0, 4, axis=1))
    k = enc.encode(f0)
    p = enc.encode(f1)
    assert k.keyframe and not p.keyframe
    if vpx.available():
        dec = vpx.Vp8Decoder()
        dec.decode(k.data)
        dy, du, dv = dec.decode(p.data)
        assert np.array_equal(dy, enc._ref[0][:H, :W])
        dec.close()
        _log("VP8 keyframe + interframe decoded by system libvpx "
             "(recon byte-exact)")
    else:
        raise RuntimeError("libvpx unavailable for VP8 decode validation")


def main() -> int:
    steps = [("tables", check_tables), ("native", check_native),
             ("h264-gop-deblock", check_h264_gop_deblock),
             ("h264-cabac", check_h264_cabac), ("vp8", check_vp8)]
    failed = []
    for name, fn in steps:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report all failures at once
            failed.append((name, e))
            _log(f"FAIL {name}: {e!r}")
    if failed:
        _log(f"{len(failed)}/{len(steps)} steps failed")
        return 1
    _log("all steps passed — artifact serves with stock env")
    return 0


if __name__ == "__main__":
    sys.exit(main())
