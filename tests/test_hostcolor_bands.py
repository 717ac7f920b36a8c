"""``utils/hostcolor.rgb_to_yuv420_host`` in row bands (ISSUE 41): a picture
cut on even rows and converted band by band in ONE fused native pass
(native/colour.cpp, road ``bands``) gives the bytes of the three cv2 calls
over the whole picture (road ``whole``), which with ``np.pad(mode="edge")``
are the function as it was until PR 41 (kept here as the reference).  The
band count follows the picture and the cores the process may run on; a test
chooses it by patching what the function asks the system
(``os.sched_getaffinity``), never through an option."""

import sys
import threading

import numpy as np
import pytest

from docker_nvidia_glx_desktop_tpu.native import lib as native_lib
from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.utils import hostcolor

cv2 = pytest.importorskip("cv2")

pytestmark = pytest.mark.skipif(
    not native_lib.has_colour(), reason="no C++ toolchain")

FAMILY = "dngd_encoder_colour_total"
GAUGE = "dngd_encoder_colour_bands"

# (w, h, pad_w, pad_h): the cells' geometries, then the awkward ones
GEOMETRIES = {
    "desk1080": (1920, 1080, 1920, 1088),
    "desk1600": (2560, 1600, 2560, 1600),
    "desk2160-mesh4": (3840, 2160, 3840, 2176),
    "desk2160": (3840, 2160, 3840, 2160),
    "width-pads": (1366, 768, 1376, 768),
    "both-pad": (1366, 1082, 1376, 1088),
    "rows-not-a-multiple-of-the-bands": (1920, 1082, 1920, 1088),
    "two-rows": (4096, 2, 4096, 16),
    "small": (128, 96, 128, 96),
}


def cores(monkeypatch, n: int) -> None:
    """The process may run on ``n`` cores, as far as the module can tell."""
    monkeypatch.setattr(hostcolor.os, "sched_getaffinity",
                        lambda pid: set(range(n)))


def road(name: str) -> float:
    return obsm.REGISTRY.get(FAMILY).labels(name).value


def picture(w: int, h: int, seed: int = 0) -> np.ndarray:
    """Noise over a ramp: every 2x2 block and every row differs, so a band
    written to the wrong rows or a mean over the wrong block shows."""
    rng = np.random.default_rng([w, h, seed])
    ramp = (np.arange(h)[:, None, None] + np.arange(w)[None, :, None]) % 97
    return (rng.integers(0, 160, (h, w, 3)) + ramp).astype(np.uint8)


def reference(rgb: np.ndarray, pad_h: int, pad_w: int):
    """The function as it was before the bands: one call a step over the
    whole picture, then ``np.pad``."""
    h, w = rgb.shape[:2]
    y = cv2.cvtColor(rgb, cv2.COLOR_RGB2YUV_I420)[:h]
    half = cv2.resize(rgb, (w // 2, h // 2), interpolation=cv2.INTER_AREA)
    cbcr = cv2.transform(half, hostcolor._CBCR_M)
    pad = ((0, pad_h - h), (0, pad_w - w))
    half_pad = ((0, (pad_h - h) // 2), (0, (pad_w - w) // 2))
    return (np.pad(y, pad, mode="edge"),
            np.pad(cbcr[..., 0], half_pad, mode="edge"),
            np.pad(cbcr[..., 1], half_pad, mode="edge"))


def same(got, want) -> bool:
    return len(got) == len(want) and all(
        a.dtype == b.dtype and np.array_equal(a, b)
        for a, b in zip(got, want))


@pytest.mark.parametrize("n", [1, 2, 4, 8, 64])
@pytest.mark.parametrize("name", list(GEOMETRIES))
def test_bands_give_the_bytes_of_the_whole_picture(name, n, monkeypatch):
    w, h, pad_w, pad_h = GEOMETRIES[name]
    rgb = picture(w, h)
    cores(monkeypatch, n)
    got = hostcolor.rgb_to_yuv420_host(rgb, pad_h, pad_w)
    assert [p.shape for p in got] == [
        (pad_h, pad_w), (pad_h // 2, pad_w // 2), (pad_h // 2, pad_w // 2)]
    assert all(p.flags.c_contiguous for p in got)
    assert same(got, reference(rgb, pad_h, pad_w))
    bands = hostcolor._bands(h, w)
    assert obsm.REGISTRY.get(GAUGE).value == bands
    if n == 1 or name in ("two-rows", "small"):
        assert bands == 1
    elif name in ("desk1080", "desk1600", "desk2160", "desk2160-mesh4"):
        assert bands == min(n, hostcolor._MAX_BANDS, 7 if h == 1080 else 8)


@pytest.mark.parametrize("h,w,n,want", [
    (2, 1 << 20, 8, 1), (6, 1 << 20, 4, 3), (1082, 1920, 4, 4),
    (1080, 1920, 1, 1), (1080, 1920, 13, 7), (96, 128, 8, 1),
    (2160, 3840, 180, 8), (768, 1366, 8, 4), (512, 1022, 8, 1)])
def test_the_band_count_follows_the_picture_and_the_cores(h, w, n, want,
                                                          monkeypatch):
    cores(monkeypatch, n)
    assert hostcolor._bands(h, w) == want


@pytest.mark.parametrize("bands", [1, 2, 3, 5, 8, 64])
@pytest.mark.parametrize("w,h", [(64, 2), (66, 6), (2050, 34), (1366, 1082)])
def test_the_native_pass_cuts_any_picture_on_even_rows(w, h, bands):
    """The C side's own cut (more bands than pairs of rows, rows that do not
    divide, a width over one sweep's 1024 pixels and not a multiple of it),
    into planes larger than the picture."""
    rgb = picture(w, h)
    planes = [np.full(shape, 77, np.uint8) for shape in
              [(h + 16, w + 8), (h // 2 + 8, w // 2 + 4),
               (h // 2 + 8, w // 2 + 4)]]
    native_lib.rgb_to_yuv420_bands(rgb, *planes, hostcolor._CBCR_M32, bands)
    want = reference(rgb, h, w)
    for got, ref, (hh, ww) in zip(planes, want, [(h, w)] + 2 * [
            (h // 2, w // 2)]):
        assert np.array_equal(got[:hh, :ww], ref)
        assert (got[hh:] == 77).all() and (got[:, ww:] == 77).all()


def test_every_rgb_triple_converts_to_cv2s_bytes():
    """All 2**24 triples as the pixels of one picture (every luma input;
    chroma over mixed blocks), then as 2x2 blocks of one colour each (every
    input of the chroma rows' float arithmetic), a slab at a time."""
    g = np.arange(1 << 24, dtype=np.uint32)
    triples = np.stack([g >> 16, (g >> 8) & 255, g & 255],
                       -1).astype(np.uint8).reshape(4096, 4096, 3)
    del g
    planes = [np.empty(s, np.uint8) for s in
              [(4096, 4096), (2048, 2048), (2048, 2048)]]
    native_lib.rgb_to_yuv420_bands(triples, *planes, hostcolor._CBCR_M32, 8)
    assert same(planes, reference(triples, 4096, 4096))
    for top in range(0, 4096, 512):
        blocks = np.repeat(np.repeat(triples[top:top + 512], 2, 0), 2, 1)
        planes = [np.empty(s, np.uint8) for s in
                  [(1024, 8192), (512, 4096), (512, 4096)]]
        native_lib.rgb_to_yuv420_bands(blocks, *planes,
                                       hostcolor._CBCR_M32, 8)
        assert same(planes, reference(blocks, 1024, 8192))


@pytest.mark.parametrize("fault", [
    "odd rows", "odd width", "float picture", "two channels",
    "short luma", "narrow chroma", "strided luma", "float64 matrix",
    "no band"])
def test_the_binding_refuses_what_the_c_side_would_misread(fault):
    rgb = picture(64, 32)
    y, u, v = (np.empty(s, np.uint8) for s in [(32, 64), (16, 32), (16, 32)])
    m, bands = hostcolor._CBCR_M32, 2
    if fault == "odd rows":
        rgb = rgb[:31]
    elif fault == "odd width":
        rgb = np.ascontiguousarray(rgb[:, :63])
    elif fault == "float picture":
        rgb = rgb.astype(np.float32)
    elif fault == "two channels":
        rgb = np.ascontiguousarray(rgb[..., :2])
    elif fault == "short luma":
        y = y[:30]
    elif fault == "narrow chroma":
        u = np.empty((16, 30), np.uint8)
    elif fault == "strided luma":
        y = np.empty((32, 128), np.uint8)[:, ::2]
    elif fault == "float64 matrix":
        m = hostcolor._CBCR_M
    elif fault == "no band":
        bands = 0
    with pytest.raises(ValueError):
        native_lib.rgb_to_yuv420_bands(rgb, y, u, v, m, bands)


def spy(monkeypatch):
    """The band counts the native pass was asked for."""
    asked = []
    real = native_lib.rgb_to_yuv420_bands

    def seen(rgb, y, u, v, m, bands):
        asked.append(bands)
        return real(rgb, y, u, v, m, bands)

    monkeypatch.setattr(native_lib, "rgb_to_yuv420_bands", seen)
    return asked


def test_one_core_is_the_cv2_calls_on_the_calling_thread(monkeypatch):
    """The single-core capture host: no native pass, no other thread."""
    cores(monkeypatch, 1)
    asked = spy(monkeypatch)
    whole, banded = road("whole"), road("bands")
    rgb = picture(1920, 1080)
    got = hostcolor.rgb_to_yuv420_host(rgb, 1088, 1920)
    assert same(got, reference(rgb, 1088, 1920))
    assert asked == []
    assert (road("whole"), road("bands")) == (whole + 1, banded)
    assert obsm.REGISTRY.get(GAUGE).value == 1


def test_cores_to_spare_is_one_native_call(monkeypatch):
    cores(monkeypatch, 4)
    asked = spy(monkeypatch)
    monkeypatch.setattr(hostcolor, "_convert_whole", lambda *a: pytest.fail(
        "a banded conversion went through cv2"))
    whole, banded = road("whole"), road("bands")
    rgb = picture(1920, 1080)
    got = hostcolor.rgb_to_yuv420_host(rgb, 1088, 1920)
    assert same(got, reference(rgb, 1088, 1920))
    assert asked == [4]
    assert (road("whole"), road("bands")) == (whole, banded + 1)
    assert obsm.REGISTRY.get(GAUGE).value == 4


def test_without_the_native_library_every_picture_is_whole(monkeypatch):
    """No C++ toolchain on the host: the parent's calls, the same bytes."""
    cores(monkeypatch, 8)
    monkeypatch.setattr(native_lib, "has_colour", lambda: False)
    asked = spy(monkeypatch)
    whole, banded = road("whole"), road("bands")
    rgb = picture(2560, 1600)
    got = hostcolor.rgb_to_yuv420_host(rgb, 1600, 2560)
    assert same(got, reference(rgb, 1600, 2560))
    assert asked == []
    assert (road("whole"), road("bands")) == (whole + 1, banded)


def test_both_roads_stand_in_metrics():
    text = obsm.REGISTRY.render()
    assert f"# TYPE {FAMILY} counter" in text
    assert f"# TYPE {GAUGE} gauge" in text
    for name in ("bands", "whole"):
        assert f'\n{FAMILY}{{road="{name}"}} ' in text


def test_consecutive_calls_return_planes_that_share_no_memory(monkeypatch):
    """The damage chain keeps a frame's luma, and the device copy of a
    frame's planes may still be reading them when the next converts."""
    cores(monkeypatch, 4)
    rgb = picture(1920, 1080)
    calls = [hostcolor.rgb_to_yuv420_host(rgb, 1088, 1920)
             for _ in range(3)]
    planes = [p for call in calls for p in call]
    for i, a in enumerate(planes):
        assert not np.shares_memory(a, rgb)
        for b in planes[i + 1:]:
            assert not np.shares_memory(a, b)
    kept = [p.copy() for p in calls[0]]
    hostcolor.rgb_to_yuv420_host(picture(1920, 1080, seed=1), 1088, 1920)
    assert same(calls[0], kept)


def test_threads_converting_at_once_each_get_their_own_frame(monkeypatch):
    """Two sessions of one process (a BatchStreamManager's) share the
    native pool, which takes one picture at a time: more callers than
    cores, each with a frame and a geometry of its own, a short switch
    interval; no plane holds another's rows and nothing deadlocks."""
    cores(monkeypatch, 4)
    jobs = []
    for i, name in enumerate(["desk1080", "both-pad", "desk1600",
                              "desk1080", "width-pads", "desk1080"]):
        w, h, pad_w, pad_h = GEOMETRIES[name]
        rgb = picture(w, h, seed=i)
        jobs.append((rgb, pad_h, pad_w, reference(rgb, pad_h, pad_w)))
    wrong, errors = [], []
    start = threading.Barrier(len(jobs))

    def session(i):
        rgb, pad_h, pad_w, want = jobs[i]
        try:
            start.wait(timeout=30)
            for _ in range(12):
                if not same(hostcolor.rgb_to_yuv420_host(rgb, pad_h, pad_w),
                            want):
                    wrong.append(i)
        except BaseException as e:      # a thread's error is the test's
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=session, args=(i,), daemon=True)
                   for i in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong


@pytest.mark.parametrize("name", ["desk1080", "both-pad", "small"])
def test_without_cv2_the_float_path_is_what_it_was(name, monkeypatch):
    w, h, pad_w, pad_h = GEOMETRIES[name]
    rgb = picture(w, h)
    cores(monkeypatch, 8)
    monkeypatch.setitem(sys.modules, "cv2", None)   # ``import cv2`` raises
    whole, banded = road("whole"), road("bands")
    assert hostcolor.rgb_to_yuv420_host(rgb, pad_h, pad_w,
                                        float_fallback=False) is None
    assert (road("whole"), road("bands")) == (whole, banded)
    got = hostcolor.rgb_to_yuv420_host(rgb, pad_h, pad_w)
    assert (road("whole"), road("bands")) == (whole + 1, banded)
    f = rgb.astype(np.float64)
    y = np.clip(np.round(f @ hostcolor._Y_M + 16.0), 0, 255).astype(np.uint8)
    hf = f.reshape(h // 2, 2, w // 2, 2, 3).mean(axis=(1, 3))
    cbcr = hf @ hostcolor._CBCR_M[:, :3].T + hostcolor._CBCR_M[:, 3]
    cbcr = np.clip(np.round(cbcr), 0, 255).astype(np.uint8)
    pad = ((0, pad_h - h), (0, pad_w - w))
    half_pad = ((0, (pad_h - h) // 2), (0, (pad_w - w) // 2))
    assert same(got, (np.pad(y, pad, mode="edge"),
                      np.pad(cbcr[..., 0], half_pad, mode="edge"),
                      np.pad(cbcr[..., 1], half_pad, mode="edge")))


SERVED_W, SERVED_H = 1024, 1080     # 1080 rows: the pad to 1088 is live


def served_frame(c: int) -> np.ndarray:
    """A texture panned by (c, 2c) whose contrast grows with ``c``
    (tests/test_turn_order.py's): the rate controller walks."""
    yy, xx = np.mgrid[c:c + SERVED_H, 2 * c:2 * c + SERVED_W]
    v = 128 + (40 + 6 * c) * np.sin(xx / 5.0) * np.cos(yy / 4.0) \
        + 30 * np.sin((xx + yy) / 3.0)
    return np.stack([v, v * 0.8 + 20, 255 - v],
                    axis=-1).clip(0, 255).astype(np.uint8)


@pytest.mark.parametrize("entropy", ["device", "cabac"])
def test_the_served_stream_is_the_same_bytes_at_one_band_and_at_four(
        entropy, monkeypatch):
    """An IDR and three P frames through the served encoder (CAVLC as
    ``desk1080`` runs it, CABAC as ``desk1080-cabac`` does), GOP 60, rate
    control on: the access units do not depend on the band count."""
    from docker_nvidia_glx_desktop_tpu.models import make_encoder
    from docker_nvidia_glx_desktop_tpu.utils.config import from_env

    cfg = from_env({"PASSWD": "pw", "SIZEW": str(SERVED_W),
                    "SIZEH": str(SERVED_H), "REFRESH": "60",
                    "ENCODER_ENTROPY": entropy,
                    "ENCODER_BITRATE_KBPS": "2000", "ENCODER_GOP": "60",
                    "ENCODER_PREWARM": "false"})
    frames = [served_frame(k) for k in range(4)]
    streams = {}
    for n in (1, 4):
        cores(monkeypatch, n)
        enc, _ = make_encoder(cfg, SERVED_W, SERVED_H)
        assert (enc.pad_h, enc.host_color, enc.gop) == (1088, True, 60)
        whole, banded = road("whole"), road("bands")
        streams[n] = [enc.encode_collect(enc.encode_submit(f)).data
                      for f in frames]
        moved = (road("whole") - whole, road("bands") - banded)
        assert moved == ((4, 0) if n == 1 else (0, 4))
        assert obsm.REGISTRY.get(GAUGE).value == n
    assert streams[1] == streams[4]
    assert len(set(streams[1])) == 4
