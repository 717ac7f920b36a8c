"""``dngd_encoder_assemble_total{road=...}``: which road framed a frame's row
slices as Annex-B NAL units (bitstream/h264.py ``annexb_rows``): ``native``,
one call into native/entropy.cpp, or ``python``, the escape loop a byte where
the library was not built.  On the two served encoders at 128x96 (device CAVLC
as ``desk1080`` runs it, CABAC as ``desk1080-cabac`` does), a frame at a time."""

import json
import pathlib

import numpy as np
import pytest

from docker_nvidia_glx_desktop_tpu.native import lib as native_lib
from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.utils.config import from_env

ROOT = pathlib.Path(__file__).resolve().parents[1]
W, H = 128, 96
FAMILY = "dngd_encoder_assemble_total"
ROADS = {"cavlc": {},
         "cabac": json.loads((ROOT / "benchmark" / "configs" /
                              "desk1080-cabac.json").read_text())["env"]}

pytestmark = pytest.mark.skipif(
    not native_lib.available(), reason="no C++ toolchain")


def road(name: str) -> float:
    return obsm.REGISTRY.get(FAMILY).labels(name).value


def frame(c: int) -> np.ndarray:
    """A soft texture panned by (c, 2c), as tests/test_stage_spans.py's."""
    yy, xx = np.mgrid[c:c + H, 2 * c:2 * c + W]
    v = 128 + 60 * np.sin(xx / 9.0) * np.cos(yy / 7.0) + 30 * np.sin(
        (xx + yy) / 3.0)
    return np.stack([v, v * 0.8 + 20, 255 - v], axis=-1).astype(np.uint8)


def test_both_roads_stand_in_metrics_before_the_first_frame():
    """A scrape of a session that has not framed a frame shows both
    series (at 0 in a new process; this one may have served frames)."""
    from docker_nvidia_glx_desktop_tpu.web import session  # noqa: F401
    text = obsm.REGISTRY.render()
    assert f"# TYPE {FAMILY} counter" in text
    for name in ("native", "python"):
        assert f'\n{FAMILY}{{road="{name}"}} ' in text


@pytest.fixture(scope="module", params=list(ROADS))
def encoder(request):
    """A served encoder of either entropy coder, one IDR behind it."""
    from docker_nvidia_glx_desktop_tpu.models import make_encoder

    cfg = from_env(dict(ROADS[request.param], PASSWD="pw", SIZEW=str(W),
                        SIZEH=str(H), REFRESH="30",
                        ENCODER_PREWARM="false"))
    enc, name = make_encoder(cfg, W, H)
    assert name == "h264_" + request.param
    enc.encode_collect(enc.encode_submit(frame(0)))
    return enc


@pytest.mark.parametrize("library", [True, False])
def test_a_frame_is_one_count_on_the_road_it_took(encoder, library,
                                                  monkeypatch):
    """An IDR and two P frames: one increment a frame, on ``native``
    with the library and on ``python`` with it patched away; the other
    series stands."""
    if not library:
        monkeypatch.setattr(native_lib, "available", lambda: False)
    moved, still = ("native", "python") if library else ("python", "native")
    encoder.request_keyframe()
    keys = []
    for c in range(3):
        before = road(moved), road(still)
        ef = encoder.encode_collect(encoder.encode_submit(frame(4 + c)))
        assert (road(moved), road(still)) == (before[0] + 1, before[1])
        assert ef.data.startswith(b"\0\0\0\1") and len(ef.data) > 64
        keys.append(ef.keyframe)
    assert keys == [True, False, False]
