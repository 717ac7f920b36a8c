"""The DENSE CABAC stream, held to the bytes of the tree before the damage
mask learnt that stream (ISSUE 43).

The masked CABAC path is new functions BESIDE the dense ones
(``_submit_cabac_p`` / ``_collect_cabac_p``, ``cabac_binarize.binarize_p``,
``h264_cabac.encode_p_from_binstream``, ``native/cabac.cpp``'s engine,
``PrefixPull``): the three one-chip CABAC cells of the benchmark run the dense
ones, and PR 42, which brought the mask to this stream first, was refused for
one picture out of order in one of THEIR traced runs.  So the bytes those
functions send are pinned here: ``DIGESTS`` was made by ``stream_digests()``
on the tree of commit 09a53ae (PR 41), with that tree's package on the path,
and any later edit that changes what the dense path sends fails on the CPU.
The native engine's existing entry is pinned as text: an entry for a new need
is added beside it, never by widening it.
"""

import hashlib
import pathlib
import re

import numpy as np
import pytest

W, H, FRAMES = 128, 96, 24
ROOT = pathlib.Path(__file__).resolve().parents[1]

# sha256 of each access unit, first 16 hex digits; frame 12 is an IDR (GOP 12)
_PARENT = """
    3882047a30436696 2571c76ddbb67912 37f0257b77fdc8ba 2955a179990d40e1
    1a834e44c94eb17b 7c2ebedd79cd803a ca3bd8632fc0460b 5ef68f2da639ae58
    e5343edcd759dc2d 4a41fa679b9f1742 a263d8904604be86 d59b15f9e50687af
    47ca3dc9c13f370c a8e7d1db0fd3d396 f139e3e6c2f76bf4 04e97100122f116b
    559c058eb8feba3e 2f8938575b64ce07 0c8d071c9feae4bd bc20165a3daa0d13
    3a19347fab4df284 7d8743e9221c254d 12dfc68931355d38 caf25aee3cfe28cd
""".split()
DIGESTS = {"device": _PARENT, "host": _PARENT}    # the same stream, as ever
ENGINE_ENTRY_SHA256 = (
    "a3d3c8275126c39f77e2d4a81c34002fdac333369127892a7071ae42d3226b44")


def picture(c: int) -> np.ndarray:
    """Desktop-like content: a gradient desk, a text-like window in which a
    glyph appears every other frame, a small texture that pans (from frame 8
    on), and a pointer that moves every frame."""
    yy, xx = np.mgrid[0:H, 0:W]
    out = np.stack([40 + xx * 60 // W, 70 + yy * 70 // H,
                    120 + (xx + yy) * 60 // (W + H)], axis=-1).astype(np.uint8)
    r = np.random.default_rng(43)
    text = r.integers(0, 2, (40, 72), np.uint8) * 200 + 30
    out[16:56, 8:80] = text[:, :, None]
    for g in range(c // 2 + 1):
        out[20 + 8 * (g % 4):26 + 8 * (g % 4), 12 + 6 * g:16 + 6 * g] = 250
    if c >= 8:
        tex = (128 + 90 * np.sin((xx + 3 * c) / 3.0)
               * np.cos((yy + c) / 2.0)).astype(np.uint8)
        out[60:92, 84:124] = tex[60:92, 84:124, None]
    px, py = (5 * c) % (W - 6), (3 * c) % (H - 8)
    out[py:py + 8, px:px + 6] = 255
    return out


def stream_digests(binarize: str) -> list:
    """FRAMES frames through the served dense CABAC path (mask off, tune
    off, loop filter on, CBR so that qp walks, GOP 12 so that an IDR lies
    inside), pipelined two deep as the session does."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    enc = H264Encoder(W, H, mode="cavlc", entropy="cabac", host_color=True,
                      gop=12, deblock=True, bitrate_kbps=120, fps=60,
                      damage_mask=False)
    enc._cabac_dev_bin = binarize == "device"
    out, tokens = [], []
    for c in range(FRAMES):
        tokens.append(enc.encode_submit(picture(c)))
        if len(tokens) == 2:
            out.append(enc.encode_collect(tokens.pop(0)))
    out.append(enc.encode_collect(tokens.pop(0)))
    assert [f.keyframe for f in out] == [c % 12 == 0 for c in range(FRAMES)]
    assert enc._rate._step_idx != enc._rate.STEPS.index(0)   # qp walked
    return [hashlib.sha256(f.data).hexdigest()[:16] for f in out]


def engine_entry_text() -> str:
    """``h264_cabac_engine_rows`` of native/cabac.cpp, from its return type
    to the brace that closes it."""
    src = (ROOT / "docker_nvidia_glx_desktop_tpu" / "native"
           / "cabac.cpp").read_text()
    m = re.search(r"^[^\n]*\bh264_cabac_engine_rows\s*\(", src, re.M)
    assert m, "the engine's entry is gone"
    depth, i = 0, src.index("{", m.end())
    for j in range(i, len(src)):
        depth += {"{": 1, "}": -1}.get(src[j], 0)
        if depth == 0:
            return src[m.start():j + 1]
    raise AssertionError("the engine's entry does not close")


@pytest.mark.parametrize("binarize", ["device", "host"])
def test_the_dense_cabac_stream_is_the_parents_bytes(binarize):
    got = stream_digests(binarize)
    assert len(DIGESTS[binarize]) == FRAMES
    assert got == DIGESTS[binarize], [
        c for c, (a, b) in enumerate(zip(got, DIGESTS[binarize])) if a != b]


def test_the_pictures_make_the_stream_worth_pinning():
    """Every frame differs from the one before, and the two placements of
    the binarizer send the same stream (so one pin would do, were it not
    that each runs its own functions)."""
    assert all((picture(c) != picture(c + 1)).any() for c in range(FRAMES - 1))
    assert DIGESTS["device"] == DIGESTS["host"]
    assert len(set(DIGESTS["device"])) == FRAMES


def test_the_native_engines_entry_keeps_its_signature_and_body():
    text = engine_entry_text()
    assert "row_bits" in text and "int64_t cap" in text
    assert hashlib.sha256(text.encode()).hexdigest() == ENGINE_ENTRY_SHA256


if __name__ == "__main__":
    # PYTHONPATH=<a tree> python3 tests/test_cabac_dense_pinned.py
    import json
    print(json.dumps({"device": stream_digests("device"),
                      "host": stream_digests("host"),
                      "engine": hashlib.sha256(
                          engine_entry_text().encode()).hexdigest()}))
