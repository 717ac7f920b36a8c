"""fMP4 muxer tests: box structure sanity, Annex-B conversion, and the
golden decode — cv2/FFmpeg plays a muxed TPU H.264 stream back and the
frames match (SURVEY.md §4 golden-decoder strategy)."""

import struct

import numpy as np
import pytest

from docker_nvidia_glx_desktop_tpu.web.mp4 import (
    Mp4Muxer, annexb_to_avcc, split_annexb)

from conftest import make_test_frame


def parse_boxes(data: bytes):
    """Top-level MP4 box walk -> [(type, payload), ...]."""
    out = []
    i = 0
    while i + 8 <= len(data):
        size, typ = struct.unpack(">I4s", data[i:i + 8])
        assert size >= 8
        out.append((typ.decode(), data[i + 8:i + size]))
        i += size
    assert i == len(data), "trailing garbage after last box"
    return out


class TestAnnexB:
    def test_split_three_and_four_byte_codes(self):
        au = (b"\x00\x00\x00\x01" + b"\x67\x42\x00\x1e"
              + b"\x00\x00\x01" + b"\x68\xce\x38\x80"
              + b"\x00\x00\x00\x01" + b"\x65\x88\x80\x10")
        nals = split_annexb(au)
        assert [n[0] & 0x1F for n in nals] == [7, 8, 5]
        assert nals[0] == b"\x67\x42\x00\x1e"
        assert nals[2] == b"\x65\x88\x80\x10"

    def test_avcc_drops_parameter_sets(self):
        au = (b"\x00\x00\x00\x01" + b"\x67\x42"
              + b"\x00\x00\x00\x01" + b"\x68\xce"
              + b"\x00\x00\x00\x01" + b"\x65\xab\xcd")
        avcc = annexb_to_avcc(au)
        ln, = struct.unpack(">I", avcc[:4])
        assert ln == 3
        assert avcc[4:] == b"\x65\xab\xcd"


class TestMuxStructure:
    def _muxer(self):
        sps = bytes.fromhex("6742c01e d9008066 e0880000 03000800".replace(" ", ""))
        pps = bytes.fromhex("68ce3880")
        return Mp4Muxer(128, 96, sps, pps, fps=30)

    def test_init_segment_boxes(self):
        boxes = parse_boxes(self._muxer().init_segment())
        assert [t for t, _ in boxes] == ["ftyp", "moov"]
        inner = parse_boxes(boxes[1][1])
        names = [t for t, _ in inner]
        assert names == ["mvhd", "trak", "mvex"]

    def test_fragment_boxes_and_offset(self):
        m = self._muxer()
        au = b"\x00\x00\x00\x01" + b"\x65" + b"\xee" * 40
        frag = m.fragment(au, keyframe=True)
        boxes = parse_boxes(frag)
        assert [t for t, _ in boxes] == ["moof", "mdat"]
        moof_payload = boxes[0][1]
        moof_len = 8 + len(moof_payload)
        # trun data_offset must point at the mdat payload
        traf = dict(parse_boxes(moof_payload))["traf"]
        trun = dict(parse_boxes(traf))["trun"]
        _, _, data_offset = struct.unpack(">I I i", trun[:12])
        assert data_offset == moof_len + 8
        # mdat payload = AVCC of the AU
        ln, = struct.unpack(">I", boxes[1][1][:4])
        assert ln == 41

    def test_decode_time_advances(self):
        m = self._muxer()
        au = b"\x00\x00\x00\x01" + b"\x65\x00"
        m.fragment(au)
        m.fragment(au)
        assert m.decode_time == 2 * m.sample_duration
        assert m.seq == 2


class TestGoldenDecode:
    @pytest.mark.slow
    def test_cv2_plays_muxed_tpu_h264(self, tmp_path):
        """Mux real TPU-encoder output; cv2's FFmpeg must decode every frame
        with high PSNR — proving init segment + fragments are valid fMP4."""
        cv2 = pytest.importorskip("cv2")
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        from docker_nvidia_glx_desktop_tpu.web.mp4 import split_annexb

        w, h = 128, 96
        enc = H264Encoder(w, h, entropy="python")
        nals = split_annexb(enc.headers())
        sps = next(n for n in nals if (n[0] & 0x1F) == 7)
        pps = next(n for n in nals if (n[0] & 0x1F) == 8)
        mux = Mp4Muxer(w, h, sps, pps, fps=30)

        frames = [make_test_frame(h, w, seed=s) for s in range(3)]
        blob = mux.init_segment()
        for f in frames:
            blob += mux.fragment(enc.encode(f).data, keyframe=True)
        path = tmp_path / "stream.mp4"
        path.write_bytes(blob)

        cap = cv2.VideoCapture(str(path))
        decoded = []
        while True:
            ok, bgr = cap.read()
            if not ok:
                break
            decoded.append(bgr[:, :, ::-1])
        cap.release()
        assert len(decoded) == len(frames)

        def psnr(a, b):
            mse = np.mean((a.astype(np.float64) - b.astype(np.float64)) ** 2)
            return 10 * np.log10(255.0 ** 2 / max(mse, 1e-9))

        # The tiny test frame is 1/8 random noise, so absolute PSNR at qp 26
        # is modest; what proves the mux is that every decoded frame matches
        # ITS OWN source far better than any other (distinct seeds).
        for i, dec in enumerate(decoded):
            scores = [psnr(f, dec) for f in frames]
            assert max(range(len(frames)), key=scores.__getitem__) == i
            assert scores[i] > 18.0, f"PSNR {scores[i]:.1f} too low"
