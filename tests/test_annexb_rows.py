"""A frame's row slices as Annex-B NAL units in ONE native call
(native/entropy.cpp ``h264_annexb_rows`` behind bitstream/h264.py
``annexb_rows``) against the road it replaced and must equal byte for byte:
``nal_unit`` a row, its Python ``emulation_prevention`` loop a byte, a
``BitWriter`` + ``slice_header`` + ``pad_to_byte(1)`` a CABAC row.  No device
program runs here: the rows are made on the host."""

import itertools

import numpy as np
import pytest

from docker_nvidia_glx_desktop_tpu.bitstream import h264 as syn
from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac
from docker_nvidia_glx_desktop_tpu.bitstream.bitwriter import BitWriter
from docker_nvidia_glx_desktop_tpu.native import lib as native_lib
from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize, cavlc_device

pytestmark = pytest.mark.skipif(
    not native_lib.available(), reason="no C++ toolchain")


def python_road(rows, nal_type, ref_idc, headers=None, prefix=b""):
    """The parent's loop: every row alone through the Python escape."""
    out = bytearray(prefix)
    for r, row in enumerate(rows):
        rbsp = (headers[r] if headers else b"") + bytes(row)
        out += (syn.START_CODE + bytes([(ref_idc << 5) | nal_type])
                + syn.emulation_prevention(rbsp))
    return bytes(out)


def laid_out(rows, gap=3):
    """Rows in one buffer with slack between them, as both roads hand
    them over: (src, row_off, row_len)."""
    src, off = bytearray(b"\xaa" * gap), []
    for row in rows:
        off.append(len(src))
        src += bytes(row) + b"\x55" * gap
    return (np.frombuffer(bytes(src), np.uint8), np.array(off, np.int64),
            np.array([len(r) for r in rows], np.int64))


def slice_header_bytes(first_mb, **hdr):
    bw = BitWriter()
    syn.slice_header(bw, first_mb=first_mb, **hdr)
    bw.pad_to_byte(1)
    return bw.getvalue()


def without_library(monkeypatch):
    monkeypatch.setattr(native_lib, "available", lambda: False)


# rows and bytes a frame of the four geometries' full-damage cells
CELLS = {"desk1080": (68, 46_000), "desk1600": (100, 106_000),
         "desk2160-cabac": (135, 191_000),
         "desk2160-cabac-mesh4": (136, 250_000)}


@pytest.mark.parametrize("cell", CELLS)
def test_random_rows_at_a_cells_size(cell):
    """Entropy-coded bytes are near random: few escapes.  Zeros are sown
    so that every row meets some."""
    nrows, total = CELLS[cell]
    rng = np.random.default_rng(nrows)
    rows = []
    for n in rng.integers(total // nrows // 2, 3 * total // nrows // 2, nrows):
        row = rng.integers(0, 256, n, dtype=np.uint8)
        row[rng.random(n) < 0.02] = 0
        for at in rng.integers(0, n - 4, 6):
            row[at:at + 3] = (0, 0, rng.integers(0, 5))
        rows.append(row)
    src, off, lens = laid_out(rows)
    for nal_type, ref_idc, prefix in ((syn.NAL_SLICE, 2, b""),
                                      (syn.NAL_IDR, 3, b"\0\0\0\1\x67sps")):
        got = syn.annexb_rows(src, off, lens, nal_type, ref_idc,
                              prefix=prefix)
        assert got == python_road(rows, nal_type, ref_idc, prefix=prefix)


EDGE_ROWS = {
    "all_zeros": [bytes(n) for n in (1, 2, 3, 4, 5, 6, 7, 100)],
    "zero_runs_before_0_to_4": [
        bytes([9] * a + [0] * z + [last, 7][:tail])
        for a, z, last, tail in itertools.product(
            (0, 1), (1, 2, 3, 4, 5), (0, 1, 2, 3, 4), (1, 2))],
    "ends_in_zeros": [b"\x80\0", b"\x80\0\0", b"\x80\0\0\0", b"\0\0\3",
                      b"\0\0\3\0\0", b"\1\0\0"],
    "one_byte": [bytes([b]) for b in (0, 1, 2, 3, 4, 0xFF)],
    "empty_rows": [b"", b"\0\0\1", b"", b"", b"\xff"],
    "only_empty": [b"", b""],
    "no_rows": [],
}


@pytest.mark.parametrize("case", EDGE_ROWS)
def test_edge_rows(case):
    """Nothing is added behind a row that ends in zeros (``nal_unit``
    adds nothing), and the escape state starts anew at every NAL."""
    rows = EDGE_ROWS[case]
    src, off, lens = laid_out(rows, gap=0)      # neighbours' zeros touch
    got = syn.annexb_rows(src, off, lens, syn.NAL_SLICE, 2)
    assert got == python_road(rows, syn.NAL_SLICE, 2)


@pytest.mark.parametrize("tail_nbits", [7, 15, 23, 24, 31, 33, 47, 64])
@pytest.mark.parametrize("first", [0, 1, 2, 3, 4])
def test_zeros_straddle_the_header_and_the_payload(tail_nbits, first):
    """The escape runs on from the slice header's bytes into the row's:
    a header that ends in zero bytes (first_mb 0 is the bit 1, then an
    all-zero tail; no real header does) before a payload that starts
    with 00, 01, 02, 03 or 04."""
    rows = [bytes([first, 0, 0, first]), bytes([0, first])]
    src, off, lens = laid_out(rows)
    nbits = 1 + tail_nbits
    pad = -nbits % 8
    header = (((1 << tail_nbits) << pad) | ((1 << pad) - 1)).to_bytes(
        (nbits + pad) // 8, "big")
    need = python_road(rows, syn.NAL_SLICE, 2, headers=[header] * 2)
    got = native_lib.annexb_rows(src, off, lens, (2 << 5) | syn.NAL_SLICE,
                                 len(need), hdr_tail_nbits=tail_nbits)
    assert got == need


KINDS = {"idr_pic_id_0": dict(slice_type=7, idr=True, idr_pic_id=0),
         "idr_pic_id_1": dict(slice_type=7, idr=True, idr_pic_id=1),
         "p_init_idc_0": dict(slice_type=5, idr=False, cabac_init_idc=0),
         "p_init_idc_1": dict(slice_type=5, idr=False, cabac_init_idc=1),
         "p_init_idc_2": dict(slice_type=5, idr=False, cabac_init_idc=2)}


@pytest.mark.parametrize("kind", KINDS)
def test_cabac_slice_headers_of_every_served_frame(kind, monkeypatch):
    """Every (frame_num, qp_delta on the rate ladder base-6..base+18,
    loop filter on or off) x every row of a 4K picture (136 rows of 240;
    120 and 160 macroblocks a row at two steps): the routine's header
    bytes against ``slice_header`` + ``pad_to_byte(1)``, on rows with no
    payload, so that the NAL is the header alone."""
    heads = [dict(KINDS[kind], cabac=True, frame_num=fn, qp_delta=dq,
                  deblocking_idc=idc)
             for fn in range(16) for dq in range(-6, 19) for idc in (0, 1)]
    none = (np.zeros(1, np.uint8), np.zeros(136, np.int64),
            np.zeros(136, np.int64))
    nal = (syn.NAL_IDR, 3) if KINDS[kind]["idr"] else (syn.NAL_SLICE, 2)
    got = [syn.annexb_rows(*none, *nal, mb_step=240, slice_hdr=h)
           for h in heads]
    steps = ((3, 120), (502, 160), (77, 65535), (9, 131071))
    got += [syn.annexb_rows(*none, *nal, mb_step=step, slice_hdr=heads[i])
            for i, step in steps]
    assert native_lib.available()
    without_library(monkeypatch)
    want = [python_road([b""] * 136, *nal,
                        headers=[slice_header_bytes(r * 240, **h)
                                 for r in range(136)])
            for h in heads]
    want += [syn.annexb_rows(*none, *nal, mb_step=step, slice_hdr=heads[i])
             for i, step in steps]
    assert got == want


def test_a_short_cap_is_said_and_the_caller_retries():
    rows = [bytes(40), b"\1\2\3", bytes(9)]
    src, off, lens = laid_out(rows)
    need = python_road(rows, syn.NAL_SLICE, 2, prefix=b"pre")
    for cap in (0, 4, 5, len(need) - 4):        # one byte short at most
        assert native_lib.annexb_rows(src, off, lens, 0x41, cap,
                                      prefix=b"pre") == -1
    assert native_lib.annexb_rows(src, off, lens, 0x41, len(need) - 3,
                                  prefix=b"pre") == need
    # all zeros grow by half: annexb_rows' first cap is short, its
    # second is the worst case
    big = [bytes(4000)] * 5
    src, off, lens = laid_out(big)
    assert native_lib.annexb_rows(src, off, lens, 0x41, 5 * 5000) == -1
    assert syn.annexb_rows(src, off, lens, syn.NAL_SLICE, 2) == python_road(
        big, syn.NAL_SLICE, 2)


@pytest.mark.parametrize("off,length", [(-1, 2), (0, -1), (9, 2), (11, 0),
                                        (2 ** 62, 2 ** 62)])
def test_a_row_outside_its_buffer_is_refused(off, length):
    src = np.zeros(10, np.uint8)
    with pytest.raises(ValueError, match="outside"):
        native_lib.annexb_rows(src, np.array([0, off]), np.array([10, length]),
                               0x41, 100)


# ---------------------------------------------------------------------------
# the two frame roads, with the library and with it patched away
# ---------------------------------------------------------------------------

def flat_buffer(rows):
    """ops/cavlc_device's flat buffer around ``rows``: the metadata words
    (big-endian: overflow, total words, row bytes, row word offsets), then
    every row from a word boundary."""
    meta = np.zeros(cavlc_device.META_WORDS, np.uint32)
    body, at = bytearray(), 0
    for r, row in enumerate(rows):
        meta[2 + r] = len(row)
        meta[2 + cavlc_device.MAX_META_ROWS + r] = at
        body += bytes(row) + bytes(-len(row) % 4)
        at = len(body) // 4
    meta[1] = at
    buf = np.frombuffer(meta.astype(">u4").tobytes() + bytes(body), np.uint8)
    return buf, cavlc_device.FlatMeta(buf, len(rows))


@pytest.mark.parametrize("kind", ["idr", "p"])
def test_the_cavlc_road_with_and_without_the_library(kind, monkeypatch):
    rng = np.random.default_rng(5)
    rows = [rng.integers(0, 4, n, dtype=np.uint8)       # escapes abound
            for n in (1, 2, 3, 700, 64, 5, 1499)]
    buf, meta = flat_buffer(rows)
    assert not meta.overflow and list(meta.row_bytes) == [len(r) for r in rows]
    how = (dict(headers=b"\0\0\0\1\x67sps\0\0\0\1\x68pps") if kind == "idr"
           else dict(nal_type=syn.NAL_SLICE, ref_idc=2))
    got = cavlc_device.assemble_annexb(buf, meta, **how)
    without_library(monkeypatch)
    assert got == cavlc_device.assemble_annexb(buf, meta, **how)
    assert got == python_road(rows, syn.NAL_IDR if kind == "idr" else 1,
                              3 if kind == "idr" else 2,
                              prefix=how.get("headers", b""))


def record_stream(rng, rows, records):
    """A transport buffer of ops/cabac_binarize's wire format: random DEC,
    RUN and BYP records a row, closed by TRM 1."""
    bits, words = [], []
    for _ in range(rows):
        v = n = 0
        for kind in rng.integers(0, 3, records):
            ctx = int(rng.integers(0, 460))
            if kind == 0:
                rec, ln = (ctx << 1) | int(rng.integers(0, 2)), 11
            elif kind == 1:
                rec, ln = (2 << 13) | (ctx << 4) | int(rng.integers(1, 16)), 15
            else:
                cnt = int(rng.integers(1, 16))
                rec = (6 << (4 + cnt)) | (cnt << cnt) | int(
                    rng.integers(0, 1 << cnt))
                ln = 7 + cnt
            v, n = (v << ln) | rec, n + ln
        v, n = (v << 4) | 0b1111, n + 4                 # TRM 1
        bits.append(n)
        pad = -n % 32
        words += list(np.frombuffer(
            (v << pad).to_bytes((n + pad) // 8, "big"), ">u4"))
    head = np.zeros(cabac_binarize.META_WORDS, np.uint32)
    head[0], head[2], head[3] = 2, len(words), rows
    return np.concatenate([head, np.array(bits, np.uint32),
                           np.array(words, np.uint32)])


@pytest.mark.parametrize("engine", ["native_engine", "python_engine"])
@pytest.mark.parametrize("kind", ["idr", "p"])
def test_the_cabac_road_with_and_without_the_library(kind, engine,
                                                     monkeypatch):
    """Both ``*_from_binstream`` functions: the native framing of the
    native engine's uncut buffer against the Python framing, of the
    native engine's rows and of the Python engine's."""
    nr, nc_mb = 9, 240
    buf = record_stream(np.random.default_rng(11), nr, 300)
    if kind == "idr":
        def code():
            return h264_cabac.encode_intra_from_binstream(
                buf, nr=nr, nc_mb=nc_mb, qp=30, idr_pic_id=1, sps=b"\x4dsps",
                pps=b"\xeepps", qp_delta=4, deblocking_idc=0)
    else:
        def code():
            return h264_cabac.encode_p_from_binstream(
                buf, nr=nr, nc_mb=nc_mb, qp=22, frame_num=13, qp_delta=-4,
                deblocking_idc=0, cabac_init_idc=2)
    assert native_lib.has_cabac_engine()
    got = code()
    without_library(monkeypatch)
    if engine == "python_engine":
        monkeypatch.setattr(native_lib, "has_cabac_engine", lambda: False)
    want = code()
    assert got == want
    # and the parts, taken apart by hand
    nals = want.split(syn.START_CODE)[1:]
    if kind == "idr":
        assert nals[0] == b"\x67\x4dsps" and nals[1] == b"\x68\xeepps"
        nals = nals[2:]
    assert len(nals) == nr
    hdr = (dict(slice_type=7, frame_num=0, idr=True, idr_pic_id=1, qp_delta=4)
           if kind == "idr" else
           dict(slice_type=5, frame_num=13, idr=False, qp_delta=-4,
                cabac_init_idc=2))
    for r, nal in enumerate(nals):
        head = slice_header_bytes(r * nc_mb, cabac=True, deblocking_idc=0,
                                  **hdr)
        assert nal[0] == (0x65 if kind == "idr" else 0x41)
        assert nal[1:1 + len(head)] == head      # no escape falls in these
