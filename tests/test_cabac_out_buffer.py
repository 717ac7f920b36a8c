"""The C coders' output buffer has ONE policy (bitstream/h264_cabac.
``_out_buffer``): a thread keeps one buffer between frames, grown to the
largest size asked, and the engine's three callers write into it.  What
that must not change: a picture's bytes, once returned, and what another
thread is writing."""

import threading

import numpy as np
import pytest

NR, NC = 17, 4


def _records(seed: int, nr: int = NR, nc: int = NC):
    """``(dense level tensors, record stream)`` of a crafted P picture."""
    from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize

    rng = np.random.default_rng(seed)
    mv = rng.integers(-9, 10, (nr, nc, 2)).astype(np.int32)
    luma = np.zeros((nr, nc, 16, 16), np.int32)
    luma[:, ::2] = rng.integers(-3, 4, (nr, (nc + 1) // 2, 16, 16))
    cbd = rng.integers(-2, 3, (nr, nc, 4)).astype(np.int32)
    cba = np.zeros((nr, nc, 4, 15), np.int32)
    crd = np.zeros((nr, nc, 4), np.int32)
    cra = rng.integers(-1, 2, (nr, nc, 4, 15)).astype(np.int32)
    dense = {"mv": mv, "luma": luma, "cb_dc": cbd, "cb_ac": cba,
             "cr_dc": crd, "cr_ac": cra}
    buf = np.asarray(cabac_binarize.binarize_p(mv, luma, cbd, cba, crd, cra))
    assert int(buf[1]) == 0 and int(buf[3]) == nr
    return dense, buf


@pytest.fixture()
def engine():
    from docker_nvidia_glx_desktop_tpu.native import lib as native_lib
    if not native_lib.has_cabac_engine():
        pytest.skip("no native CABAC engine (g++) here")
    return native_lib


@pytest.fixture()
def sources(monkeypatch):
    """Every ``src`` that ``annexb_rows`` is handed, by thread."""
    from docker_nvidia_glx_desktop_tpu.bitstream import h264 as syn
    seen = []
    real = syn.annexb_rows

    def spy(src, *a, **kw):
        seen.append((threading.get_ident(), src))
        return real(src, *a, **kw)

    monkeypatch.setattr(syn, "annexb_rows", spy)
    return seen


def test_two_pictures_of_a_thread_share_the_buffer(engine, sources):
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac

    (d1, b1), (d2, b2) = _records(1), _records(2)
    au1 = h264_cabac.encode_p_from_binstream(b1, nr=NR, nc_mb=NC, qp=26,
                                             frame_num=1)
    keep1 = bytes(bytearray(au1))
    au2 = h264_cabac.encode_p_from_binstream(b2, nr=NR, nc_mb=NC, qp=31,
                                             frame_num=2)
    (_, s1), (_, s2) = sources
    assert np.shares_memory(s1, s2)
    assert s1.ctypes.data == s2.ctypes.data
    # the first picture's bytes are its own: the second left them alone
    assert au1 == keep1 != au2
    assert au1 == h264_cabac.encode_p_picture(d1, qp=26, frame_num=1,
                                              use_native=False)
    assert au2 == h264_cabac.encode_p_picture(d2, qp=31, frame_num=2,
                                              use_native=False)


def test_the_other_coders_take_the_same_buffer(engine, sources,
                                               monkeypatch):
    """``_native_slices`` (the dense fallback's C coder) writes where the
    engine wrote: one buffer a thread, whoever asks."""
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac

    dense, buf = _records(3)
    h264_cabac.encode_p_from_binstream(buf, nr=NR, nc_mb=NC, qp=26,
                                       frame_num=1)
    asked = []
    real = h264_cabac._out_buffer

    def spy(size, scale=1):
        out = real(size, scale)
        asked.append(out)
        return out

    monkeypatch.setattr(h264_cabac, "_out_buffer", spy)
    au = h264_cabac.encode_p_picture(dense, qp=26, frame_num=1)
    assert au == h264_cabac.encode_p_picture(dense, qp=26, frame_num=1,
                                             use_native=False)
    assert len(asked) == 1 and np.shares_memory(asked[0], sources[0][1])


def test_two_threads_get_different_buffers(engine, sources):
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac

    pictures = [_records(4), _records(5)]
    gate = threading.Barrier(2)
    got = {}

    def work(i):
        dense, buf = pictures[i]
        for n in range(4):
            gate.wait()
            got[i, n] = h264_cabac.encode_p_from_binstream(
                buf, nr=NR, nc_mb=NC, qp=26 + i, frame_num=1 + n)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_thread = {}
    for ident, src in sources:
        by_thread.setdefault(ident, []).append(src)
    assert len(by_thread) == 2
    a, b = by_thread.values()
    assert all(np.shares_memory(a[0], s) for s in a)
    assert all(np.shares_memory(b[0], s) for s in b)
    assert not np.shares_memory(a[0], b[0])
    for i, (dense, _) in enumerate(pictures):
        for n in range(4):
            assert got[i, n] == h264_cabac.encode_p_picture(
                dense, qp=26 + i, frame_num=1 + n, use_native=False)


def test_the_retry_at_four_times_the_cap_is_not_kept(engine):
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac

    kept = h264_cabac._out_buffer(4096)
    big = h264_cabac._out_buffer(4 * 4096, 4)
    assert len(big) == 4 * 4096 and not np.shares_memory(kept, big)
    again = h264_cabac._out_buffer(1024)
    assert np.shares_memory(kept, again) and len(again) == 1024
    assert len(h264_cabac._TLS.buf) >= 4096


def test_a_short_band_after_a_long_one_reads_as_from_a_fresh_buffer(
        engine, monkeypatch):
    """A masked frame of 3 rows after one of 17, with another tail: the
    kept buffer still holds the long band's rows and ITS tail where the
    short one's rows end, and none of it may show."""
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac

    (_, long_band), (_, short_band) = _records(6), _records(7)
    nr = 24
    long_rows = np.arange(2, 2 + NR)
    short_rows = np.array([1, 8, 20])
    tails = {27: b"\x5a" * 11, 33: b"\xc3" * 5}
    monkeypatch.setattr(h264_cabac, "skip_row_payload",
                        lambda nc_mb, qp, idc=0: (tails[qp], True))

    def frames():
        return (h264_cabac.encode_p_rows_from_binstream(
                    long_band, long_rows, nr=nr, nc_mb=NC, qp=27,
                    frame_num=1),
                h264_cabac.encode_p_rows_from_binstream(
                    short_band, short_rows, nr=nr, nc_mb=NC, qp=33,
                    frame_num=2))

    got = frames()
    assert len(h264_cabac._TLS.buf) >= NR * h264_cabac._row_cap(NC) + 11
    # the form before the kept buffer: a fresh one a call, here full of
    # bytes that no slice may hold a run of
    monkeypatch.setattr(h264_cabac, "_out_buffer",
                        lambda size, scale=1: np.full(size, 0xEE, np.uint8))
    want = frames()
    assert got[0] is not None and got[1] is not None
    assert got == want
    assert got[1].count(tails[33]) >= nr - 3 and tails[27] not in got[1]


def test_engine_refuses_a_short_output(engine):
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac
    from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize

    _, buf = _records(8)
    payload, row_off, row_bits = cabac_binarize.split_rows(buf, NR)
    cap = h264_cabac._row_cap(NC)
    with pytest.raises(AssertionError):
        engine.cabac_engine_rows(
            payload, row_off, row_bits, NR, 26,
            *h264_cabac._native_tables(1), cap,
            np.empty(NR * cap - 1, np.uint8))
