"""H.264 (ISO/IEC 14496-10) bitstream syntax: Exp-Golomb, NAL wrapping,
SPS/PPS/slice headers.

This replaces the bitstream-construction half of the reference's
``nvh264enc`` element (reference Dockerfile:210): NVENC emits Annex-B NAL
units in silicon; we emit them first-party.  Only baseline-profile intra
tools are produced initially (CAVLC, I-slices), matching the reference's
``WEBRTC_ENCODER`` default envelope of constrained-baseline H.264
(README.md:19-21).
"""

from __future__ import annotations

import numpy as np

from ..obs import metrics as obsm
from .bitwriter import BitWriter

_M_ASSEMBLE = obsm.counter(
    "dngd_encoder_assemble_total",
    "Calls that framed a frame's row slices as Annex-B NAL units "
    "(annexb_rows: one a frame; one a shard on a CAVLC mesh): native = one "
    "call into native/entropy.cpp over all rows; python = the library was "
    "not built and every byte of every row went through the Python escape "
    "loop, about 50 times slower",
    ("road",))
_M_ASSEMBLE_NATIVE = _M_ASSEMBLE.labels("native")
_M_ASSEMBLE_PYTHON = _M_ASSEMBLE.labels("python")


# ---------------------------------------------------------------------------
# Exp-Golomb
# ---------------------------------------------------------------------------

def write_ue(bw: BitWriter, v: int) -> None:
    """Unsigned Exp-Golomb code."""
    assert v >= 0
    code = v + 1
    nbits = code.bit_length()
    bw.write(0, nbits - 1)
    bw.write(code, nbits)


def write_se(bw: BitWriter, v: int) -> None:
    """Signed Exp-Golomb: 0, 1, -1, 2, -2 ... -> ue(0), ue(1), ue(2) ..."""
    write_ue(bw, 2 * v - 1 if v > 0 else -2 * v)


def rbsp_trailing_bits(bw: BitWriter) -> None:
    bw.write(1, 1)
    bw.pad_to_byte(0)


# ---------------------------------------------------------------------------
# NAL units
# ---------------------------------------------------------------------------

NAL_SLICE = 1
NAL_IDR = 5
NAL_SEI = 6
NAL_SPS = 7
NAL_PPS = 8

START_CODE = b"\x00\x00\x00\x01"


def emulation_prevention(rbsp: bytes) -> bytes:
    """Insert 0x03 after any 0x0000 followed by 0x00/01/02/03 (spec §7.4.1.1)."""
    out = bytearray()
    zeros = 0
    for b in rbsp:
        if zeros >= 2 and b <= 3:
            out.append(3)
            zeros = 0
        out.append(b)
        zeros = zeros + 1 if b == 0 else 0
    return bytes(out)


def nal_unit(nal_type: int, rbsp: bytes, ref_idc: int = 3) -> bytes:
    """Annex-B NAL unit: start code + header byte + EPB-escaped RBSP.

    For the single NALs: SPS, PPS, the host-entropy fallbacks' slices.
    The threshold keeps a short RBSP (an SPS is a dozen bytes) off a
    ``ctypes`` call whose fixed cost, two array wraps and a copy out, is
    that of a few hundred bytes of the Python loop; it was set generously,
    which priced every row slice (0.2-2 KB) at the loop's 54 ns a byte
    while the frame roads came through here a row at a time.  They do not
    any more: :func:`annexb_rows` frames all rows in one native call, and
    only its fallback without the library calls this a row.
    """
    from ..native import lib as native_lib
    header = bytes([(ref_idc << 5) | nal_type])
    if len(rbsp) > 4096 and native_lib.available():
        escaped = native_lib.emulation_prevention(rbsp)
    else:
        escaped = emulation_prevention(rbsp)
    return START_CODE + header + escaped


def annexb_rows(src: np.ndarray, row_off, row_len, nal_type: int,
                ref_idc: int = 3, *, prefix: bytes = b"", mb_step: int = 0,
                slice_hdr: dict = None) -> bytes:
    """A frame's row slices as Annex-B NAL units behind ``prefix`` (the
    SPS/PPS of an IDR): row ``r``'s RBSP is ``src[row_off[r]:][:row_len[r]]``
    of the uint8 buffer ``src``, so neither frame road copies a row.

    ``slice_hdr`` (the CABAC road, whose slice headers are the host's):
    :func:`slice_header`'s keywords but ``first_mb``, which is
    ``r * mb_step``; the header is padded with cabac_alignment_one_bits
    and escaped with the row as one RBSP.  ``None`` where the rows carry
    their headers (device CAVLC).

    With the compiled library ONE call frames every row
    (native/entropy.cpp ``h264_annexb_rows``); without it
    :func:`nal_unit` a row, the same bytes (tests/test_annexb_rows.py).
    Counted by road in ``dngd_encoder_assemble_total``."""
    from ..native import lib as native_lib
    rows = len(row_off)
    if native_lib.available():
        _M_ASSEMBLE_NATIVE.inc()
        tail = tail_nbits = 0
        if slice_hdr is not None:
            # the headers differ in first_mb alone, their first field:
            # with first_mb 0 that field is the one bit 1; the rest is
            # every row's tail
            bw = BitWriter()
            slice_header(bw, first_mb=0, **slice_hdr)
            bits, nbits = bw.peek_bits()
            tail_nbits = nbits - 1
            tail = bits - (1 << tail_nbits)
        # a row: start code, NAL byte, at most 16 bytes of slice header,
        # its RBSP.  Escapes are rare in entropy-coded bytes; the worst
        # case (all zeros: three bytes for two) where that was short
        total = int(np.sum(row_len)) + 21 * rows
        for cap in (total + total // 16, total + total // 2):
            au = native_lib.annexb_rows(
                src, row_off, row_len, (ref_idc << 5) | nal_type, cap,
                prefix=prefix, mb_step=mb_step, hdr_tail=tail,
                hdr_tail_nbits=tail_nbits)
            if not isinstance(au, int):
                return au
        raise RuntimeError("annexb_rows: worst-case output cap too short")
    _M_ASSEMBLE_PYTHON.inc()
    out = bytearray(prefix)
    for r in range(rows):
        start = int(row_off[r])
        rbsp = src[start:start + int(row_len[r])].tobytes()
        if slice_hdr is not None:
            bw = BitWriter()
            slice_header(bw, first_mb=r * mb_step, **slice_hdr)
            bw.pad_to_byte(1)             # cabac_alignment_one_bit
            rbsp = bw.getvalue() + rbsp
        out += nal_unit(nal_type, rbsp, ref_idc=ref_idc)
    return bytes(out)


# ---------------------------------------------------------------------------
# Parameter sets (baseline profile)
# ---------------------------------------------------------------------------

# Table A-1 from level 4.2 up: (level_idc, MaxMBPS, MaxFS).
_LEVELS = ((42, 522240, 8704), (50, 589824, 22080), (51, 983040, 36864),
           (52, 2073600, 36864), (60, 4177920, 139264),
           (61, 8355840, 139264), (62, 16711680, 139264))


def level_idc_for(width: int, height: int, fps: float) -> int:
    """The lowest level of Table A-1 that holds ``fps`` pictures a second
    of this size (MaxFS, MaxMBPS, and A.3.1's sides of at most
    sqrt(8 * MaxFS) macroblocks), never under 4.2: what a hardware decoder
    sizes itself by.  Past 6.2 the stream declares 6.2."""
    mb_w = (width + 15) // 16
    mb_h = (height + 15) // 16
    fs = mb_w * mb_h
    for idc, max_mbps, max_fs in _LEVELS:
        if (fs <= max_fs and fs * fps <= max_mbps
                and max(mb_w, mb_h) ** 2 <= 8 * max_fs):
            return idc
    return _LEVELS[-1][0]


def sps_rbsp(width: int, height: int, fps: float = 60.0,
             profile: str = "baseline", coded_height: int = None) -> bytes:
    """Sequence parameter set for progressive 4:2:0.

    ``fps``: the refresh the stream is built for; with the CODED size it
    sets ``level_idc`` (:func:`level_idc_for`).
    ``profile``: "baseline" (CAVLC streams) or "main" (required for
    CABAC, spec A.2.2 — baseline excludes entropy_coding_mode_flag=1).
    ``coded_height``: lines the encoder codes where that is more than
    ``height`` rounded up to 16 (a picture padded until a mesh's shards
    divide its macroblock rows, models/h264.py); cropped back as well.
    Frame cropping carries non-multiple-of-16 dimensions; POC type 2 keeps
    the slice header free of POC syntax for an I/P-only stream.
    """
    mb_w = (width + 15) // 16
    mb_h = (max(height, coded_height or 0) + 15) // 16
    crop_r = mb_w * 16 - width      # luma samples to crop on the right
    crop_b = mb_h * 16 - height     # and bottom
    bw = BitWriter()
    if profile == "main":
        bw.write(77, 8)              # profile_idc: main
        bw.write(0b01000000, 8)      # constraint_set1 (main), reserved 0
    else:
        bw.write(66, 8)              # profile_idc: baseline
        bw.write(0b11000000, 8)      # constraint_set0+1, reserved zeros
    bw.write(level_idc_for(width, mb_h * 16, fps), 8)
    write_ue(bw, 0)                  # seq_parameter_set_id
    write_ue(bw, 0)                  # log2_max_frame_num_minus4 -> 4 bits
    write_ue(bw, 2)                  # pic_order_cnt_type
    write_ue(bw, 1)                  # max_num_ref_frames
    bw.write(0, 1)                   # gaps_in_frame_num_value_allowed
    write_ue(bw, mb_w - 1)           # pic_width_in_mbs_minus1
    write_ue(bw, mb_h - 1)           # pic_height_in_map_units_minus1
    bw.write(1, 1)                   # frame_mbs_only_flag
    bw.write(1, 1)                   # direct_8x8_inference_flag
    if crop_r or crop_b:
        bw.write(1, 1)               # frame_cropping_flag
        write_ue(bw, 0)              # left (chroma units: /2)
        write_ue(bw, crop_r // 2)    # right
        write_ue(bw, 0)              # top
        write_ue(bw, crop_b // 2)    # bottom
    else:
        bw.write(0, 1)
    bw.write(0, 1)                   # vui_parameters_present_flag
    rbsp_trailing_bits(bw)
    return bw.getvalue()


def pps_rbsp(init_qp: int = 26, cabac: bool = False) -> bytes:
    """Picture parameter set: CAVLC or CABAC entropy coding.

    deblocking_filter_control_present_flag=1 lets every slice header turn
    the loop filter off (disable_deblocking_filter_idc=1), which our
    parallel closed-loop reconstruction requires to stay bit-exact.
    """
    bw = BitWriter()
    write_ue(bw, 0)                  # pic_parameter_set_id
    write_ue(bw, 0)                  # seq_parameter_set_id
    bw.write(1 if cabac else 0, 1)   # entropy_coding_mode_flag
    bw.write(0, 1)                   # bottom_field_pic_order_in_frame_present
    write_ue(bw, 0)                  # num_slice_groups_minus1
    write_ue(bw, 0)                  # num_ref_idx_l0_default_active_minus1
    write_ue(bw, 0)                  # num_ref_idx_l1_default_active_minus1
    bw.write(0, 1)                   # weighted_pred_flag
    bw.write(0, 2)                   # weighted_bipred_idc
    write_se(bw, init_qp - 26)       # pic_init_qp_minus26
    write_se(bw, 0)                  # pic_init_qs_minus26
    write_se(bw, 0)                  # chroma_qp_index_offset
    bw.write(1, 1)                   # deblocking_filter_control_present_flag
    bw.write(0, 1)                   # constrained_intra_pred_flag
    bw.write(0, 1)                   # redundant_pic_cnt_present_flag
    rbsp_trailing_bits(bw)
    return bw.getvalue()


def slice_header(bw: BitWriter, *, first_mb: int, slice_type: int,
                 frame_num: int, idr: bool, idr_pic_id: int = 0,
                 qp_delta: int = 0, deblocking_idc: int = 1,
                 cabac: bool = False, cabac_init_idc: int = 0) -> None:
    """Write a slice header (I=7 / P=5 all-slices-same-type variants).

    Assumes the SPS/PPS above: frame_num is 4 bits, POC type 2,
    deblocking control present.  With ``cabac`` (PPS
    entropy_coding_mode_flag=1), P slices carry cabac_init_idc
    (spec 7.3.3) — the caller appends cabac_alignment_one_bit padding
    before the arithmetic-coded slice data.
    """
    write_ue(bw, first_mb)           # first_mb_in_slice
    write_ue(bw, slice_type)         # 7 = I (all), 5 = P (all)
    write_ue(bw, 0)                  # pic_parameter_set_id
    bw.write(frame_num & 0xF, 4)     # frame_num
    if idr:
        write_ue(bw, idr_pic_id)     # idr_pic_id
    if slice_type % 5 == 0:          # P slice
        bw.write(0, 1)               # num_ref_idx_active_override_flag
        bw.write(0, 1)               # ref_pic_list_modification_flag_l0
    if idr:
        bw.write(0, 1)               # no_output_of_prior_pics_flag
        bw.write(0, 1)               # long_term_reference_flag
    elif slice_type % 5 == 0:
        bw.write(0, 1)               # adaptive_ref_pic_marking_mode_flag
    if cabac and slice_type % 5 != 2 and slice_type % 5 != 4:
        write_ue(bw, cabac_init_idc)  # cabac_init_idc (P slices)
    write_se(bw, qp_delta)           # slice_qp_delta
    write_ue(bw, deblocking_idc)     # disable_deblocking_filter_idc
    if deblocking_idc != 1:
        write_se(bw, 0)              # slice_alpha_c0_offset_div2
        write_se(bw, 0)              # slice_beta_offset_div2
