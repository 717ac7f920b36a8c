#!/usr/bin/env python3
"""The damage-masked encoder against its plain references, at the timed size,
by hand (not inside a run: two encoders' programs are compiled).

    chiprun --timeout 3000 -- python3 benchmark/mask_reference.py \
        --workload desk1600-mask.desktop --seed <n>

One IDR and the P frames after it (``--frames``, 16; from frame ``--start`` of
the cell's traffic) go through the encoder the cell serves (``make_encoder``
under the configuration's environment, as ``run.py`` builds it:
``DNGD_DAMAGE_MASK`` on).  Nothing here knows of worklists or buckets.  For
every P frame:

(a) this file's own damage grid, a plain loop over macroblocks of
    ``sum |y - y_prev| > thr`` on the luma ``check.source_luma`` gives the
    frame, names the rows that MUST be coded;
(b) the access unit is split into its one-slice-a-row NAL units by a plain
    reader (start codes, emulation prevention, Exp-Golomb; the SPS and PPS the
    encoder sent say what a slice header holds).  Every row that (a) names
    must be byte-identical to that row's slice from a DENSE encoder (the
    control configuration's environment: the cell's with the mask off, which
    is ``desk1600``'s) that was given the masked encoder's reference picture
    (``import_state``) and its qp for that frame; every other row must parse
    as slice header + ``mb_skip_run == width in macroblocks`` + trailing bits;
(c) the whole stream goes through cv2's ffmpeg: the decoder's luma must be
    the masked encoder's own reference picture (``export_state()["ref"]``)
    after every frame.

No tolerance: every number compared is exact.  The last line of standard
output is one JSON object; exit code 0 only if all three hold for every frame.
``--rehearse --geometry WxH`` runs it on XLA:CPU, for the tests; ``--fault
stale_row`` leaves the most damaged row out of the encoder's plan from the
second P frame on (what a worklist that lost a row would do), for the test
that this check can fail.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("OPENCV_LOG_LEVEL", "ERROR")

MASK_KNOB = "DNGD_DAMAGE_MASK"
THR_KNOB = "DNGD_CONTENT_DAMAGE_THR"


# -- (a) the rows that must be coded ------------------------------------------

def rows_that_changed(y, prev, thr: int) -> list:
    """Macroblock rows holding a macroblock whose summed absolute luma
    difference from the frame before is over ``thr``."""
    import numpy as np

    rows = []
    for r in range(y.shape[0] // 16):
        for c in range(y.shape[1] // 16):
            a = y[16 * r:16 * r + 16, 16 * c:16 * c + 16].astype(np.int64)
            b = prev[16 * r:16 * r + 16, 16 * c:16 * c + 16].astype(np.int64)
            if int(np.abs(a - b).sum()) > thr:
                rows.append(r)
                break
    return rows


# -- (b) a plain reader of the stream -----------------------------------------

def nal_units(data: bytes) -> list:
    """The NAL units of an Annex-B byte stream, start codes taken off."""
    units, i, start = [], 0, None
    while True:
        j = data.find(b"\x00\x00\x01", i)
        if j < 0:
            break
        if start is not None:
            units.append(data[start:j].rstrip(b"\x00"))
        start = i = j + 3
    if start is not None:
        units.append(data[start:])
    return units


class Bits:
    """The RBSP of a NAL unit (header byte and emulation prevention taken
    off), read bit by bit."""

    def __init__(self, nal: bytes):
        self.data = nal[1:].replace(b"\x00\x00\x03", b"\x00\x00")
        self.pos = 0

    def u(self, n: int) -> int:
        v = 0
        for _ in range(n):
            byte = self.data[self.pos >> 3]
            v = (v << 1) | ((byte >> (7 - (self.pos & 7))) & 1)
            self.pos += 1
        return v

    def ue(self) -> int:
        zeros = 0
        while self.u(1) == 0:
            zeros += 1
        return (1 << zeros) - 1 + self.u(zeros)

    def se(self) -> int:
        k = self.ue()
        return (k + 1) // 2 if k & 1 else -(k // 2)

    def only_trailing_bits_left(self) -> bool:
        """rbsp_trailing_bits and nothing else: a one, zeros to the end."""
        left = 8 * len(self.data) - self.pos
        return 1 <= left <= 8 and self.u(left) == 1 << (left - 1)


def stream_parameters(headers: bytes) -> dict:
    """What a slice header's layout depends on, from the SPS (7.3.2.1) and
    the PPS (7.3.2.2) the encoder sent."""
    out = {}
    for nal in nal_units(headers):
        b = Bits(nal)
        if nal[0] & 0x1F == 7:
            profile = b.u(8)
            b.u(16)                                # constraint flags, level
            b.ue()                                 # seq_parameter_set_id
            if profile not in (66, 77, 88):        # (no chroma_format_idc)
                raise SystemExit(f"profile_idc {profile}: not this reader's")
            out["frame_num_bits"] = b.ue() + 4
            out["poc_type"] = b.ue()
            if out["poc_type"] == 0:
                out["poc_lsb_bits"] = b.ue() + 4
            elif out["poc_type"] == 1:
                raise SystemExit("pic_order_cnt_type 1: not this reader's")
            b.ue()                                 # max_num_ref_frames
            b.u(1)                                 # gaps allowed
            out["mb_w"] = b.ue() + 1
            b.ue()                                 # height in map units
            if not b.u(1):
                raise SystemExit("field coding: not this reader's")
        elif nal[0] & 0x1F == 8:
            b.ue(), b.ue()                         # pps id, sps id
            out["cabac"] = b.u(1)
            out["poc_present"] = b.u(1)
            if b.ue():
                raise SystemExit("slice groups: not this reader's")
            b.ue(), b.ue()                         # num_ref_idx defaults
            out["weighted"] = b.u(1)               # (P slices': the table)
            b.u(2)                                 # weighted_bipred_idc
            b.se(), b.se(), b.se()                 # init qp, qs, chroma off
            out["deblock_control"] = b.u(1)
            b.u(1)                                 # constrained_intra_pred
            out["redundant_pic_cnt"] = b.u(1)
    return out


def all_skip_row(nal: bytes, sp: dict, row: int) -> bool:
    """Whether ``nal`` is a P slice that starts at ``row``'s first macroblock
    and holds slice header, ``mb_skip_run`` of a whole row, trailing bits
    (7.3.3, 7.3.4), and nothing else."""
    if nal[0] & 0x1F != 1 or sp["cabac"] or sp["weighted"]:
        return False
    b = Bits(nal)
    try:
        first_mb, slice_type = b.ue(), b.ue()
        if first_mb != row * sp["mb_w"] or slice_type % 5 != 0:
            return False
        b.ue()                                     # pic_parameter_set_id
        b.u(sp["frame_num_bits"])
        if sp["poc_type"] == 0:
            b.u(sp["poc_lsb_bits"])
            if sp["poc_present"]:
                b.se()
        if sp["redundant_pic_cnt"]:
            b.ue()
        if b.u(1):                                 # num_ref_idx override
            b.ue()
        if b.u(1):                                 # ref_pic_list_modification
            return False
        if nal[0] >> 5 and b.u(1):                 # adaptive marking
            return False
        b.se()                                     # slice_qp_delta
        if sp["deblock_control"] and b.ue() != 1:
            b.se(), b.se()
        return b.ue() == sp["mb_w"] and b.only_trailing_bits_left()
    except IndexError:
        return False


def slices_by_row(au: bytes, mb_w: int) -> dict:
    """{row: NAL unit} of an access unit's slices (one a row)."""
    out = {}
    for nal in nal_units(au):
        if nal[0] & 0x1F in (1, 5):
            out[Bits(nal).ue() // mb_w] = nal
    return out


# -- the fault ----------------------------------------------------------------

def lose_a_row(after_calls: int = 1) -> None:
    """``ops/damage_mask.plan_rows`` forgets the row with the most damaged
    macroblocks, from the call after ``after_calls`` on."""
    from docker_nvidia_glx_desktop_tpu.ops import damage_mask

    plan_rows, calls = damage_mask.plan_rows, [0]

    def faulty(grid):
        calls[0] += 1
        if calls[0] > after_calls and grid.any(axis=1).sum() > 1:
            grid = grid.copy()
            grid[int(grid.sum(axis=1).argmax())] = 0
        return plan_rows(grid)

    damage_mask.plan_rows = faulty


def token_qp(token) -> int:
    """The qp the encoder reserved for a submitted frame."""
    payload = token[4]
    if token[0] == "intra":
        return payload[2]
    return payload[1] if payload[0] == "dmg" else payload[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--frames", type=int, default=16,
                    help="P frames behind the IDR")
    ap.add_argument("--start", type=int, default=0,
                    help="the traffic's frame the IDR is")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--geometry", default=None)
    ap.add_argument("--fault", choices=("stale_row",), default=None)
    args = ap.parse_args(argv)

    from benchmark import run as bench

    spec = bench.resolve_cell(args.workload)
    env = dict(spec["config"]["env"])
    if env.get(MASK_KNOB) != "true":
        raise SystemExit(f"{args.workload}: the configuration does not turn "
                         f"{MASK_KNOB} on")
    os.environ.update(env)
    os.environ.update({"PASSWD": "x",
                       "JAX_PLATFORMS": "cpu" if args.rehearse else "tpu"})
    if args.geometry:
        w, h = args.geometry.lower().split("x")
        os.environ.update({"SIZEW": w, "SIZEH": h})
    device = bench.attach_device(spec["cell"]["chips"], args.rehearse)
    bench.note(f"device: {json.dumps(device)}")

    import numpy as np

    from benchmark import barcode, check
    from docker_nvidia_glx_desktop_tpu.models import make_encoder
    from docker_nvidia_glx_desktop_tpu.utils.config import from_env
    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)

    setup_compile_cache()
    cfg = from_env()
    width, height = cfg.sizew, cfg.sizeh
    if width % 16 or height % 16:
        raise SystemExit("this check wants whole macroblocks: "
                         f"{width}x{height}")
    thr = int(round(float(os.environ.get(THR_KNOB, "2.0")) * 256))
    if args.fault:
        lose_a_row()
    mask_enc, name = make_encoder(cfg, width, height)
    os.environ[MASK_KNOB] = "false"        # the control's environment
    dense_enc, _ = make_encoder(from_env(), width, height)
    assert mask_enc.damage_mask and not dense_enc.damage_mask
    sp = stream_parameters(mask_enc.headers())
    assert sp["mb_w"] == width // 16, sp
    scene = bench.build_scene(spec["traffic"], width, height, cfg.refresh,
                              args.seed)
    mask_enc.request_keyframe()
    data, refs, frames, prev = mask_enc.headers(), [], [], None
    for c in range(args.frames + 1):
        rgb = np.zeros((height, width, 3), np.uint8)
        scene.render(args.start + c, rgb)
        barcode.draw(rgb, args.start + c)
        y = check.source_luma(rgb)
        before = mask_enc.export_state()
        token = mask_enc.encode_submit(rgb)
        qp = token_qp(token)
        ef = mask_enc.encode_collect(token)
        data += ef.data
        refs.append(np.array(mask_enc.export_state()["ref"][0]))
        line = {"frame": args.start + c, "keyframe": ef.keyframe, "qp": qp,
                "bytes": len(ef.data)}
        if not ef.keyframe:
            # the dense encoder from the masked one's reference, at its qp
            dense_enc.import_state(before)
            dense_enc._force_idr = False   # (an import asks for an IDR)
            dense_enc._forced_qp = qp
            want = dense_enc.encode(rgb)
            assert not want.keyframe
            got, dense = (slices_by_row(ef.data, sp["mb_w"]),
                          slices_by_row(want.data, sp["mb_w"]))
            must = rows_that_changed(y, prev, thr)
            others = [r for r in range(height // 16) if r not in must]
            line.update(
                rows=len(got), must_code=len(must),
                differing=[r for r in must if got.get(r) != dense.get(r)
                           or r not in got][:16],
                not_skipped=[r for r in others if r not in got
                             or not all_skip_row(got[r], sp, r)][:16])
        prev = y
        frames.append(line)
        bench.note(json.dumps(line))
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "reference.h264")
        with open(path, "wb") as f:
            f.write(data)
        decoded = list(check.decode_luma(path, width, height))
    diffs = [int(np.abs(luma.astype(np.int16) - ref).max())
             for luma, ref in zip(decoded, refs)]
    p_frames = [f for f in frames if not f["keyframe"]]
    result = {
        "workload": args.workload, "codec": name, "device": device,
        "geometry": [width, height], "threshold": thr,
        "frames": len(frames), "p_frames": len(p_frames),
        "rows_that_must_be_coded": sum(f["must_code"] for f in p_frames),
        "rows_differing": sum(len(f["differing"]) for f in p_frames),
        "rows_not_skipped": sum(len(f["not_skipped"]) for f in p_frames),
        "frames_exact": sum(not f["differing"] and not f["not_skipped"]
                            for f in p_frames),
        "pictures_decoded": len(diffs),
        "luma_maxdiff": max(diffs) if len(diffs) == len(refs) else 255,
        "qps": [f["qp"] for f in frames]}
    print(json.dumps(result), flush=True)
    return 0 if (len(p_frames) == args.frames
                 and result["frames_exact"] == len(p_frames)
                 and result["luma_maxdiff"] == 0) else 1


if __name__ == "__main__":
    sys.exit(main())
