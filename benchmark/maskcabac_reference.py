#!/usr/bin/env python3
"""The damage-masked CABAC encoder against its plain references, at the timed
size, by hand (not inside a run: a second encoder's programs are compiled and
the Python coder codes every planned row again).

    chiprun --timeout 3000 -- python3 benchmark/maskcabac_reference.py \
        --workload desk1600-cabac-mask.desktop --seed <n> \
        [--start 660 --frames 120]

One IDR and the frames after it (``--frames``, 16; from frame ``--start`` of
the cell's traffic; the encoder's own GOP puts an IDR every 60) go through the
encoder the cell serves (``make_encoder`` under the configuration's
environment, as ``run.py`` builds it: ``ENCODER_ENTROPY=cabac``, the binarizer
on the device, ``DNGD_DAMAGE_MASK`` on), through ``encode_submit`` /
``encode_collect``.  For every P frame:

(i)   the rows a plain loop over macroblocks of ``sum |y - y_prev| > thr`` on
      the frame's luma names (``mask_reference.rows_that_changed``) must be
      the rows of the frame's plan: a frame of the row program has exactly
      those (row 0 alone where nothing changed), and a frame the dense
      programs coded must have more of them than the ladder's top;
(ii)  every slice of an UNPLANNED row is parsed by a decoder written here:
      the slice header by ``mask_reference``'s plain reader (with
      ``cabac_init_idc``, and the alignment ones behind it), the slice data
      by this file's own CABAC DECODING engine (9.3.1.2, 9.3.3.2: contexts
      initialised from the slice's qp, ``DecodeDecision``, ``DecodeTerminate``,
      ``RenormD``; nothing of the program's coder runs backwards).  It must
      hold ``mb_w`` times ``mb_skip_flag`` 1 under ctxIdx 11 and
      ``end_of_slice_flag`` 0, the last one 1, with the stop bit where the
      engine's last read ends, and nothing else;
(iii) the whole access unit must be, byte for byte, what the pure-Python dense
      CABAC coder (``h264_cabac.encode_p_picture(use_native=False)``) makes
      of the worklist's level tensors scattered to the full frame (every
      other row zero), with the served ``qp`` and ``frame_num``;
(iv)  the planned rows' slices must be byte-identical to those rows' slices
      from a DENSE CABAC encoder (the cell's environment with the mask off)
      that was given the masked encoder's reference picture
      (``import_state``) and its qp for that frame;
and the whole stream goes through cv2's ffmpeg: the decoder's luma must be the
masked encoder's own reference picture after every frame.

No tolerance: every number compared is exact.  The last line of standard
output is one JSON object; exit code 0 only if all of it holds for every
frame.  ``--rehearse --geometry WxH`` runs it on XLA:CPU, for the tests;
``--fault stale_row`` leaves the most damaged row out of the encoder's plan
from the second P frame on (``mask_reference.lose_a_row``), for the test that
this check can fail.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("OPENCV_LOG_LEVEL", "ERROR")

from benchmark import mask_reference as plain  # noqa: E402

MASK_KNOB, THR_KNOB = plain.MASK_KNOB, plain.THR_KNOB

# Table 9-13, ctxIdx 11..13 (mb_skip_flag of P and SP slices): (m, n) for
# cabac_init_idc 0, 1, 2
SKIP_CTX_MN = (((23, 33), (23, 2), (21, 0)),
               ((22, 25), (34, 0), (16, 0)),
               ((29, 16), (25, 0), (14, 0)))


# -- (ii) a CABAC decoder for the slices that must be all-skip ----------------

class Decoder:
    """The arithmetic DECODING engine of 9.3.1.2 and 9.3.3.2 over the bytes
    of a slice's data, with the one context an all-skip row reads."""

    def __init__(self, data: bytes, slice_qp: int, cabac_init_idc: int,
                 tables):
        self.rng_lps, self.trans_mps, self.trans_lps = tables
        self.data, self.pos = data, 0
        self.state, self.mps = {}, {}
        for ctx, (m, n) in zip((11, 12, 13), SKIP_CTX_MN[cabac_init_idc]):
            pre = min(max(((m * min(max(slice_qp, 0), 51)) >> 4) + n, 1), 126)
            self.state[ctx], self.mps[ctx] = (
                (63 - pre, 0) if pre <= 63 else (pre - 64, 1))
        self.range = 510
        self.offset = self.bits(9)

    def bits(self, n: int) -> int:
        v = 0
        for _ in range(n):
            byte = self.data[self.pos >> 3]        # IndexError: ran out
            v = (v << 1) | ((byte >> (7 - (self.pos & 7))) & 1)
            self.pos += 1
        return v

    def renorm(self) -> None:
        while self.range < 256:
            self.range <<= 1
            self.offset = (self.offset << 1) | self.bits(1)

    def decision(self, ctx: int) -> int:
        s = self.state[ctx]
        lps = int(self.rng_lps[s][(self.range >> 6) & 3])
        self.range -= lps
        if self.offset >= self.range:
            b = 1 - self.mps[ctx]
            self.offset -= self.range
            self.range = lps
            if s == 0:
                self.mps[ctx] = 1 - self.mps[ctx]
            self.state[ctx] = int(self.trans_lps[s])
        else:
            b = self.mps[ctx]
            self.state[ctx] = int(self.trans_mps[s])
        self.renorm()
        return b

    def terminate(self) -> int:
        self.range -= 2
        if self.offset >= self.range:
            return 1                   # no renormalisation: parsing ends
        self.renorm()
        return 0

    def ended_on_the_stop_bit(self) -> bool:
        """After ``end_of_slice_flag`` 1 the last bit the engine read is the
        rbsp_stop_one_bit; zeros to the end of the byte, and no byte more."""
        if self.pos < 1 or self.pos > 8 * len(self.data):
            return False
        at = self.pos - 1
        last = (self.data[at >> 3] >> (7 - (at & 7))) & 1
        left = 8 * len(self.data) - self.pos
        return last == 1 and left < 8 and (
            left == 0 or self.data[-1] & ((1 << left) - 1) == 0)


def pic_init_qp(headers: bytes) -> int:
    """26 + pic_init_qp_minus26 of the PPS the encoder sent (7.3.2.2)."""
    for nal in plain.nal_units(headers):
        if nal[0] & 0x1F == 8:
            b = plain.Bits(nal)
            b.ue(), b.ue()                         # pps id, sps id
            b.u(1), b.u(1)                         # entropy mode, poc present
            if b.ue():
                raise SystemExit("slice groups: not this reader's")
            b.ue(), b.ue()                         # num_ref_idx defaults
            b.u(1), b.u(2)                         # weighted pred, bipred
            return 26 + b.se()
    raise SystemExit("the encoder sent no PPS")


def all_skip_cabac_row(nal: bytes, sp: dict, row: int, init_qp: int,
                       tables):
    """``None`` where ``nal`` is a CABAC P slice that starts at ``row``'s
    first macroblock and holds ``mb_w`` skipped macroblocks and nothing
    else; else a word on what it is instead.  Returns the slice's qp beside
    it: ``(fault, qp)``."""
    if nal[0] & 0x1F != 1 or not sp["cabac"] or sp["weighted"]:
        return "not a CABAC P slice", None
    b = plain.Bits(nal)
    try:
        first_mb, slice_type = b.ue(), b.ue()
        if first_mb != row * sp["mb_w"] or slice_type % 5 != 0:
            return f"first_mb {first_mb}, slice_type {slice_type}", None
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
            return "a reference list modification", None
        if nal[0] >> 5 and b.u(1):                 # adaptive marking
            return "adaptive reference marking", None
        idc = b.ue()                               # cabac_init_idc
        if idc > 2:
            return f"cabac_init_idc {idc}", None
        qp = init_qp + b.se()                      # slice_qp_delta
        if sp["deblock_control"] and b.ue() != 1:
            b.se(), b.se()
        while b.pos & 7:                           # cabac_alignment_one_bit
            if b.u(1) != 1:
                return "a zero among the alignment bits", qp
        dec = Decoder(b.data[b.pos >> 3:], qp, idc, tables)
        for mb in range(sp["mb_w"]):
            # the macroblock to the left is skipped or outside the slice,
            # the one above is in another slice: ctxIdxInc 0
            if dec.decision(11) != 1:
                return f"macroblock {mb} is not skipped", qp
            if dec.terminate() != (mb == sp["mb_w"] - 1):
                return f"end_of_slice_flag wrong at macroblock {mb}", qp
        if not dec.ended_on_the_stop_bit():
            return "bytes behind the last macroblock", qp
        return None, qp
    except IndexError:
        return "the slice ends inside its syntax", None


# -- (iii) the Python coder over the scattered levels -------------------------

def reference_unit(enc, token) -> bytes:
    """What the pure-Python dense CABAC coder makes of a submitted frame's
    level tensors: a dense frame's as they are, a masked frame's worklist
    scattered to the full frame by the plan's rows, every other row zero."""
    import numpy as np

    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac
    from docker_nvidia_glx_desktop_tpu.ops import level_pack

    keys = [k for k, _, _ in level_pack.P_KEYS]
    kind, payload = token[0], token[4]
    if kind == "cabac_p":
        _, out, _, _, _, mv, qp, frame_num = payload
        dense = {k: np.asarray(out[k]) for k in keys}
        dense["mv"] = np.asarray(mv, np.int32)
    else:
        qp, frame_num, plan, levels, mv, _, _ = payload
        band = {k: np.asarray(levels[k]) for k in keys}
        band["mv"] = np.asarray(mv, np.int32)
        n = len(plan.rows)                 # the band's first n rows are it
        dense = {}
        for k, v in band.items():
            dense[k] = np.zeros((enc.mb_h,) + v.shape[1:], v.dtype)
            dense[k][np.asarray(plan.rows)] = v[:n]
    return h264_cabac.encode_p_picture(
        dense, qp=qp, frame_num=frame_num, qp_delta=qp - enc.qp,
        deblocking_idc=enc._deblock_idc, use_native=False)


def token_plan(token):
    """``(qp, rows of the plan or None for a dense frame)``."""
    payload = token[4]
    if token[0] == "cabac_p_mask":
        return payload[0], [int(r) for r in payload[2].rows]
    return payload[-2], None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--frames", type=int, default=16,
                    help="frames behind the first IDR")
    ap.add_argument("--start", type=int, default=0,
                    help="the traffic's frame the first IDR is")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--geometry", default=None)
    ap.add_argument("--fault", choices=("stale_row",), default=None)
    args = ap.parse_args(argv)

    from benchmark import run as bench

    spec = bench.resolve_cell(args.workload)
    env = dict(spec["config"]["env"])
    if env.get(MASK_KNOB) != "true" or env.get("ENCODER_ENTROPY") != "cabac":
        raise SystemExit(f"{args.workload}: not a configuration with "
                         f"{MASK_KNOB} on under ENCODER_ENTROPY=cabac")
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
    from docker_nvidia_glx_desktop_tpu.bitstream import cabac_tables
    from docker_nvidia_glx_desktop_tpu.models import make_encoder
    from docker_nvidia_glx_desktop_tpu.ops import damage_mask
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
        plain.lose_a_row()
    mask_enc, name = make_encoder(cfg, width, height)
    os.environ[MASK_KNOB] = "false"        # the dense encoder's environment
    dense_enc, _ = make_encoder(from_env(), width, height)
    assert mask_enc.damage_mask and not dense_enc.damage_mask
    assert mask_enc.cabac_device_binarize and mask_enc.entropy == "cabac"
    sp = plain.stream_parameters(mask_enc.headers())
    assert sp["mb_w"] == width // 16 and sp["cabac"], sp
    init_qp = pic_init_qp(mask_enc.headers())
    tables = cabac_tables.engine_tables()  # the standard's three tables
    total = height // 16
    top = max(damage_mask.bucket_ladder(total))
    scene = bench.build_scene(spec["traffic"], width, height, cfg.refresh,
                              args.seed)
    mask_enc.request_keyframe()
    data, refs, frames, prev = mask_enc.headers(), [], [], None
    t_ref = 0.0
    for c in range(args.frames + 1):
        rgb = np.zeros((height, width, 3), np.uint8)
        scene.render(args.start + c, rgb)
        barcode.draw(rgb, args.start + c)
        y = check.source_luma(rgb)
        before = mask_enc.export_state()
        token = mask_enc.encode_submit(rgb)
        line = {"frame": args.start + c, "kind": token[0]}
        if token[0] != "cabac_intra":
            qp, planned = token_plan(token)
            t0 = time.monotonic()
            want = reference_unit(mask_enc, token)
            t_ref += time.monotonic() - t0
        ef = mask_enc.encode_collect(token)
        data += ef.data
        refs.append(np.array(mask_enc.export_state()["ref"][0]))
        line.update(keyframe=ef.keyframe, bytes=len(ef.data))
        if not ef.keyframe:
            # (i) the plan against a plain difference of the two lumas
            must = plain.rows_that_changed(y, prev, thr)
            if planned is None:
                plan_fault = len(must) <= top
                coded = list(range(total))
            else:
                plan_fault = planned != (must or [0])
                coded = planned
            got = plain.slices_by_row(ef.data, sp["mb_w"])
            # (ii) every unplanned row through this file's decoder
            not_skipped = {}
            for r in range(total):
                if r in coded:
                    continue
                fault, slice_qp = (all_skip_cabac_row(
                    got[r], sp, r, init_qp, tables) if r in got
                    else ("no slice", None))
                if fault is None and slice_qp != qp:
                    fault = f"slice qp {slice_qp}, the frame's is {qp}"
                if fault is not None:
                    not_skipped[r] = fault
            # (iii) the whole unit against the Python coder
            units = check_units(ef.data, want)
            # (iv) the planned rows against the dense CABAC encoder
            dense_enc.import_state(before)
            dense_enc._force_idr = False   # (an import asks for an IDR)
            dense_enc._forced_qp = qp
            dense_au = dense_enc.encode(rgb)
            assert not dense_au.keyframe
            dense = plain.slices_by_row(dense_au.data, sp["mb_w"])
            line.update(
                qp=qp, rows=len(got), must_code=len(must),
                planned=len(coded), plan_fault=bool(plan_fault),
                not_skipped=dict(list(not_skipped.items())[:8]),
                units_differing=units[:16],
                rows_differing=[r for r in coded if r not in got
                                or got[r] != dense.get(r)][:16])
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
    exact = [f for f in p_frames
             if not (f["plan_fault"] or f["not_skipped"]
                     or f["units_differing"] or f["rows_differing"])]
    result = {
        "workload": args.workload, "codec": name, "device": device,
        "geometry": [width, height], "threshold": thr,
        "frames": len(frames), "p_frames": len(p_frames),
        "idr_frames": len(frames) - len(p_frames),
        "row_program_frames": sum(f["kind"] == "cabac_p_mask"
                                  for f in p_frames),
        "rows_that_must_be_coded": sum(f["must_code"] for f in p_frames),
        "rows_planned": sum(f["planned"] for f in p_frames),
        "plans_wrong": sum(f["plan_fault"] for f in p_frames),
        "rows_decoded_as_all_skip": sum(
            f["rows"] - f["planned"] - len(f["not_skipped"])
            for f in p_frames),
        "rows_not_skipped": sum(len(f["not_skipped"]) for f in p_frames),
        "units_differing": sum(len(f["units_differing"]) for f in p_frames),
        "rows_differing": sum(len(f["rows_differing"]) for f in p_frames),
        "frames_exact": len(exact),
        "pictures_decoded": len(diffs),
        "luma_maxdiff": max(diffs) if len(diffs) == len(refs) else 255,
        "qps": sorted({f["qp"] for f in p_frames}),
        "python_coder_s": round(t_ref, 1)}
    print(json.dumps(result), flush=True)
    return 0 if (len(p_frames) > 0 and len(exact) == len(p_frames)
                 and result["luma_maxdiff"] == 0) else 1


def check_units(got: bytes, want: bytes) -> list:
    """Indices of the NAL units (a slice is a macroblock row) that differ."""
    a, b = plain.nal_units(got), plain.nal_units(want)
    return [i for i in range(max(len(a), len(b)))
            if i >= len(a) or i >= len(b) or a[i] != b[i]]


if __name__ == "__main__":
    sys.exit(main())
