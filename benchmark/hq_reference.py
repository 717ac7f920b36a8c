#!/usr/bin/env python3
"""``ENCODER_TUNE=hq`` under the loop filter against its plain references, at
the timed size, by hand (not inside a run: the plain reader takes seconds a
picture).

    chiprun --timeout 3000 -- python3 benchmark/hq_reference.py \\
        --workload desk1080-hq.fulldamage --seed <n>

One IDR and the P frames after it (``--frames``, 15; from frame ``--start`` of
the cell's traffic) go through the encoder the cell serves (``make_encoder``
under the configuration's environment, as ``run.py`` builds it).  Nothing here
knows of planes, chains or threshold tiles.  For every frame:

(a) this file's own qp plane: a plain float64 loop over macroblocks of the
    luma ``check.source_luma`` gives the frame (padded as the coded picture
    is, the last line and column repeated) by the rule ``ops/aq.py``
    documents: activity = the macroblock's summed squared deviation from its
    mean, offset = ``round(0.5 * (log2(activity + 1) - 12))`` clipped to
    -4..+1, qp = the slice's qp + offset clipped to 1..51; the slice's qp is
    read from the PPS and the slice header by a plain Exp-Golomb reader;
(b) a plain reader of the CAVLC macroblock layer (7.3.5: ``mb_skip_run``,
    ``mb_type``, the prediction syntax, ``coded_block_pattern``,
    ``mb_qp_delta``, ``residual_block_cavlc`` by the normative tables, which
    are data and taken from ``bitstream/cavlc.py`` and ``h264_entropy.py``)
    gives every macroblock's type and QPY from the STREAM.  Wherever the
    syntax carries ``mb_qp_delta``, QPY must equal (a): exactly, except the
    macroblocks whose unrounded offset lies within 2**-10 of a half (float32
    on the chip against float64 here), which are counted and may not pass
    0.1% of those compared.  Under ``fulldamage`` the P slices must hold
    I_16x16 macroblocks;
(c) the whole stream goes through cv2's ffmpeg: the decoder's luma must be the
    encoder's own reference picture (``export_state()["ref"]``) after EVERY
    frame, limit 0.  This is what holds the per-edge thresholds, the
    effective chain and the intra bS to the standard: a wrong qPav or bS is a
    wrong sample at the first edge it touches.

The last line of standard output is one JSON object; exit code 0 only if all
three hold.  ``--rehearse --geometry WxH`` runs it on XLA:CPU, for the tests;
``--fault uniform_thresholds`` hands the loop filter the slice's qp for every
edge (what the filter did before it knew of planes) and must fail (c).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("OPENCV_LOG_LEVEL", "ERROR")

from benchmark.mask_reference import Bits, nal_units  # noqa: E402  (plain readers)

TUNE_KNOB = "ENCODER_TUNE"
NEAR_HALF = 2.0 ** -10        # an unrounded offset this near k + 0.5 may round
NEAR_HALF_SHARE = 1e-3        # either way; at most this share of those compared


# -- (a) the plane the rule gives ----------------------------------------------

def padded_luma(y, pad_h: int, pad_w: int):
    """The coded picture's luma: the last line and column repeated."""
    import numpy as np
    return np.pad(y, ((0, pad_h - y.shape[0]), (0, pad_w - y.shape[1])),
                  mode="edge")


def plain_offsets(y):
    """(offset, near_half) a macroblock of the padded luma ``y``, by the
    documented rule in float64."""
    import numpy as np

    nr, nc = y.shape[0] // 16, y.shape[1] // 16
    off = np.zeros((nr, nc), np.int64)
    near = np.zeros((nr, nc), bool)
    for r in range(nr):
        for c in range(nc):
            mb = y[16 * r:16 * r + 16, 16 * c:16 * c + 16].astype(np.float64)
            act = float(((mb - mb.mean()) ** 2).sum())
            d = 0.5 * (math.log2(act + 1.0) - 12.0)
            off[r, c] = min(1, max(-4, int(np.round(d))))
            near[r, c] = abs(abs(d - math.floor(d)) - 0.5) < NEAR_HALF
    return off, near


# -- (b) a plain reader of the stream -------------------------------------------

def _tables():
    """The normative CAVLC tables as prefix-code dictionaries
    ``{(length, bits): symbol}``, from the program's DATA (Tables 9-5, 9-7,
    9-8, 9-9a, 9-10 as ``bitstream/cavlc.py`` holds them; Table 9-4 as
    ``h264_entropy.py`` does)."""
    from docker_nvidia_glx_desktop_tpu.bitstream import cavlc, h264_entropy

    def prefix(lens, bits, symbol):
        return {(ln, b): symbol(i) for i, (ln, b) in enumerate(zip(lens, bits))
                if ln}

    tok = lambda i: (i // 4, i % 4)                # (TotalCoeff, TrailingOnes)
    t = {"ct": [prefix(cavlc._CT_LEN[k], cavlc._CT_BITS[k], tok)
                for k in range(3)],
         "ct_cdc": prefix(cavlc._CT_LEN_CDC, cavlc._CT_BITS_CDC, tok),
         "tz": [prefix(ln, b, int) for ln, b in zip(cavlc._TZ_LEN,
                                                    cavlc._TZ_BITS)],
         "tz_cdc": [prefix(ln, b, int) for ln, b in zip(cavlc._TZ_LEN_CDC,
                                                        cavlc._TZ_BITS_CDC)],
         "rb": [prefix(ln, b, int) for ln, b in zip(cavlc._RB_LEN,
                                                    cavlc._RB_BITS)],
         "cbp_inter": list(h264_entropy._CBP_INTER_BY_CODENUM),
         "cbp_intra": list(h264_entropy._CBP_INTRA_BY_CODENUM)}
    return t


class Reader(Bits):
    """A slice's RBSP, with the end of its data and prefix codes."""

    def __init__(self, nal: bytes):
        super().__init__(nal)
        # the last one bit is rbsp_stop_one_bit: data ends before it
        tail = len(self.data) - 1
        while self.data[tail] == 0:
            tail -= 1
        low = self.data[tail] & -self.data[tail]
        self.end = 8 * tail + 8 - low.bit_length()

    def more_data(self) -> bool:
        return self.pos < self.end

    def vlc(self, table: dict):
        ln = val = 0
        while ln < 17:
            val, ln = (val << 1) | self.u(1), ln + 1
            if (ln, val) in table:
                return table[(ln, val)]
        raise ValueError("no such codeword")


def read_block(b, t, nc: int, max_coeff: int) -> int:
    """residual_block_cavlc (7.3.5.3.2): consumes it, returns TotalCoeff."""
    if nc == -1:
        total, t1 = b.vlc(t["ct_cdc"])
    elif nc >= 8:
        v = b.u(6)
        total, t1 = (0, 0) if v == 3 else ((v >> 2) + 1, v & 3)
    else:
        total, t1 = b.vlc(t["ct"][0 if nc < 2 else 1 if nc < 4 else 2])
    if total == 0:
        return 0
    suffix_len = 1 if total > 10 and t1 < 3 else 0
    for i in range(total):
        if i < t1:
            b.u(1)                                 # trailing_ones_sign_flag
            continue
        prefix = 0
        while b.u(1) == 0:
            prefix += 1
        code = min(15, prefix) << suffix_len
        if suffix_len > 0 or prefix >= 14:
            size = (prefix - 3 if prefix >= 15
                    else 4 if prefix == 14 and suffix_len == 0
                    else suffix_len)
            if size:
                code += b.u(size)
        if prefix >= 15 and suffix_len == 0:
            code += 15
        if prefix >= 16:
            code += (1 << (prefix - 3)) - 4096
        if i == t1 and t1 < 3:
            code += 2
        level = (code + 2) >> 1 if code % 2 == 0 else -((code + 1) >> 1)
        if suffix_len == 0:
            suffix_len = 1
        if abs(level) > (3 << (suffix_len - 1)) and suffix_len < 6:
            suffix_len += 1
    zeros_left = 0
    if total < max_coeff:
        zeros_left = b.vlc((t["tz_cdc"] if nc == -1 else t["tz"])[total - 1])
    for _ in range(total - 1):
        if zeros_left <= 0:
            break
        zeros_left -= b.vlc(t["rb"][min(zeros_left, 7) - 1])
    return total


# luma4x4BlkIdx -> (x, y) in 4x4 blocks (6.4.3)
BLK_XY = [(2 * (i // 4 % 2) + i % 2, 2 * (i // 8) + i // 2 % 2)
          for i in range(16)]


class RowState:
    """What a row (one slice) remembers from the macroblock to the left:
    its blocks' TotalCoeff, for nC (9.2.1; nothing above: another slice)."""

    def __init__(self):
        self.luma = self.cb = self.cr = None       # None: not available


def read_residual(b, t, st: RowState, *, i16: bool, cbp: int) -> None:
    luma = [[0] * 4 for _ in range(4)]             # [y][x] of this macroblock

    def nc_luma(x, y):
        a = (luma[y][x - 1] if x else
             None if st.luma is None else st.luma[y][3])
        up = luma[y - 1][x] if y else None
        return _nc(a, up)

    if i16:
        read_block(b, t, nc_luma(0, 0), 16)        # Intra16x16DCLevel
    for i, (x, y) in enumerate(BLK_XY):
        if cbp & (1 << (i // 4)):
            luma[y][x] = read_block(b, t, nc_luma(x, y), 15 if i16 else 16)
    st_c = {"cb": [[0, 0], [0, 0]], "cr": [[0, 0], [0, 0]]}
    if cbp >> 4:
        for _ in ("cb", "cr"):
            read_block(b, t, -1, 4)                # chroma DC
    if cbp >> 4 == 2:
        for plane in ("cb", "cr"):
            cur, left = st_c[plane], getattr(st, plane)
            for i in range(4):
                x, y = i % 2, i // 2
                a = cur[y][0] if x else None if left is None else left[y][1]
                up = cur[0][x] if y else None
                cur[y][x] = read_block(b, t, _nc(a, up), 15)
    st.luma, st.cb, st.cr = luma, st_c["cb"], st_c["cr"]


def _nc(a, up) -> int:
    if a is not None and up is not None:
        return (a + up + 1) >> 1
    return a if a is not None else up if up is not None else 0


def stream_parameters(headers: bytes) -> dict:
    """What this reader needs of the SPS (7.3.2.1) and the PPS (7.3.2.2)."""
    out = {}
    for nal in nal_units(headers):
        b = Bits(nal)
        if nal[0] & 0x1F == 7:
            profile = b.u(8)
            b.u(16), b.ue()
            if profile not in (66, 77, 88):
                raise SystemExit(f"profile_idc {profile}: not this reader's")
            out["frame_num_bits"] = b.ue() + 4
            if b.ue() != 2:
                raise SystemExit("pic_order_cnt_type: not this reader's")
            b.ue(), b.u(1)
            out["mb_w"] = b.ue() + 1
            out["mb_h"] = b.ue() + 1
            if not b.u(1):
                raise SystemExit("field coding: not this reader's")
        elif nal[0] & 0x1F == 8:
            b.ue(), b.ue()
            if b.u(1) or b.u(1) or b.ue():
                raise SystemExit("CABAC, field order or slice groups: not "
                                 "this reader's")
            if b.ue() or b.ue():
                raise SystemExit("more than one reference: not this reader's")
            if b.u(1) or b.u(2):
                raise SystemExit("weighted prediction: not this reader's")
            out["init_qp"] = 26 + b.se()
            b.se(), b.se()
            if not b.u(1) or b.u(1) or b.u(1):
                raise SystemExit("deblocking control, constrained intra or "
                                 "redundant pictures: not this reader's")
    return out


def read_slice(nal: bytes, sp: dict, t: dict) -> dict:
    """One slice (a macroblock row): its first macroblock, its qp, and per
    macroblock ``(kind, QPY, carries mb_qp_delta)``, kind one of ``skip``,
    ``p16``, ``i16``, ``i4``."""
    b = Reader(nal)
    idr = nal[0] & 0x1F == 5
    first_mb, slice_type = b.ue(), b.ue() % 5
    b.ue(), b.u(sp["frame_num_bits"])
    if idr:
        b.ue()
    if slice_type == 0 and (b.u(1) or b.u(1)):
        raise SystemExit("reference list syntax: not this reader's")
    if idr:
        b.u(2)
    elif nal[0] >> 5 and b.u(1):
        raise SystemExit("adaptive marking: not this reader's")
    qp = sp["init_qp"] + b.se()
    idc = b.ue()
    if idc != 1:
        b.se(), b.se()
    mbs, qpy, st = [], qp, RowState()

    def intra(mb_type: int):
        nonlocal qpy
        if mb_type == 0:                           # I_NxN
            for _ in range(16):
                if not b.u(1):
                    b.u(3)
            b.ue()                                 # intra_chroma_pred_mode
            cbp = t["cbp_intra"][b.ue()]
            carries = cbp > 0
            if carries:
                qpy = (qpy + b.se() + 52) % 52
            read_residual(b, t, st, i16=False, cbp=cbp)
            return ("i4", qpy, carries)
        if mb_type > 24:
            raise SystemExit("I_PCM: not this reader's")
        cbp = (15 if mb_type > 12 else 0) | (((mb_type - 1) // 4 % 3) << 4)
        b.ue()
        qpy = (qpy + b.se() + 52) % 52
        read_residual(b, t, st, i16=True, cbp=cbp)
        return ("i16", qpy, True)

    while len(mbs) < sp["mb_w"]:
        if slice_type == 0:
            for _ in range(b.ue()):                # mb_skip_run
                mbs.append(("skip", qpy, False))
                st.luma = [[0] * 4 for _ in range(4)]
                st.cb = st.cr = [[0, 0], [0, 0]]
            if not b.more_data():
                break
            mb_type = b.ue()
            if mb_type >= 5:
                mbs.append(intra(mb_type - 5))
                continue
            if mb_type:
                raise SystemExit(f"P mb_type {mb_type}: not this reader's")
            b.se(), b.se()                         # mvd_l0
            cbp = t["cbp_inter"][b.ue()]
            if cbp:
                qpy = (qpy + b.se() + 52) % 52
            read_residual(b, t, st, i16=False, cbp=cbp)
            mbs.append(("p16", qpy, cbp > 0))
        else:
            mbs.append(intra(b.ue()))
    if b.more_data() or len(mbs) != sp["mb_w"]:
        raise SystemExit(f"slice at macroblock {first_mb}: {len(mbs)} "
                         f"macroblocks read, data left: {b.more_data()}")
    return {"first_mb": first_mb, "qp": qp, "mbs": mbs}


def read_picture(au: bytes, sp: dict, t: dict) -> list:
    """The access unit's slices, in row order."""
    rows = {}
    for nal in nal_units(au):
        if nal[0] & 0x1F in (1, 5):
            s = read_slice(nal, sp, t)
            rows[s["first_mb"] // sp["mb_w"]] = s
    if sorted(rows) != list(range(sp["mb_h"])):
        raise SystemExit(f"rows {sorted(rows)[:8]}...: not one slice a row")
    return [rows[r] for r in range(sp["mb_h"])]


# -- the fault ------------------------------------------------------------------

def uniform_thresholds(encoder) -> None:
    """The encoder's loop filter is handed the slice's qp for every edge,
    whatever the macroblocks were coded at (the intra flags it keeps)."""
    import jax.numpy as jnp

    filt = encoder._deblock

    def faulty(y, cb, cr, qp, **kw):
        if "qp_eff" in kw:
            kw["qp_eff"] = jnp.full_like(kw["qp_eff"], qp)
        return filt(y, cb, cr, qp, **kw)

    encoder._deblock = faulty


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--frames", type=int, default=15,
                    help="P frames behind the IDR")
    ap.add_argument("--start", type=int, default=0,
                    help="the traffic's frame the IDR is")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--geometry", default=None)
    ap.add_argument("--fault", choices=("uniform_thresholds",), default=None)
    args = ap.parse_args(argv)

    from benchmark import run as bench

    spec = bench.resolve_cell(args.workload)
    env = dict(spec["config"]["env"])
    if env.get(TUNE_KNOB) != "hq":
        raise SystemExit(f"{args.workload}: the configuration does not set "
                         f"{TUNE_KNOB}=hq")
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
    enc, name = make_encoder(cfg, width, height)
    if args.fault:
        uniform_thresholds(enc)
    sp, t = stream_parameters(enc.headers()), _tables()
    pad_w, pad_h = 16 * sp["mb_w"], 16 * sp["mb_h"]
    scene = bench.build_scene(spec["traffic"], width, height, cfg.refresh,
                              args.seed)
    enc.request_keyframe()
    data, refs, frames = enc.headers(), [], []
    for c in range(args.frames + 1):
        rgb = np.zeros((height, width, 3), np.uint8)
        scene.render(args.start + c, rgb)
        barcode.draw(rgb, args.start + c)
        ef = enc.encode(rgb)
        data += ef.data
        refs.append(np.array(enc.export_state()["ref"][0][:height, :width]))
        offsets, near = plain_offsets(
            padded_luma(check.source_luma(rgb), pad_h, pad_w))
        line = {"frame": args.start + c, "keyframe": ef.keyframe,
                "bytes": len(ef.data), "compared": 0, "differing": 0,
                "near_half": 0, "i16": 0, "skipped": 0, "qps": set()}
        for r, row in enumerate(read_picture(ef.data, sp, t)):
            line["qps"].add(row["qp"])
            for col, (kind, qpy, carries) in enumerate(row["mbs"]):
                line["i16"] += kind == "i16"
                line["skipped"] += kind == "skip"
                if not carries:
                    continue
                want = min(51, max(1, row["qp"] + int(offsets[r, col])))
                line["compared"] += 1
                if qpy != want:
                    line["near_half" if near[r, col] and abs(qpy - want) == 1
                         else "differing"] += 1
        line["qps"] = sorted(line["qps"])
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
    compared = sum(f["compared"] for f in frames)
    result = {
        "workload": args.workload, "codec": name, "device": device,
        "geometry": [width, height], "frames": len(frames),
        "p_frames": len(p_frames),
        "macroblocks_compared": compared,
        "qp_differing": sum(f["differing"] for f in frames),
        "qp_near_half": sum(f["near_half"] for f in frames),
        "i16_in_p": sum(f["i16"] for f in p_frames),
        "pictures_decoded": len(diffs),
        "luma_maxdiff_by_frame": diffs,
        "luma_maxdiff": max(diffs) if len(diffs) == len(refs) else 255,
        "slice_qps": sorted({q for f in frames for q in f["qps"]})}
    print(json.dumps(result), flush=True)
    wants_intra = spec["cell"]["traffic"] == "fulldamage"
    return 0 if (len(p_frames) == args.frames and compared > 0
                 and result["qp_differing"] == 0
                 and result["qp_near_half"] <= NEAR_HALF_SHARE * compared
                 and (result["i16_in_p"] > 0 or not wants_intra)
                 and result["luma_maxdiff"] == 0) else 1


if __name__ == "__main__":
    sys.exit(main())
