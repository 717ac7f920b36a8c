#!/usr/bin/env python3
"""The served CABAC encoder against its plain references, at the timed size,
by hand (not inside a run: the Python coder takes about a minute a 1080p
picture).

    chiprun --timeout 3000 -- python3 benchmark/cabac_reference.py \
        --workload desk1080-cabac.fulldamage --seed <n>

One IDR and the P frames after it (``--frames``, 8) of the cell's traffic go
through the encoder the cell serves (``make_encoder`` under the configuration's
environment, as ``run.py`` builds it).  For every frame the level tensors the
device stage handed the entropy path are pulled whole and coded again by the
reference of the entropy layer: the pure-Python ``bitstream/cabac.py``
``CabacEncoder``, driven in specification order by
``h264_cabac.encode_intra_picture`` / ``encode_p_picture(use_native=False)``;
the served access unit (binarized on the chip, run through the native engine)
must be the same bytes.  The whole stream then goes through the reference of
the whole path, cv2's ffmpeg: its luma must be the encoder's own reference
picture after every frame (what ``check.closed_loop_maxdiff`` holds every run
of the cell to).  The last line of standard output is one JSON object; exit
code 0 only if every frame is identical on both counts.  ``--rehearse
--geometry WxH`` runs it on XLA:CPU, for the tests.
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


def reference_unit(enc, token) -> bytes:
    """The access unit the Python reference coder makes of the level tensors
    in a submitted frame's token (``H264Encoder._submit_cabac_intra`` /
    ``_submit_cabac_p``)."""
    import numpy as np

    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac
    from docker_nvidia_glx_desktop_tpu.ops import level_pack

    kind, payload = token[0], token[4]
    if kind == "cabac_intra":
        levels, _, _, _, qp, idr_pic_id = payload
        keys = [k for k, _, _ in level_pack.INTRA_KEYS] + [
            "pred_mode", "mb_i4", "i4_modes"]
        return h264_cabac.encode_intra_picture(
            {k: np.asarray(levels[k]) for k in keys}, qp=qp, frame_num=0,
            idr_pic_id=idr_pic_id, sps=enc._sps, pps=enc._pps,
            with_headers=True, qp_delta=qp - enc.qp,
            deblocking_idc=enc._deblock_idc, use_native=False)
    if kind != "cabac_p":
        raise ValueError(f"not a frame of the per-frame CABAC path: {kind!r}")
    _, out, _, _, _, mv, qp, frame_num = payload
    dense = {k: np.asarray(out[k]) for k, _, _ in level_pack.P_KEYS}
    dense["mv"] = np.asarray(mv, np.int32)
    return h264_cabac.encode_p_picture(
        dense, qp=qp, frame_num=frame_num, qp_delta=qp - enc.qp,
        deblocking_idc=enc._deblock_idc, use_native=False)


def differing_units(got: bytes, want: bytes) -> list:
    """Indices of the NAL units (a slice is a macroblock row) that differ."""
    from docker_nvidia_glx_desktop_tpu.web.mp4 import split_annexb

    a, b = split_annexb(got), split_annexb(want)
    return [i for i in range(max(len(a), len(b)))
            if i >= len(a) or i >= len(b) or a[i] != b[i]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--frames", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--geometry", default=None)
    ap.add_argument("--env", action="append", default=[],
                    help="KEY=VALUE over the configuration's environment "
                         "(ENCODER_CABAC_BINARIZE=host)")
    args = ap.parse_args(argv)

    from benchmark import run as bench

    spec = bench.resolve_cell(args.workload)
    os.environ.update(spec["config"]["env"])
    os.environ.update(kv.split("=", 1) for kv in args.env)
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
    scene = bench.build_scene(spec["traffic"], width, height, cfg.refresh,
                              args.seed)
    enc.request_keyframe()
    data, refs, frames = enc.headers(), [], []
    for c in range(args.frames):
        rgb = np.zeros((height, width, 3), np.uint8)
        scene.render(c, rgb)
        barcode.draw(rgb, c)
        t0 = time.monotonic()
        token = enc.encode_submit(rgb)
        want = reference_unit(enc, token)
        t1 = time.monotonic()
        ef = enc.encode_collect(token)
        data += ef.data
        refs.append(np.array(enc.export_state()["ref"][0][:height, :width]))
        frames.append({"frame": c, "keyframe": ef.keyframe,
                       "qp": token[4][-2], "bytes": len(ef.data),
                       "identical": ef.data == want})
        if ef.data != want:
            frames[-1]["differing_units"] = differing_units(ef.data, want)
        bench.note(f"{json.dumps(frames[-1])} (reference coder "
                   f"{t1 - t0:.1f} s)")
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "reference.h264")
        with open(path, "wb") as f:
            f.write(data)
        diffs = [int(np.abs(luma.astype(np.int16) - ref).max())
                 for luma, ref in zip(
                     check.decode_luma(path, width, height), refs)]
    result = {
        "workload": args.workload, "codec": name, "device": device,
        "geometry": [width, height], "frames": len(frames),
        "bytes_identical": sum(f["identical"] for f in frames),
        "pictures_decoded": len(diffs),
        "luma_maxdiff": max(diffs) if len(diffs) == len(refs) else 255,
        "qps": [f["qp"] for f in frames]}
    print(json.dumps(result), flush=True)
    return 0 if (result["bytes_identical"] == len(frames)
                 and result["luma_maxdiff"] == 0) else 1


if __name__ == "__main__":
    sys.exit(main())
