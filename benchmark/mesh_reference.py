#!/usr/bin/env python3
"""The spatially sharded encoder against its plain references, at the timed
size, by hand (not inside a run: two encoders' programs are compiled).

    chiprun --chips 4 --timeout 3000 -- python3 benchmark/mesh_reference.py \
        --workload desk2160-cabac-mesh4.fulldamage --seed <n>

One IDR and the P frames after it (``--frames``, 8) of the cell's traffic go
through the encoder the cell serves (``make_encoder`` under the
configuration's environment, as ``run.py`` builds it: one session's
macroblock rows over the mesh) and, in the same process, through a ONE-chip
encoder of the same coded picture: the control configuration's environment
(the cell's with ``ENCODER_SPATIAL_SHARDS`` off, which is ``desk2160-cabac``'s)
built with the mesh's row alignment, and fed the sharded encoder's qp
sequence.  Neither reference knows the mesh: the one-chip served path, which
``cabac_reference.py`` holds to the pure-Python CABAC coder, must emit the
same bytes for every access unit; the whole stream then goes through cv2's
ffmpeg, whose luma (the display's size: the padding rows are cropped) must be
the sharded encoder's own gathered reference picture after every frame.  No
tolerance: every number compared is exact.  The last line of standard output
is one JSON object; exit code 0 only if every frame is identical on both
counts.  ``--rehearse --geometry WxH`` runs it on XLA:CPU (with at least as
many host devices as the cell has chips), for the tests; ``--fault swap_rows``
exchanges two shards' rows of the sharded encoder's reference picture after
the first P frame (the IDR's, at the controller's first qp, can be flat), for
the test that this check can fail.
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

SHARDS_KNOB = "ENCODER_SPATIAL_SHARDS"


def swap_shard_rows(enc) -> None:
    """The fault: the first two shards' rows of the sharded reference
    pictures change places (what a stitch or a gather in the wrong order
    would leave behind)."""
    import numpy as np

    rows = 16 * enc._sp_rows_local()
    planes = []
    for plane, step in zip(enc.export_state()["ref"], (rows, rows // 2,
                                                       rows // 2)):
        plane = np.array(plane)
        first = plane[:step].copy()
        plane[:step] = plane[step:2 * step]
        plane[step:2 * step] = first
        planes.append(plane)
    enc._ref = tuple(planes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--frames", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--geometry", default=None)
    ap.add_argument("--fault", choices=("swap_rows",), default=None)
    args = ap.parse_args(argv)

    from benchmark import run as bench

    spec = bench.resolve_cell(args.workload)
    env = dict(spec["config"]["env"])
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
    mesh_enc, name = make_encoder(cfg, width, height)
    shards = mesh_enc._spatial_nx
    if shards < 2:
        raise SystemExit(f"{args.workload}: the encoder did not shard "
                         f"({SHARDS_KNOB}={env.get(SHARDS_KNOB)!r}, "
                         f"{device['count']} device(s))")
    os.environ[SHARDS_KNOB] = "0"          # the control's environment
    one_enc, _ = make_encoder(from_env(), width, height,
                              row_align=mesh_enc.row_align)
    assert one_enc._spatial_nx == 1
    assert (one_enc.pad_h, one_enc.pad_w) == (mesh_enc.pad_h, mesh_enc.pad_w)
    bench.note(f"{width}x{height} coded as {mesh_enc.pad_w}x{mesh_enc.pad_h}: "
               f"{shards} shards of {mesh_enc._sp_rows_local()} rows against "
               "one chip")
    scene = bench.build_scene(spec["traffic"], width, height, cfg.refresh,
                              args.seed)
    mesh_enc.request_keyframe()
    one_enc.request_keyframe()
    data, refs, frames = mesh_enc.headers(), [], []
    for c in range(args.frames):
        rgb = np.zeros((height, width, 3), np.uint8)
        scene.render(c, rgb)
        barcode.draw(rgb, c)
        token = mesh_enc.encode_submit(rgb)
        qp = token[4][2]
        ef = mesh_enc.encode_collect(token)
        one_enc._forced_qp = qp            # the sharded encoder's sequence
        want = one_enc.encode(rgb)
        data += ef.data
        refs.append(np.array(
            mesh_enc.export_state()["ref"][0][:height, :width]))
        frames.append({"frame": c, "keyframe": ef.keyframe, "qp": qp,
                       "bytes": len(ef.data),
                       "identical": (ef.data == want.data
                                     and ef.keyframe == want.keyframe)})
        if not frames[-1]["identical"]:
            from benchmark.cabac_reference import differing_units
            frames[-1]["differing_units"] = differing_units(
                ef.data, want.data)[:16]
        bench.note(json.dumps(frames[-1]))
        if args.fault == "swap_rows" and c == 1:
            swap_shard_rows(mesh_enc)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "reference.h264")
        with open(path, "wb") as f:
            f.write(data)
        decoded = list(check.decode_luma(path, width, height))
    diffs = [int(np.abs(luma.astype(np.int16) - ref).max())
             for luma, ref in zip(decoded, refs)]
    result = {
        "workload": args.workload, "codec": name, "device": device,
        "geometry": [width, height],
        "coded": [mesh_enc.pad_w, mesh_enc.pad_h], "shards": shards,
        "frames": len(frames),
        "bytes_identical": sum(f["identical"] for f in frames),
        "pictures_decoded": len(diffs),
        "decoded_size": list(decoded[0].shape[::-1]) if decoded else None,
        "luma_maxdiff": max(diffs) if len(diffs) == len(refs) else 255,
        "qps": [f["qp"] for f in frames]}
    print(json.dumps(result), flush=True)
    return 0 if (result["bytes_identical"] == len(frames)
                 and result["luma_maxdiff"] == 0) else 1


if __name__ == "__main__":
    sys.exit(main())
