#!/usr/bin/env python3
"""The two CABAC binarize programs on the chip against XLA:CPU, by hand.

    chiprun --timeout 1800 -- python3 chip_binarize_check.py

On the TPU ``ops/cabac_binarize`` packs its record slots with the two Pallas
kernels of ``ops/cabac_pack``; everywhere else with the bitmerge hierarchy.
Tier-1 holds the kernels to that hierarchy in interpret mode at small sizes.
What only the chip can show is what XLA:TPU and Mosaic make of the programs
at 1920x1080 (PR 28: a fused reversed cumsum counted wrong there and nowhere
else).  So: one I picture and the P picture after it of the benchmark's two
traffics at qp 20, 32 and 44, twelve transport buffers from the chip, each
against XLA:CPU's from the same level tensors, whole buffer, word for word;
then the programs' device time.  One JSON line a picture; the last line is
``ALL_IDENTICAL`` and the exit code 0 only if all twelve are.
"""

import json
import pathlib
import sys
import time
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.run import build_scene
from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize as cb
from docker_nvidia_glx_desktop_tpu.ops import h264_device, h264_inter
from docker_nvidia_glx_desktop_tpu.utils.hostcolor import rgb_to_yuv420_host

W, H = 1920, 1088
P_KEYS = ("mv", "luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")
I_KEYS = ("luma_dc", "luma_ac", "cb_dc", "cb_ac", "cr_dc", "cr_ac",
          "pred_mode", "mb_i4", "i4_modes", "luma_i4")


def pictures():
    """(name, binarize_p's arguments, binarize_intra's) from the served
    device stages: frame 200 as an I picture, frame 201 predicted from it."""
    for kind, seed in (("desktop", 3141592653), ("fulldamage", 2718281828)):
        traffic = json.loads(
            (ROOT / "benchmark" / "traffic" / f"{kind}.json").read_text())
        scene = build_scene(traffic, W, H, 60, seed)
        planes = []
        for c in (200, 201):
            rgb = np.zeros((H, W, 3), np.uint8)
            scene.render(c, rgb)
            planes.append(rgb_to_yuv420_host(rgb, H, W, float_fallback=True))
        for qp in (20, 32, 44):
            lv = h264_device.encode_intra_frame_yuv_dynqp(
                *map(jnp.asarray, planes[0]), np.int32(qp),
                i16_modes="auto", tune="off")
            out = h264_inter.encode_p_frame_dynqp(
                *map(jnp.asarray, planes[1]), jnp.array(lv["recon_y"]),
                jnp.array(lv["recon_cb"]), jnp.array(lv["recon_cr"]),
                np.int32(qp), tune="off")
            yield (f"{kind}.qp{qp}", [np.asarray(out[k]) for k in P_KEYS],
                   [np.asarray(lv[k]) for k in I_KEYS])


def main() -> int:
    if jax.default_backend() != "tpu":
        print(f"no TPU: the default backend is {jax.default_backend()!r}")
        return 2
    cpu = jax.devices("cpu")[0]
    # functions of their own: JAX keeps a trace by the function, and the
    # chip's programs are traces of ``cb.binarize_*``
    on_cpu = {"p": jax.jit(lambda *a: cb.binarize_p.__wrapped__(*a)),
              "intra": jax.jit(lambda *a: cb.binarize_intra.__wrapped__(*a))}
    on_chip = {"p": cb.binarize_p, "intra": cb.binarize_intra}
    pics = list(pictures())
    same = True
    for name, p_args, i_args in pics:
        for kind, args in (("p", p_args), ("intra", i_args)):
            got = np.asarray(on_chip[kind](*args))
            with mock.patch.object(jax, "default_backend", lambda: "cpu"):
                want = np.asarray(on_cpu[kind](
                    *[jax.device_put(a, cpu) for a in args]))
            differing = (int((got != want).sum())
                         if got.shape == want.shape else -1)
            same &= differing == 0
            print(json.dumps({
                "picture": name, "kind": kind, "identical": differing == 0,
                "differing_words": differing, "payload_words": int(want[2]),
                "overflow": int(want[1])}), flush=True)
    for name, p_args, i_args in pics[1::3]:
        for kind, args in (("p", p_args), ("intra", i_args)):
            dev = [jnp.asarray(a) for a in args]
            on_chip[kind](*dev).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(20):
                out = on_chip[kind](*dev)
            out.block_until_ready()
            print(json.dumps({
                "picture": name, "kind": kind,
                "ms_per_call": (time.perf_counter() - t0) * 50}), flush=True)
    print("ALL_IDENTICAL" if same else "DIFFERENT", flush=True)
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
