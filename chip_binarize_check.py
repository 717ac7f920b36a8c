#!/usr/bin/env python3
"""The entropy fronts' packers on the chip against XLA:CPU, by hand.

    chiprun --timeout 1800 -- python3 chip_binarize_check.py
    chiprun --timeout 1800 -- python3 chip_binarize_check.py --cavlc

On the TPU ``ops/cabac_binarize`` packs its record slots with the two Pallas
kernels of ``ops/cabac_pack``; everywhere else with the bitmerge hierarchy.
Tier-1 holds the kernels to that hierarchy in interpret mode at small sizes.
What only the chip can show is what XLA:TPU and Mosaic make of the programs
at 1920x1080 (PR 28: a fused reversed cumsum counted wrong there and nowhere
else) and at 3840x2160 (a new shape is a new compile).  So: one I picture
and the P picture after it of the benchmark's two traffics at qp 20, 32 and
44, twelve transport buffers from the chip at 1920x1088, and at 3840x2176
six (the desktop at qp 32, full damage at qp 20 and 44), each against
XLA:CPU's from the same level tensors, whole buffer, word for word; then the
programs' device time.  One JSON line a picture; the last line is
``ALL_IDENTICAL`` and the exit code 0 only if all eighteen are.
``--geometry WxH`` runs one size alone (with ``--cavlc`` too).

``--cavlc``: the same for the CAVLC programs' ``flat`` (``cavlc_device.
pack_frame``: the same two kernels on the TPU since PR 31), at 1920x1088 and
2560x1600, the I and the P picture of three pictures each, whole buffer,
byte for byte; then device time by inner scope of ``dngd.pack`` from a trace
of four calls of each program and of its bitmerge form compiled for the chip
(what ran there before PR 31; ``chiprun_out/cavlc_pack_<H>.xplane.pb``).
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

QPS = {(1920, 1088): {"desktop": (20, 32, 44), "fulldamage": (20, 32, 44)},
       (3840, 2176): {"desktop": (32,), "fulldamage": (20, 44)}}
P_KEYS = ("mv", "luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")
I_KEYS = ("luma_dc", "luma_ac", "cb_dc", "cb_ac", "cr_dc", "cr_ac",
          "pred_mode", "mb_i4", "i4_modes", "luma_i4")


def pictures(w, h, qps):
    """(name, binarize_p's arguments, binarize_intra's) from the served
    device stages: frame 200 as an I picture, frame 201 predicted from it."""
    for kind, seed in (("desktop", 3141592653), ("fulldamage", 2718281828)):
        traffic = json.loads(
            (ROOT / "benchmark" / "traffic" / f"{kind}.json").read_text())
        scene = build_scene(traffic, w, h, 60, seed)
        planes = []
        for c in (200, 201):
            rgb = np.zeros((h, w, 3), np.uint8)
            scene.render(c, rgb)
            planes.append(rgb_to_yuv420_host(rgb, h, w, float_fallback=True))
        for qp in qps[kind]:
            lv = h264_device.encode_intra_frame_yuv_dynqp(
                *map(jnp.asarray, planes[0]), np.int32(qp),
                i16_modes="auto", tune="off")
            out = h264_inter.encode_p_frame_dynqp(
                *map(jnp.asarray, planes[1]), jnp.array(lv["recon_y"]),
                jnp.array(lv["recon_cb"]), jnp.array(lv["recon_cr"]),
                np.int32(qp), tune="off")
            yield (f"{kind}.qp{qp}", [np.asarray(out[k]) for k in P_KEYS],
                   [np.asarray(lv[k]) for k in I_KEYS])


def _flat(kind, hv, hl, *levels):
    """``flat`` of an I or a P picture from its level tensors: the served
    programs' ``dngd.slots`` and ``dngd.pack``, without the stages in front."""
    from docker_nvidia_glx_desktop_tpu.ops import cavlc_device, cavlc_p_device

    none = jnp.zeros((1, 1), jnp.uint8)          # the recon rides through
    lv = dict(zip(P_KEYS if kind == "p" else I_KEYS, levels),
              recon_y=none, recon_cb=none, recon_cr=none)
    if kind == "p":
        return cavlc_p_device._finish_p(lv, hv, hl, slice_qp=26)[0]
    return cavlc_device._finish_cavlc(lv, hv, hl, False, 26)


def _program(name, kind):
    """A jit of ``_flat`` through a function of its own (JAX keeps a trace
    by the function), under a name the trace can be read by."""
    def fn(*a):
        return _flat(kind, *a)
    fn.__name__ = name
    return jax.jit(fn)


def _pack_scopes(path) -> dict:
    """{program: {scope: device self time, ms a call}} of a trace of
    ``_flat``: the ``dngd.`` stages, ``dngd.pack`` by the scope inside it."""
    from benchmark import stage_reduce

    planes = stage_reduce.load(str(path))
    dev = planes[min(p for p in planes
                     if p.startswith(stage_reduce.DEVICE_PREFIX))]
    calls = {}
    for name, *_ in dev.get(stage_reduce.MODULES_LINE, ()):
        name = stage_reduce.short_module(name)
        calls[name] = calls.get(name, 0) + 1
    out = {}
    for (_name, _s, _e, op_name), ps in stage_reduce.self_times(
            dev.get(stage_reduce.OPS_LINE, ())):
        parts = op_name.split("/")
        prog = "jit_" + parts[0][4:-1] if parts[0][:4] == "jit(" else "-"
        scope = stage_reduce.scope_of(op_name)
        if scope == "dngd.pack":
            inner = parts[parts.index(scope) + 1:-1]
            scope += "/" + (inner[0] if inner and inner[0][:4] != "jit("
                            else "(no inner scope)")
        by = out.setdefault(prog, {})
        by[scope] = by.get(scope, 0.0) + ps / 1e9 / calls.get(prog, 1)
    return {prog: {k: round(v, 4) for k, v in
                   sorted(by.items(), key=lambda kv: -kv[1])}
            for prog, by in out.items()}


def cavlc(sizes) -> int:
    """The CAVLC ``flat`` of the chip (the kernels) against XLA:CPU's (the
    bitmerge hierarchy) on the same levels, then where the time goes."""
    from docker_nvidia_glx_desktop_tpu.ops import cavlc_device

    cpu = jax.devices("cpu")[0]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    same = True
    for w, h in sizes:
        kinds = ("p", "intra")
        on_chip = {k: _program(f"flat_{k}", k) for k in kinds}
        before = {k: _program(f"flat_{k}_bitmerge", k) for k in kinds}
        on_cpu = {k: _program(f"flat_{k}_cpu", k) for k in kinds}
        pics = list(pictures(w, h, qps={"desktop": (20,),
                                        "fulldamage": (26, 38)}))
        timed = {}
        for name, p_args, i_args in pics:
            for kind, args in (("p", p_args), ("intra", i_args)):
                hv, hl = cavlc_device.slice_header_slots(
                    h // 16, w // 16, frame_num=5, deblocking_idc=2,
                    **({"slice_type": 5, "idr": False} if kind == "p"
                       else {}))
                got = np.asarray(on_chip[kind](hv, hl, *args))
                with mock.patch.object(jax, "default_backend",
                                       lambda: "cpu"):
                    want = np.asarray(on_cpu[kind](
                        *[jax.device_put(a, cpu) for a in (hv, hl, *args)]))
                meta = cavlc_device.FlatMeta(want, h // 16)
                if meta.overflow:       # the flag is all such a buffer says
                    got, want = got[:4], want[:4]
                differing = (int((got != want).sum())
                             if got.shape == want.shape else -1)
                same &= differing == 0
                print(json.dumps({
                    "picture": f"{w}x{h}.{name}", "kind": kind,
                    "identical": differing == 0,
                    "differing_bytes": differing,
                    "stream_words": meta.total_words,
                    "overflow": int(meta.overflow)}), flush=True)
                timed[kind] = [jnp.asarray(a) for a in (hv, hl, *args)]
        with mock.patch.object(jax, "default_backend", lambda: "cpu"):
            for kind, dev in timed.items():       # traced here, run below
                before[kind](*dev).block_until_ready()
        trace_dir = out_dir / f"cavlc_pack_{h}"
        jax.profiler.start_trace(str(trace_dir))
        for kind, dev in timed.items():
            for fn in (on_chip[kind], before[kind]):
                for _ in range(4):
                    fn(*dev).block_until_ready()
        jax.profiler.stop_trace()
        pb = next(trace_dir.rglob("*.xplane.pb"))
        keep = out_dir / f"cavlc_pack_{h}.xplane.pb"
        pb.replace(keep)
        print(json.dumps({"geometry": f"{w}x{h}",
                          "ms_per_call_by_scope": _pack_scopes(keep)}),
              flush=True)
    print("ALL_IDENTICAL" if same else "DIFFERENT", flush=True)
    return 0 if same else 1


def main() -> int:
    if jax.default_backend() != "tpu":
        print(f"no TPU: the default backend is {jax.default_backend()!r}")
        return 2
    sizes = ([(1920, 1088), (2560, 1600)] if "--cavlc" in sys.argv[1:]
             else list(QPS))
    if "--geometry" in sys.argv[1:]:
        w, h = sys.argv[sys.argv.index("--geometry") + 1].lower().split("x")
        sizes = [(int(w), int(h))]
    if "--cavlc" in sys.argv[1:]:
        return cavlc(sizes)
    cpu = jax.devices("cpu")[0]
    # functions of their own: JAX keeps a trace by the function, and the
    # chip's programs are traces of ``cb.binarize_*``
    on_cpu = {"p": jax.jit(lambda *a: cb.binarize_p.__wrapped__(*a)),
              "intra": jax.jit(lambda *a: cb.binarize_intra.__wrapped__(*a))}
    on_chip = {"p": cb.binarize_p, "intra": cb.binarize_intra}
    same = True
    for w, h in sizes:
        pics = list(pictures(w, h, QPS[w, h]))
        for name, p_args, i_args in pics:
            for kind, args in (("p", p_args), ("intra", i_args)):
                got = np.asarray(on_chip[kind](*args))
                with mock.patch.object(jax, "default_backend",
                                       lambda: "cpu"):
                    want = np.asarray(on_cpu[kind](
                        *[jax.device_put(a, cpu) for a in args]))
                differing = (int((got != want).sum())
                             if got.shape == want.shape else -1)
                same &= differing == 0
                print(json.dumps({
                    "picture": f"{w}x{h}.{name}", "kind": kind,
                    "identical": differing == 0,
                    "differing_words": differing,
                    "payload_words": int(want[2]),
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
                    "picture": f"{w}x{h}.{name}", "kind": kind,
                    "ms_per_call": (time.perf_counter() - t0) * 50}),
                    flush=True)
    print("ALL_IDENTICAL" if same else "DIFFERENT", flush=True)
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
