"""Benchmark: encoded frames/sec/chip at 1080p + p50 frame-encode latency.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
value = sustained 1080p encode fps on one chip for the best available codec
path; vs_baseline = fps / 60 (the 1080p60 real-time bar from BASELINE.md —
the reference publishes no numbers, so 60 fps real-time is the target).

The measured loop is the serving pipeline (web/session.py): pipelined
encode_submit/encode_collect so frame N+1's host->device upload overlaps
frame N's device compute + bitstream pull (SURVEY.md §3.2 double-buffering).
A per-stage breakdown (host color conversion / device submit / collect+
assemble) is reported so the remaining bottleneck is visible in the JSON.

``bench.py --serving-budget`` runs the LOOPBACK END-TO-END bench instead
(VERDICT r5 next-round item 6): synthetic X source -> StreamSession ->
muxer -> aiohttp server -> local WebSocket sink, through the production
code paths, and emits a ``serving_budget`` block — per-stage p50s from
the obs/budget ledger with the host<->device link cost measured
separately (devloop round-trip probe) and the BASELINE ladder SLO
verdicts.  ``--quick`` shrinks it to CPU-backend smoke geometry (CI).

``bench.py --chaos`` runs the CHAOS bench instead (web/chaos): every
registered fault point (resilience/faults) is injected against the live
loopback serving path and must recover — session alive, stream resumed
via IDR, recovery time bounded — and the SLO-driven degradation ladder
(resilience/degrade) must downshift under an injected sustained budget
breach and restore afterwards.

``bench.py --fleet`` runs the FLEET CHURN bench (web/fleetbench): N
batched sessions on a simulated v5e-8 (forced host-platform devices)
behind the fleet admission scheduler (fleet/), with a churning client
population — every join must be admitted, queued, or cleanly rejected
with ``retry_after_s`` (no silent hangs), ``mesh_chip_lost`` and
``ws_send_stall`` fire mid-churn, and the report carries sessions/chip
at SLO, p99 join latency and the rejection rate.  ``--quick`` shrinks
it to CI smoke geometry.
"""

from __future__ import annotations

import json
import os
import signal
import time


RESULT = {
    "metric": "h264_1080p_intra_encode_fps_per_chip",
    "value": 0.0,
    "unit": "frames/sec/chip",
    "vs_baseline": 0.0,
}


def _emit_and_exit(code: int = 0):
    print(json.dumps(RESULT), flush=True)
    os._exit(code)


def _watchdog(signum, frame):
    RESULT["note"] = "watchdog timeout (device unreachable or compile stuck)"
    _emit_and_exit(1)


def make_frames():
    import numpy as np

    # Desktop-like 1080p frame: gradients + flat window + text-ish noise.
    h, w = 1080, 1920
    r = np.random.default_rng(0)
    yy, xx = np.mgrid[0:h, 0:w]
    frame = np.stack(
        [(xx * 255 // w), (yy * 255 // h), ((xx + yy) * 255 // (h + w))],
        axis=-1).astype(np.uint8)
    frame[h // 4:h // 2, w // 4:w // 2] = (240, 240, 235)
    frame[h // 2:h // 2 + h // 8] = (
        r.integers(0, 2, size=(h // 8, w, 3)) * 200).astype(np.uint8)
    frames = [frame]
    for shift in (8, 16, 24):  # mild motion so DC prediction isn't static
        frames.append(np.ascontiguousarray(np.roll(frame, shift, axis=1)))
    return frames


_T0 = time.perf_counter()


def _force_cpu_mesh(ndev: int = 0) -> None:
    """Pin the CPU backend BEFORE the first jax import (CI smoke must
    not touch a chip — same rationale as tests/conftest.py) and
    optionally force an ``ndev``-device fake host mesh for multi-chip
    scenarios."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if ndev:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={ndev}"
            ).strip()


def _arm_watchdog(default_s: int) -> int:
    """Arm the SIGALRM hang watchdog at ``BENCH_TIMEOUT_S`` (or the
    entry point's default) and return the armed budget in seconds."""
    signal.signal(signal.SIGALRM, _watchdog)
    budget_s = int(os.environ.get("BENCH_TIMEOUT_S", str(default_s)))
    signal.alarm(budget_s)
    return budget_s


def main() -> None:
    _arm_watchdog(600)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()   # skip compiles a previous bench run already did

    frames = make_frames()
    h, w = frames[0].shape[:2]

    from docker_nvidia_glx_desktop_tpu.models import make_flagship_encoder

    enc, codec_name = make_flagship_encoder(w, h)
    RESULT["metric"] = f"{codec_name}_1080p_intra_encode_fps_per_chip"

    enc.encode(frames[0])  # compile + table warmup
    enc.encode(frames[1])

    # --- pipelined steady-state (the serving loop shape) ---
    # Depth 3: three frames in flight overlaps upload N+2, device compute
    # N+1, and the (submit-time-prefetched, models/h264._prefetch_host)
    # bitstream pull of N; async D2H prefetch lets in-flight pulls
    # overlap each other and the next dispatch.
    depth = int(os.environ.get("BENCH_PIPELINE_DEPTH", "3"))
    n = int(os.environ.get("BENCH_FRAMES", "60"))
    lat_ms = []
    submit_ms = []
    collect_ms = []
    nbytes = 0
    t_start = time.perf_counter()
    pending = []
    done = 0
    i = 0
    while done < n:
        while i < n and len(pending) < depth:
            t0 = time.perf_counter()
            pending.append(enc.encode_submit(frames[i % len(frames)]))
            submit_ms.append((time.perf_counter() - t0) * 1e3)
            i += 1
        t0 = time.perf_counter()
        ef = enc.encode_collect(pending.pop(0))
        collect_ms.append((time.perf_counter() - t0) * 1e3)
        lat_ms.append(ef.encode_ms)
        nbytes += len(ef.data)
        done += 1
    wall = time.perf_counter() - t_start

    lat_sorted = sorted(lat_ms)
    fps = n / wall

    def p(vals, q):
        s = sorted(vals)
        return round(s[min(len(s) - 1, int(q / 100 * len(s)))], 2)

    RESULT.update({
        "value": round(fps, 2),
        "vs_baseline": round(fps / 60.0, 4),
        "p50_encode_ms": p(lat_sorted, 50),
        "p90_encode_ms": p(lat_sorted, 90),
        "avg_kbits_per_frame": round(nbytes * 8 / n / 1e3, 1),
        "codec": codec_name,
        "backend": _backend_name(),
        "host_cores": os.cpu_count(),
        "pipelined": True,
        # submit/collect p50 show where the time goes
        "stage_ms": {
            # submit = host color conversion + async device dispatch;
            # collect = block on device + bitstream pull + Annex-B assembly.
            "submit_p50": p(submit_ms, 50),
            "collect_p50": p(collect_ms, 50),
            "frame_interval_p50": round(wall / n * 1e3, 2),
        },
    })

    # --- secondary: GOP mode (I + P with device entropy), time-gated ---
    budget_s = int(os.environ.get("BENCH_TIMEOUT_S", "600"))
    if time.perf_counter() - _T0 < budget_s * 0.5:
        try:
            from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

            genc = H264Encoder(frames[0].shape[1], frames[0].shape[0],
                               mode="cavlc", entropy="device",
                               host_color=True, gop=60)
            genc.encode(frames[0])          # IDR (compiled already)
            # Warm one full content cycle: P sizes vary across the bench
            # frames, so this compiles EVERY pull-prefix slice size the
            # decaying-max guess will use (a fresh slice length is a
            # fresh XLA executable; round 3 measured ~700 ms each, which
            # a 12-frame run absorbed as a 3.7x fps loss).
            for k in range(1, 1 + len(frames)):
                genc.encode(frames[k % len(frames)])
            ng = int(os.environ.get("BENCH_FRAMES_GOP", "36"))
            gbytes = 0
            gsub, gcol = [], []
            tg = time.perf_counter()
            gp = []
            gi = 0
            gdone = 0
            while gdone < ng:               # same pipeline shape as intra
                while gi < ng and len(gp) < depth:
                    ts = time.perf_counter()
                    gp.append(genc.encode_submit(
                        frames[(gi + 2) % len(frames)]))
                    gsub.append((time.perf_counter() - ts) * 1e3)
                    gi += 1
                ts = time.perf_counter()
                gbytes += len(genc.encode_collect(gp.pop(0)).data)
                gcol.append((time.perf_counter() - ts) * 1e3)
                gdone += 1
            gwall = time.perf_counter() - tg
            RESULT["gop"] = {
                "fps": round(ng / gwall, 2),
                "avg_kbits_per_frame": round(gbytes * 8 / ng / 1e3, 1),
                "stage_ms": {"submit_p50": p(gsub, 50),
                             "collect_p50": p(gcol, 50),
                             "frame_interval_p50": round(
                                 gwall / ng * 1e3, 2)},
            }
        except Exception as e:  # never fail the primary metric
            RESULT["gop"] = {"error": f"{type(e).__name__}: {e}"[:300]}

    # --- device-only steady state (compute-vs-link separation) ---
    # K encode steps inside one fori_loop on device, 4-byte pull, two trip
    # counts differenced so the fixed per-call cost cancels (ops/devloop).
    # This is the number that says whether the codec kernels clear
    # 16.7 ms/frame, independent of the host link.
    # Runs LAST: measure_steady_state's reps realize ~2x its budget_s, so
    # it must never gate the serving metrics out of the JSON.
    if time.perf_counter() - _T0 < budget_s * 0.6:
        # Intra and P are measured under SEPARATE try-blocks so a failure
        # in one path can never wipe the other's already-computed number
        # (round-3 postmortem: a P-path signature drift erased both).
        dev = {}
        RESULT["device_only"] = dev
        try:
            import jax
            import jax.numpy as jnp
            import numpy as np

            from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
            from docker_nvidia_glx_desktop_tpu.ops import devloop

            denc = (enc if getattr(enc, "host_color", False)
                    else H264Encoder(w, h, mode="cavlc", entropy="device",
                                     host_color=True))
            planes = denc._host_yuv420(frames[0])
            if planes is None:
                raise RuntimeError("cv2 unavailable")
            d = [jax.device_put(np.asarray(p)) for p in planes]
            hv, hl = denc._hdr_slots(0, 0)
            # each measure call's wall time is ~2x its budget_s (two reps
            # of k_hi plus the k_lo probes); split the remaining time so
            # both measurements fit inside the watchdog with margin
            remaining = budget_s - (time.perf_counter() - _T0)
            sub_budget = min(60.0, remaining * 0.18)
            qp = denc.qp
        except Exception as e:
            dev["error"] = f"{type(e).__name__}: {e}"
        else:
            try:
                intra = devloop.measure_steady_state(
                    lambda k: np.asarray(devloop.intra_loop(
                        *d, hv, hl, jnp.int32(k), qp)),
                    budget_s=sub_budget)
                dev["intra_fps"] = intra["fps"]
                dev["intra_step_ms"] = intra["step_ms"]
            except Exception as e:
                dev["intra_error"] = f"{type(e).__name__}: {e}"
            try:
                hvp, hlp = denc._p_hdr_slots(1, 0)
                # deblock=True inside the loop body: matches what serving
                # actually runs per P frame (models/h264._submit_p_device)
                pres = devloop.measure_steady_state(
                    lambda k: np.asarray(devloop.p_loop(
                        *d, *d, hvp, hlp, jnp.int32(k), qp, deblock=True)),
                    budget_s=sub_budget)
                dev["p_fps"] = pres["fps"]
                dev["p_step_ms"] = pres["step_ms"]
                dev["p_deblock_in_loop"] = True
            except Exception as e:
                dev["p_error"] = f"{type(e).__name__}: {e}"

    # --- CABAC path: device stage (transform+quant+compaction) + host
    # native coder (VERDICT r4 item 4: ENCODER_ENTROPY=cabac must be
    # serving-viable).  The two stages overlap in the pipelined serving
    # loop, so effective throughput = 1/max(device_step, host_code). ---
    if time.perf_counter() - _T0 < budget_s * 0.72:
        cab = {}
        RESULT["cabac"] = cab
        try:
            import jax
            import jax.numpy as jnp
            import numpy as np

            from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac
            from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
            from docker_nvidia_glx_desktop_tpu.ops import devloop

            cenc = H264Encoder(w, h, mode="cavlc", entropy="cabac",
                               host_color=True)
            planes = cenc._host_yuv420(frames[0])
            d = [jax.device_put(np.asarray(p)) for p in planes]
            remaining = budget_s - (time.perf_counter() - _T0)
            sub_budget = min(45.0, remaining * 0.15)
            qp = cenc.qp
            res = devloop.measure_steady_state(
                lambda k: np.asarray(devloop.cabac_intra_loop(
                    *d, jnp.int32(k), qp)),
                budget_s=sub_budget)
            cab["intra_device_step_ms"] = res["step_ms"]
            # host stages (level-pack decode + native CABAC coder) on
            # this content's actual levels.  Both are row-parallel C
            # (native/levelpack.cpp, native/cabac.cpp), so they scale
            # with host cores — record the core count for context.
            import os as _os

            from docker_nvidia_glx_desktop_tpu.ops import (h264_device,
                                                           level_pack)
            lv = h264_device.encode_intra_frame_yuv(*d, qp)
            buf = np.asarray(level_pack.pack_levels(
                lv, level_pack.INTRA_KEYS))
            cab["payload_mb"] = round(int(buf[2]) * 4 / 1e6, 2)
            nrows = int(buf[3])
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                level_pack.unpack_levels(buf, nrows, w // 16,
                                         level_pack.INTRA_KEYS)
                times.append((time.perf_counter() - t0) * 1e3)
            cab["host_unpack_ms"] = p(times, 50)
            lvn = {k: np.asarray(v) for k, v in lv.items()
                   if not k.startswith("recon")}
            times = []
            for _ in range(8):
                t0 = time.perf_counter()
                h264_cabac.encode_intra_picture(lvn, qp=qp)
                times.append((time.perf_counter() - t0) * 1e3)
            cab["intra_host_code_ms"] = p(times, 50)
            nrows = (h + 15) // 16           # MB-padded row count
            cab["rows"] = nrows
            cab["intra_host_code_ms_per_row"] = round(
                cab["intra_host_code_ms"] / nrows, 3)
            cab["host_cores"] = _os.cpu_count()
            bound = max(cab["intra_device_step_ms"],
                        cab["host_unpack_ms"] + cab["intra_host_code_ms"])
            cab["intra_pipelined_fps"] = round(1e3 / bound, 1)
            # --- round-6 split: device-side binarization + ctxIdx
            # (ops/cabac_binarize) -> host runs ONLY the arithmetic
            # engine.  Device stage re-measured with the binarize pack;
            # host stage = engine replay + NAL assembly, timed per
            # picture AND per row (the rows are pool-parallel, so the
            # per-row number plus host_cores makes any multi-core
            # throughput claim reproducible — VERDICT r5 item 5).
            try:
                from docker_nvidia_glx_desktop_tpu.ops import (
                    cabac_binarize)

                resb = devloop.measure_steady_state(
                    lambda k: np.asarray(devloop.cabac_intra_loop(
                        *d, jnp.int32(k), qp, binarize=True)),
                    budget_s=min(45.0, max(
                        10.0, (budget_s - (time.perf_counter() - _T0))
                        * 0.12)))
                cab["intra_device_binarize_step_ms"] = resb["step_ms"]
                binbuf = np.asarray(cabac_binarize.binarize_intra(
                    lv["luma_dc"], lv["luma_ac"], lv["cb_dc"],
                    lv["cb_ac"], lv["cr_dc"], lv["cr_ac"],
                    lv["pred_mode"], lv["mb_i4"], lv["i4_modes"],
                    lv["luma_i4"]))
                cab["binarize_payload_mb"] = round(
                    int(binbuf[2]) * 4 / 1e6, 2)
                times = []
                au0 = None
                for _ in range(8):
                    t0 = time.perf_counter()
                    au0 = h264_cabac.encode_intra_from_binstream(
                        binbuf, nr=int(binbuf[3]), nc_mb=w // 16, qp=qp)
                    times.append((time.perf_counter() - t0) * 1e3)
                if au0 is None:
                    raise RuntimeError("binarize overflow on bench frame")
                cab["intra_host_engine_ms"] = p(times, 50)
                cab["intra_host_engine_ms_per_row"] = round(
                    cab["intra_host_engine_ms"] / nrows, 3)
                boundb = max(cab["intra_device_binarize_step_ms"],
                             cab["intra_host_engine_ms"])
                cab["intra_binarize_pipelined_fps"] = round(
                    1e3 / boundb, 1)
                # calm desktop content: the bench frame's noise strip
                # is incompressible (94% of its intra bits, BASELINE
                # r3 note) and pins the engine's bin count far above
                # real desktop serving — measure the representative
                # point too, same geometry
                calm = frames[0].copy()
                calm[h // 2:h // 2 + h // 8] = (180, 180, 178)
                pc = cenc._host_yuv420(calm)
                dcal = [jax.device_put(np.asarray(p)) for p in pc]
                lvc = h264_device.encode_intra_frame_yuv(*dcal, qp)
                bufc = np.asarray(cabac_binarize.binarize_intra(
                    lvc["luma_dc"], lvc["luma_ac"], lvc["cb_dc"],
                    lvc["cb_ac"], lvc["cr_dc"], lvc["cr_ac"],
                    lvc["pred_mode"], lvc["mb_i4"], lvc["i4_modes"],
                    lvc["luma_i4"]))
                times = []
                auc = None
                for _ in range(8):
                    t0 = time.perf_counter()
                    auc = h264_cabac.encode_intra_from_binstream(
                        bufc, nr=int(bufc[3]), nc_mb=w // 16, qp=qp)
                    times.append((time.perf_counter() - t0) * 1e3)
                if auc is not None:
                    eng = p(times, 50)
                    cab["calm_desktop"] = {
                        "payload_mb": round(int(bufc[2]) * 4 / 1e6, 2),
                        "host_engine_ms": eng,
                        "host_engine_ms_per_row": round(eng / nrows, 3),
                        "pipelined_fps": round(1e3 / max(
                            cab["intra_device_binarize_step_ms"],
                            eng), 1),
                    }
                # the headline CABAC number is the better split; which
                # one won is recorded so the claim is reproducible
                if cab["intra_binarize_pipelined_fps"] > \
                        cab["intra_pipelined_fps"]:
                    cab["intra_pipelined_fps"] = \
                        cab["intra_binarize_pipelined_fps"]
                    cab["split"] = "device-binarize"
                else:
                    cab["split"] = "host-coder"
            except Exception as e:
                cab["binarize_error"] = f"{type(e).__name__}: {e}"[:300]
            # per-row CAVLC host-stage timing (the native C twin), for
            # the same reproducibility record
            try:
                from docker_nvidia_glx_desktop_tpu.native import (
                    lib as native_lib)

                if native_lib.has_cavlc():
                    lv_dc = {k: np.ascontiguousarray(v, np.int32)
                             for k, v in lvn.items()
                             if k in ("luma_dc", "luma_ac", "cb_dc",
                                      "cb_ac", "cr_dc", "cr_ac")}
                    times = []
                    for _ in range(5):
                        t0 = time.perf_counter()
                        native_lib.h264_encode_intra_picture(
                            lv_dc, frame_num=0, idr_pic_id=0)
                        times.append((time.perf_counter() - t0) * 1e3)
                    cab["cavlc_host_code_ms"] = p(times, 50)
                    cab["cavlc_host_code_ms_per_row"] = round(
                        cab["cavlc_host_code_ms"] / nrows, 3)
            except Exception as e:
                cab["cavlc_host_error"] = f"{type(e).__name__}: {e}"[:200]
            # P device stage (the GOP steady state: inter + deblock +
            # compaction, recon-chained)
            resp = devloop.measure_steady_state(
                lambda k: np.asarray(devloop.cabac_p_loop(
                    *d, *d, jnp.int32(k), qp)),
                budget_s=sub_budget)
            cab["p_device_step_ms"] = resp["step_ms"]
        except Exception as e:
            cab["error"] = f"{type(e).__name__}: {e}"[:300]

    # --- BASELINE config 4: 4K30 (3840x2160) device-only intra + P ---
    # (VERDICT r4 item 2: the 33 ms/frame bar must be MEASURED, not
    # extrapolated.)
    if time.perf_counter() - _T0 < budget_s * 0.8:
        fourk = {}
        RESULT["4k"] = fourk
        try:
            import jax
            import jax.numpy as jnp
            import numpy as np

            from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
            from docker_nvidia_glx_desktop_tpu.ops import devloop

            w4, h4 = 3840, 2160
            f4 = np.tile(frames[0], (2, 2, 1))[:h4, :w4]
            kenc = H264Encoder(w4, h4, mode="cavlc", entropy="device",
                               host_color=True)
            planes = kenc._host_yuv420(f4)
            if planes is None:
                raise RuntimeError("cv2 unavailable")
            d = [jax.device_put(np.asarray(pl)) for pl in planes]
            hv, hl = kenc._hdr_slots(0, 0)
            remaining = budget_s - (time.perf_counter() - _T0)
            sub_budget = min(45.0, remaining * 0.2)
            qp = kenc.qp
            try:
                r4 = devloop.measure_steady_state(
                    lambda k: np.asarray(devloop.intra_loop(
                        *d, hv, hl, jnp.int32(k), qp)),
                    budget_s=sub_budget)
                fourk["intra_step_ms"] = r4["step_ms"]
                fourk["intra_fps"] = r4["fps"]
            except Exception as e:
                fourk["intra_error"] = f"{type(e).__name__}: {e}"[:200]
            try:
                hvp, hlp = kenc._p_hdr_slots(1, 0)
                rp4 = devloop.measure_steady_state(
                    lambda k: np.asarray(devloop.p_loop(
                        *d, *d, hvp, hlp, jnp.int32(k), qp,
                        deblock=True)),
                    budget_s=sub_budget)
                fourk["p_step_ms"] = rp4["step_ms"]
                fourk["p_fps"] = rp4["fps"]
                fourk["meets_4k30"] = rp4["step_ms"] <= 33.3
            except Exception as e:
                fourk["p_error"] = f"{type(e).__name__}: {e}"[:200]
            # --- round-6 per-stage profile: the sub-pel lever measured
            # OLD vs NEW on this backend (alternate-line subpel SAD vs
            # the round-5 full-line re-rank), plus the ME/deblock/entropy
            # split wired into the serving-budget ledger as first-class
            # device spans (/debug/budget attribution).
            try:
                prof = {}
                fourk["profile"] = prof
                remaining = budget_s - (time.perf_counter() - _T0)
                pb = min(30.0, max(8.0, remaining * 0.04))
                me_new = devloop.measure_steady_state(
                    lambda k: np.asarray(devloop.inter_loop(
                        *d, *d, jnp.int32(k), qp)), budget_s=pb)
                me_old = devloop.measure_steady_state(
                    lambda k: np.asarray(devloop.inter_loop(
                        *d, *d, jnp.int32(k), qp, refine="full")),
                    budget_s=pb)
                db_new = devloop.measure_steady_state(
                    lambda k: np.asarray(devloop.deblock_loop(
                        *d, jnp.int32(k), qp)), budget_s=pb)
                prof["me_step_ms"] = me_new["step_ms"]
                prof["me_step_ms_r5_fullline"] = me_old["step_ms"]
                prof["me_improvement_pct"] = round(
                    (1 - me_new["step_ms"] / me_old["step_ms"]) * 100, 1)
                prof["deblock_step_ms"] = db_new["step_ms"]
                if "p_step_ms" in fourk:
                    entropy = max(
                        fourk["p_step_ms"] - prof["me_step_ms"]
                        - prof["deblock_step_ms"], 0.0)
                    prof["entropy_step_ms_est"] = round(entropy, 3)
                    from docker_nvidia_glx_desktop_tpu.obs.budget import (
                        LEDGER)
                    LEDGER.set_device_profile({
                        "device-me": prof["me_step_ms"],
                        "device-deblock": prof["deblock_step_ms"],
                        "device-entropy": prof["entropy_step_ms_est"],
                    })
                    fourk["budget_attribution"] = \
                        LEDGER.device_profile
            except Exception as e:
                fourk["profile_error"] = f"{type(e).__name__}: {e}"[:200]
            # --- ISSUE 12: 4k.sharded — ONE session's frame split
            # across the chips (parallel/batch spatial steps): per-
            # shard step ms, halo-exchange ms, stitch ms, effective
            # fps at 1/2/4 shards, old-vs-new.  Geometry 3840x2176
            # (the 2/4-splittable 4K-class padding; native 2160 = 135
            # MB rows shards 3/5-way under serving).  Needs >= 2
            # devices; single-device rounds use `bench.py --spatial`
            # (forced host mesh) for this block.
            try:
                ndev = len(jax.devices())
                if ndev >= 2:
                    deadline = _T0 + budget_s * 0.95
                    fourk["sharded"] = _spatial_sharded_block(
                        3840, 2176, (1, 2, 4), deadline)
                    if "p_step_ms" in fourk:
                        fourk["sharded"]["single_chip_2160_step_ms"] \
                            = fourk["p_step_ms"]
                else:
                    fourk["sharded"] = {
                        "skipped": "single-device backend; run "
                                   "bench.py --spatial for the "
                                   "forced-host-mesh block"}
            except Exception as e:
                fourk["sharded_error"] = f"{type(e).__name__}: {e}"[:200]
        except Exception as e:
            fourk["error"] = f"{type(e).__name__}: {e}"[:300]
    _stamp_obs()
    signal.alarm(0)
    _emit_and_exit(0)


def _backend_name() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:
        return "unknown"


def _stamp_obs(profile: bool = True, slo: bool = False) -> None:
    """Stamp RESULT with the same observability state ``/metrics`` and
    ``/debug/*`` serve (ISSUE 16 tentpole: BENCH lines are snapshots of
    the live registry/profiler, not parallel computations) plus full
    provenance — backend, versions, topology, env knobs, git SHA — so
    two BENCH files are mechanically diffable.  Defensive: a missing
    obs plane must never cost a bench its measured numbers."""
    try:
        from docker_nvidia_glx_desktop_tpu.obs import provenance as obspv
        RESULT["provenance"] = obspv.provenance_block()
    except Exception as e:
        RESULT["provenance"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    if profile:
        try:
            from docker_nvidia_glx_desktop_tpu.obs.profile import PROFILER
            RESULT["profile"] = PROFILER.snapshot()
        except Exception as e:
            RESULT["profile"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    if slo:
        try:
            from docker_nvidia_glx_desktop_tpu.obs import slo as obss
            RESULT["slo"] = obss.snapshot()
        except Exception as e:
            RESULT["slo"] = {"error": f"{type(e).__name__}: {e}"[:200]}


def _spatial_sharded_block(w: int, h: int, shards, deadline: float,
                           qp: int = 26, reps: int = 5) -> dict:
    """Measure the single-session SPATIAL-sharded P step (ISSUE 12):
    one frame's MB rows across 1/2/4 chips (parallel/batch.
    h264_spatial_step, deblock on — the serving shape).

    Per shard count: wall-clock per step (dispatch included — every
    count is measured the same way, so ratios are honest), host
    stitch/assembly ms, effective fps.  At the widest measured count
    the halo-exchange cost is isolated by differencing against the
    halo-off twin (edge replication instead of ppermute — identical
    compute shape), and both overheads are fed to the budget ledger
    (``dngd_halo_ms`` / ``dngd_stitch_ms``, /debug/budget rows) so a
    4K regression names the leaking sub-stage.

    ``deadline`` is an absolute perf_counter horizon: shard counts are
    dropped (recorded as skipped) rather than blowing the watchdog.
    """
    import jax
    import numpy as np

    from docker_nvidia_glx_desktop_tpu.bitstream import h264 as syn
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
    from docker_nvidia_glx_desktop_tpu.obs.budget import LEDGER
    from docker_nvidia_glx_desktop_tpu.ops import cavlc_device
    from docker_nvidia_glx_desktop_tpu.parallel import batch as pbatch

    block = {"geometry": f"{w}x{h}", "deblock": True,
             "host_cores": os.cpu_count(), "shards": {}}
    ndev = len(jax.devices())
    enc = H264Encoder(w, h, qp=qp, mode="cavlc", entropy="device",
                      host_color=True)
    r = np.random.default_rng(0)
    frame = np.stack(
        [(np.mgrid[0:h, 0:w][1] * 255 // w).astype(np.uint8)] * 3,
        axis=-1)
    frame[h // 2:h // 2 + h // 8] = (
        r.integers(0, 2, size=(h // 8, w, 3)) * 200).astype(np.uint8)
    planes = enc._host_yuv420(frame)
    if planes is None:
        raise RuntimeError("cv2 unavailable")
    y0, cb0, cr0 = (np.asarray(p) for p in planes)
    hv, hl = cavlc_device.slice_header_slots(
        h // 16, w // 16, frame_num=1, slice_type=5, idr=False,
        deblocking_idc=2)
    hv, hl = np.asarray(hv), np.asarray(hl)

    def run(step):
        """Warm once, then median wall of ``reps`` recon-chained calls
        (the collect forces the gathered flat to host each call)."""
        refs = (y0, cb0, cr0)
        out = step(y0, cb0, cr0, *refs, hv, hl)
        np.asarray(out[0])
        refs = (out[1], out[2], out[3])
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = step(y0, cb0, cr0, *refs, hv, hl)
            flat = np.asarray(out[0])
            refs = (out[1], out[2], out[3])
            times.append((time.perf_counter() - t0) * 1e3)
        return sorted(times)[len(times) // 2], flat

    shards = [n for n in shards]
    measured = {}
    for nx in shards:
        key = str(nx)
        if nx > ndev:
            block["shards"][key] = {"skipped": f"{ndev} devices"}
            continue
        if (h // 16) % nx or not pbatch.p_halo_feasible(h, nx):
            block["shards"][key] = {"skipped": "geometry infeasible"}
            continue
        if time.perf_counter() > deadline:
            block["shards"][key] = {"skipped": "time budget"}
            continue
        mesh = pbatch.make_spatial_mesh(nx)
        step, rows_l = pbatch.h264_spatial_step(mesh, h, w, qp=qp,
                                                deblock=True)
        step_ms, flat = run(step)
        t0 = time.perf_counter()
        metas = [cavlc_device.FlatMeta(flat[i], rows_l)
                 for i in range(nx)]
        au = b"".join(cavlc_device.assemble_annexb(
            flat[i], m, nal_type=syn.NAL_SLICE, ref_idc=2)
            for i, m in enumerate(metas))
        stitch_ms = (time.perf_counter() - t0) * 1e3
        measured[nx] = step_ms
        block["shards"][key] = {
            "p_step_ms": round(step_ms, 3),
            "effective_fps": round(1e3 / max(step_ms, 1e-6), 1),
            "stitch_ms": round(stitch_ms, 3),
            "au_bytes": len(au),
        }
        LEDGER.record_spatial(stitch_ms=stitch_ms)
    widest = max((nx for nx in measured if nx > 1), default=0)
    if widest and time.perf_counter() < deadline:
        # halo attribution: same program shape minus the ppermute
        mesh = pbatch.make_spatial_mesh(widest)
        step_nh, _ = pbatch.h264_spatial_step(mesh, h, w, qp=qp,
                                              deblock=True, halo=False)
        nh_ms, _ = run(step_nh)
        halo_ms = max(measured[widest] - nh_ms, 0.0)
        block["shards"][str(widest)]["halo_exchange_ms"] = \
            round(halo_ms, 3)
        block["halo_measured_at"] = widest
        LEDGER.record_spatial(halo_ms=halo_ms)
    if 1 in measured and widest:
        block["old_vs_new"] = {
            "single_chip_step_ms": round(measured[1], 3),
            f"sharded_{widest}x_step_ms": round(measured[widest], 3),
            "speedup": round(measured[1] / max(measured[widest], 1e-6),
                             2),
            # each chip computes rows/nx of the frame: on a REAL mesh
            # the sharded wall IS the per-chip wall; on a forced host
            # mesh the fake chips share the cores, so wall speedup is
            # bounded by the core count, not the shard count
            "per_chip_row_fraction": round(1.0 / widest, 3),
        }
        if (os.cpu_count() or 1) < widest:
            block["note"] = (
                f"{os.cpu_count()} host core(s) back {widest} fake "
                "chips: shard wall-clock serializes — per-chip gain "
                "needs cores >= shards or real devices")
    return block


def spatial_main(quick: bool = False) -> None:
    """Spatial-shard bench (``bench.py --spatial [--quick]``): the
    ISSUE 12 ``4k.sharded`` block on a forced host-device mesh, for
    rounds where the attached backend exposes a single device (the
    in-process main() bench records the block only when its own device
    pool allows).  Full mode measures 3840x2176 (the 4K bucket padded
    to a 2/4-splittable MB-row count; native 2160 = 135 rows shards
    3/5-way — feasible_spatial_shards picks that under serving);
    --quick shrinks to CI smoke geometry."""
    _force_cpu_mesh(4 if quick else 8)
    budget_s = _arm_watchdog(420 if quick else 1200)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    w, h = (512, 256) if quick else (3840, 2176)
    block = _spatial_sharded_block(
        w, h, (1, 2, 4), _T0 + budget_s * 0.85)
    RESULT["4k"] = {"sharded": block}
    ovn = block.get("old_vs_new", {})
    # headline = the widest sharded step that actually measured (the
    # halo-differencing pass may have been deadline-skipped)
    sharded_key = next((k for k in ovn if k.startswith("sharded_")),
                       None)
    RESULT.update({
        "metric": f"h264_spatial_sharded_p_step_ms_{w}x{h}",
        "value": ovn.get(sharded_key, 0.0) if sharded_key else 0.0,
        "unit": "ms",
        "vs_baseline": ovn.get("speedup", 0.0),
        "backend": _backend_name(),
        "host_cores": os.cpu_count(),
    })
    signal.alarm(0)
    _emit_and_exit(0)


def _trace_overhead_quick(w: int, h: int) -> dict:
    """A/B the serving loop with full journey tracing ON (marks +
    journeys + the serving-default 1-in-8 ack probe/echo) vs the obs
    master switches OFF.  Interleaved best-of-3 per arm over the
    loopback path; fps from the sink's interarrival p50 (a median,
    noise-resistant).  REFRESH is set far above the encode rate so both
    arms are encode-bound — a refresh-capped loop would hide any
    overhead."""
    import asyncio

    from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
    from docker_nvidia_glx_desktop_tpu.obs import trace as obst
    from docker_nvidia_glx_desktop_tpu.web import loopback

    cfg = loopback.serving_budget_config(w, h, 960)
    sample0 = obsj.sample_every()

    def run_once() -> float:
        block = asyncio.run(loopback.run_serving_budget(
            cfg, frames=80, probe_link=False, timeout_s=90.0))
        return float(block["sink"].get("fps") or 0.0)

    fps_on, fps_off = [], []
    try:
        obsj.sample_every(8)             # the serving default
        run_once()                       # warm (compile + caches)
        for _ in range(3):               # interleaved A/B
            obst.set_enabled(False)
            obsj.set_enabled(False)
            fps_off.append(run_once())
            obst.set_enabled(True)
            obsj.set_enabled(True)
            fps_on.append(run_once())
    finally:
        obst.set_enabled(True)
        obsj.set_enabled(True)
        obsj.sample_every(sample0)
    best_on, best_off = max(fps_on), max(fps_off)
    if best_on <= 0.0 or best_off <= 0.0:
        # a wedged sink is its own failure mode, not a trace overhead;
        # report it without tripping the percentage gate
        return {"fps_on": best_on, "fps_off": best_off, "pct": 0.0,
                "note": "sink produced no rate; overhead not measured"}
    pct = max(0.0, (best_off - best_on) / best_off * 100.0)
    return {"fps_on": best_on, "fps_off": best_off,
            "fps_on_runs": fps_on, "fps_off_runs": fps_off,
            "sample_every": 8, "pct": round(pct, 2)}


def _content_overhead_quick(w: int, h: int) -> dict:
    """A/B the serving loop with the content & quality telemetry plane
    ON (in-graph PSNR/damage/mode stats every frame, obs/content) vs
    its master switch OFF — same interleaved best-of-3 loopback
    protocol as :func:`_trace_overhead_quick`.  The plane's contract is
    free-and-inert: <1% fps (gated ABSOLUTE in quick_main) and zero
    extra dispatch crossings (asserted exactly against the baseline)."""
    import asyncio

    from docker_nvidia_glx_desktop_tpu.obs import content as obsc
    from docker_nvidia_glx_desktop_tpu.web import loopback

    cfg = loopback.serving_budget_config(w, h, 960)

    def run_once() -> float:
        block = asyncio.run(loopback.run_serving_budget(
            cfg, frames=80, probe_link=False, timeout_s=90.0))
        return float(block["sink"].get("fps") or 0.0)

    fps_on, fps_off = [], []
    try:
        obsc.set_enabled(True)
        run_once()                       # warm (stats-kernel compile)
        for _ in range(3):               # interleaved A/B
            obsc.set_enabled(False)
            fps_off.append(run_once())
            obsc.set_enabled(True)
            fps_on.append(run_once())
    finally:
        obsc.set_enabled(True)
    best_on, best_off = max(fps_on), max(fps_off)
    if best_on <= 0.0 or best_off <= 0.0:
        return {"fps_on": best_on, "fps_off": best_off, "pct": 0.0,
                "note": "sink produced no rate; overhead not measured"}
    pct = max(0.0, (best_off - best_on) / best_off * 100.0)
    return {"fps_on": best_on, "fps_off": best_off,
            "fps_on_runs": fps_on, "fps_off_runs": fps_off,
            "pct": round(pct, 2)}


def _damage_speedup_quick(w: int, h: int) -> dict:
    """Damage-driven encode acceptance (masked cavlc path): calm
    content (static desktop, one dirty MB walking per frame) must
    encode at least 3x faster than full-frame noise with the mask on —
    per-frame cost proportional to CHANGED pixels, not frame area.
    Three claims, measured on the real per-frame device path:

    - ``speedup``: noise-p50 / calm-p50 wall ms, mask ON (the content
      plane is switched OFF for the A/B so the measurement isolates
      encode work);
    - ``byte_identity``: a fully-damaged sequence through the mask
      must be byte-identical to the mask-off path (the 100%-damage
      worklist covers every row, so the masked program IS the full
      program);
    - crossings: mask ON must dispatch EXACTLY as often as mask OFF
      (the row worklist rides the existing submit crossing)."""
    import numpy as np

    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
    from docker_nvidia_glx_desktop_tpu.obs import content as obsc

    r = np.random.default_rng(20)
    base = r.integers(0, 256, (h, w, 3), np.uint8)
    n = 20
    calm = []
    for i in range(n):
        f = base.copy()
        x0 = (16 * i) % (w - 16)
        f[0:16, x0:x0 + 16] = r.integers(0, 256, (16, 16, 3), np.uint8)
        calm.append(f)
    noise = [r.integers(0, 256, (h, w, 3), np.uint8) for _ in range(n)]

    def mk(mask):
        return H264Encoder(w, h, mode="cavlc", entropy="device",
                           host_color=True, gop=600, damage_mask=mask)

    def run(enc, frames, measure=False):
        outs, t_ms = [], []
        c0 = getattr(enc, "_disp_count", 0)
        for f in frames:
            t0 = time.perf_counter()
            outs.append(enc.encode(f).data)
            t_ms.append((time.perf_counter() - t0) * 1e3)
        crossings = (getattr(enc, "_disp_count", 0) - c0) / len(frames)
        s = sorted(t_ms)
        return outs, (s[len(s) // 2] if measure else None), crossings

    was_on = obsc.enabled()
    try:
        obsc.set_enabled(False)
        e_on, e_off = mk(True), mk(False)
        run(e_on, calm)                       # compile IDR + buckets
        _, calm_ms, cr_on = run(e_on, calm[1:], measure=True)
        run(e_on, noise)                      # compile the full P step
        _, noise_ms, _ = run(e_on, noise[1:], measure=True)
        au_on, _, _ = run(mk(True), noise)    # 100%-damage identity
        au_off, _, _ = run(e_off, noise)
        run(e_off, calm)                      # crossings baseline arm
        _, _, cr_off = run(e_off, calm[1:])
    finally:
        obsc.set_enabled(was_on)
    return {
        "calm_p50_ms": round(calm_ms, 3),
        "noise_p50_ms": round(noise_ms, 3),
        "speedup": round(noise_ms / max(calm_ms, 1e-6), 2),
        "byte_identity_100pct": au_on == au_off,
        "crossings_on": round(cr_on, 3),
        "crossings_off": round(cr_off, 3),
    }


def quick_main() -> None:
    """CI perf-regression smoke (round-6 satellite): tiny geometry on
    the CPU backend, through the REAL pipelined serving loop + devloop.

    Measures submit/collect p50s of the pipelined GOP loop and the
    device p_step (RTT-cancelled), then compares each against
    ``deploy/bench_quick_baseline.json``: a stage p50 regressing more
    than 20% (plus a 2 ms absolute guard for shared-runner timer
    noise) exits non-zero.  After an INTENTIONAL perf change, refresh
    the baseline from the emitted ``stages`` block.

    Four forced host devices (not one) since round 12: the spatial-
    shard rung (``spatial2_p_step_ms``) needs a mesh to shard ONE
    session's frame across; the single-device stages run on device 0
    of the same pool (baseline refreshed under this config).
    """
    _force_cpu_mesh(4)
    _arm_watchdog(420)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
    from docker_nvidia_glx_desktop_tpu.obs.profile import PROFILER
    from docker_nvidia_glx_desktop_tpu.ops import devloop

    # the profiler ring covers exactly THIS run: the emitted profile
    # block (and the CI tripwire over it) must not inherit samples from
    # whatever imported bench before us
    PROFILER.clear()

    w, h = 256, 160
    r = np.random.default_rng(0)
    base = np.stack([
        (np.mgrid[0:h, 0:w][1] * 255 // w).astype(np.uint8)] * 3,
        axis=-1)
    base[h // 2:h // 2 + h // 8] = (
        r.integers(0, 2, size=(h // 8, w, 3)) * 200).astype(np.uint8)
    frames = [np.ascontiguousarray(np.roll(base, 4 * i, axis=1))
              for i in range(4)]

    def drive(enc, n):
        """Run n frames through the pipelined loop at the encoder's
        preferred depth; returns (submit_ms[], collect_ms[],
        dispatch_crossings_per_frame)."""
        depth = getattr(enc, "pipeline_depth", 2)
        sub_ms, col_ms = [], []
        c0 = getattr(enc, "_disp_count", 0)
        pend, i, done = [], 0, 0
        while done < n:
            while i < n and len(pend) < depth:
                t0 = time.perf_counter()
                pend.append(enc.encode_submit(frames[i % len(frames)]))
                sub_ms.append((time.perf_counter() - t0) * 1e3)
                i += 1
            t0 = time.perf_counter()
            enc.encode_collect(pend.pop(0))
            col_ms.append((time.perf_counter() - t0) * 1e3)
            done += 1
        crossings = (getattr(enc, "_disp_count", 0) - c0) / max(n, 1)
        return sub_ms, col_ms, round(crossings, 3)

    enc = H264Encoder(w, h, mode="cavlc", entropy="device",
                      host_color=True, gop=30)
    for f in frames:                     # compile IDR + P + pull sizes
        enc.encode(f)
    n = 40
    sub_ms, col_ms, crossings = drive(enc, n)

    # trace-overhead gate (ISSUE 13): full frame-journey tracing (every
    # frame minted/completed/probed/acked) must cost <2% fps vs tracing
    # disabled, measured A/B over the REAL loopback serving path at the
    # same geometry the stages above compiled.
    overhead = _trace_overhead_quick(w, h)

    # content-plane overhead gate (ISSUE 17): the in-graph PSNR/damage/
    # mode stats must cost <1% fps vs the plane's master switch off,
    # over the same loopback path
    content_overhead = _content_overhead_quick(w, h)

    # damage-driven encode gates (ISSUE 20): calm content through the
    # masked path must beat full-frame noise >=3x, 100% damage must be
    # byte-identical to mask-off, and the mask must not add crossings
    damage = _damage_speedup_quick(w, h)

    # GOP-chunk super-step (ROADMAP item 2): same loop through the
    # donated-ring chunk dispatch — submit p50 must collapse (staging is
    # host-only) and crossings/frame drop to ~(1 IDR + P-run/chunk)/GOP.
    chunk = 4
    enc_ss = H264Encoder(w, h, mode="cavlc", entropy="device",
                         host_color=True, gop=29,     # 28 P = 7 chunks
                         superstep_chunk=chunk)
    drive(enc_ss, 2 * chunk + 2)         # compile intra + chunk step
    ss_sub_ms, ss_col_ms, ss_crossings = drive(enc_ss, n)

    def p50(v):
        s = sorted(v)
        return round(s[len(s) // 2], 2)

    planes = enc._host_yuv420(frames[0])
    d = [jax.device_put(np.asarray(pl)) for pl in planes]
    hvp, hlp = enc._p_hdr_slots(1, 0)
    pres = devloop.measure_steady_state(
        lambda k: np.asarray(devloop.p_loop(
            *d, *d, hvp, hlp, jnp.int32(k), enc.qp, deblock=True)),
        budget_s=30.0)
    # XLA's static cost model for the same compiled P step (cache hit —
    # measure_steady_state just ran it): lands in the profile block's
    # cost_analysis so a wall-clock regression is separable from a
    # computation-got-bigger change
    devloop.capture_cost_analysis(
        "p_loop", devloop.p_loop, *d, *d, hvp, hlp, jnp.int32(4),
        qp=enc.qp, deblock=True)

    # spatial-shard rung (ISSUE 12): the single-session mesh-sharded P
    # step at 2 shards over the forced host mesh — wall-clock per call
    # (dispatch included), guarding the halo-exchange + sharded-entropy
    # path against regression like every other stage
    from docker_nvidia_glx_desktop_tpu.parallel import batch as pbatch

    sp_mesh = pbatch.make_spatial_mesh(2)
    sp_step, _sp_rows = pbatch.h264_spatial_step(
        sp_mesh, enc.pad_h, enc.pad_w, qp=enc.qp, deblock=True)
    hv_np, hl_np = np.asarray(hvp), np.asarray(hlp)
    y0, cb0, cr0 = (np.asarray(pl) for pl in planes)

    def sp_call(refs):
        out = sp_step(y0, cb0, cr0, *refs, hv_np, hl_np)
        np.asarray(out[0])
        return (out[1], out[2], out[3])

    sp_refs = sp_call((y0, cb0, cr0))          # compile + warm
    sp_ms = []
    for _ in range(7):
        t0 = time.perf_counter()
        sp_refs = sp_call(sp_refs)
        sp_ms.append((time.perf_counter() - t0) * 1e3)

    stages = {"submit_p50_ms": p50(sub_ms),
              "collect_p50_ms": p50(col_ms),
              "p_step_ms": pres["step_ms"],
              # dispatch stage (ROADMAP item 2 acceptance numbers):
              # Python->device crossings per frame on both paths plus
              # the super-step's stage p50s — the CI gate fails a >2x
              # crossings regression (per-frame dispatch sneaking back)
              "dispatch_crossings_per_frame": crossings,
              "superstep_submit_p50_ms": p50(ss_sub_ms),
              "superstep_collect_p50_ms": p50(ss_col_ms),
              "superstep_crossings_per_frame": ss_crossings,
              "spatial2_p_step_ms": p50(sp_ms),
              # gated ABSOLUTE (<2%), not against the baseline ms rule
              "trace_overhead_pct": overhead["pct"],
              # gated ABSOLUTE (<1%, ISSUE 17): content telemetry is
              # free-and-inert or it does not ship
              "content_overhead_pct": content_overhead["pct"],
              # gated ABSOLUTE (>=3x, ISSUE 20): bigger is better —
              # excluded from the ms regression rule below
              "damage_speedup": damage["speedup"],
              "damage_crossings_per_frame": damage["crossings_on"]}
    RESULT.update({
        "metric": f"bench_quick_stage_p50s_{w}x{h}",
        "value": pres["step_ms"],
        "unit": "ms",
        "vs_baseline": 0.0,
        "backend": _backend_name(),
        "host_cores": os.cpu_count(),
        "stages": stages,
        "trace_overhead": overhead,
        "content_overhead": content_overhead,
        "damage": damage,
        "superstep": {
            "chunk": chunk,
            "submit_speedup": round(
                p50(sub_ms) / max(p50(ss_sub_ms), 1e-3), 2),
            "crossings_ratio": round(
                crossings / max(ss_crossings, 1e-3), 2),
        },
    })
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "deploy", "bench_quick_baseline.json")
    rc = 0
    if os.path.exists(base_path):
        with open(base_path) as f:
            baseline = json.load(f)
        regressions = {}
        for k, got in stages.items():
            if k == "trace_overhead_pct":
                # absolute gate (ISSUE 13): full journey tracing must
                # cost <2% fps vs tracing disabled — the baseline
                # records the measured value for trend, the limit is
                # the contract itself
                if got > 2.0:
                    regressions[k] = {"got_pct": got, "limit_pct": 2.0}
                continue
            if k == "content_overhead_pct":
                # absolute gate (ISSUE 17): the content plane must cost
                # <1% fps vs its master switch off
                if got > 1.0:
                    regressions[k] = {"got_pct": got, "limit_pct": 1.0}
                continue
            if k == "damage_speedup":
                # absolute gate (ISSUE 20), bigger is better — the ms
                # rule below would fail an IMPROVEMENT
                if got < 3.0:
                    regressions[k] = {
                        "got": got, "limit": 3.0,
                        "rule": "calm encode >= 3x noise, mask on"}
                continue
            want = baseline.get("stages", {}).get(k)
            if want is None:
                continue
            if k.endswith("crossings_per_frame"):
                # dispatch-regression gate: >2x crossings per frame =
                # per-frame Python dispatch crept back into a batched
                # path (+0.1 absolute: integer-ish counts, no timer
                # noise to forgive)
                limit = want * 2.0 + 0.1
                if got > limit:
                    regressions[k] = {"baseline": want, "got": got,
                                      "limit": round(limit, 3)}
                continue
            limit = want * 1.2 + 2.0
            if got > limit:
                regressions[k] = {"baseline_ms": want, "got_ms": got,
                                  "limit_ms": round(limit, 2)}
        # content-telemetry inertness (ISSUE 17): the whole stage run
        # above executed with the plane ON (its default), so crossings
        # per frame must be EXACTLY the baseline — the stats jit rides
        # existing submit events; any extra crossing is a wiring bug,
        # not timer noise, hence no tolerance
        for k in ("dispatch_crossings_per_frame",
                  "superstep_crossings_per_frame"):
            want = baseline.get("stages", {}).get(k)
            if want is not None and stages.get(k) != want:
                regressions[f"{k}_with_content_telemetry"] = {
                    "baseline": want, "got": stages.get(k),
                    "rule": "exact equality with content telemetry on"}
        # damage-driven encode invariants (ISSUE 20): the masked path
        # must be invisible in bytes (100% damage == mask off) and in
        # dispatch shape (mask on/off crossings exactly equal) — both
        # are wiring claims, not timing, hence no tolerance
        if not damage["byte_identity_100pct"]:
            regressions["damage_byte_identity"] = {
                "rule": "mask on at 100% damage == mask-off bytes"}
        if damage["crossings_on"] != damage["crossings_off"]:
            regressions["damage_crossings_mask_on_vs_off"] = {
                "mask_on": damage["crossings_on"],
                "mask_off": damage["crossings_off"],
                "rule": "exact equality, mask on vs off"}
        RESULT["baseline_stages"] = baseline.get("stages")
        RESULT["regressions"] = regressions
        rc = 1 if regressions else 0
        RESULT["vs_baseline"] = round(
            baseline.get("stages", {}).get("p_step_ms", 0.0)
            / max(pres["step_ms"], 1e-9), 4)
    # built-in regression verdict over the profiler's per-stage p50s
    # (steady-state samples only — a cold-cache CI run recompiling must
    # not fail the latency gate).  The same diff runs artifact-side in
    # CI via `python -m ...obs.provenance --tripwire`.
    _stamp_obs(slo=True)
    if os.path.exists(base_path):
        try:
            from docker_nvidia_glx_desktop_tpu.obs.provenance import (
                stage_p50_tripwire)
            verdict = stage_p50_tripwire(
                RESULT.get("profile", {}).get("stage_p50_ms_steady", {}),
                baseline.get("profile_stage_p50_ms", {}))
            RESULT["profile_tripwire"] = verdict
            if not verdict["ok"]:
                rc = 1
        except Exception as e:
            RESULT["profile_tripwire"] = {
                "error": f"{type(e).__name__}: {e}"[:200]}
    signal.alarm(0)
    _emit_and_exit(rc)


def serving_budget_main(quick: bool = False) -> None:
    """Loopback end-to-end serving bench (web/loopback).

    Emits ONE JSON line whose ``serving_budget`` block carries per-stage
    p50s (link separated) + SLO verdicts; the headline value is the
    link-separated compute p50 at the measured geometry, vs_baseline =
    budget / p50 (>= 1.0 means the active ladder rung is met).
    """
    import asyncio

    if quick:
        # CI smoke: CPU backend, tiny geometry, no device needed.
        _force_cpu_mesh()
    budget_s = _arm_watchdog(300 if quick else 600)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
    from docker_nvidia_glx_desktop_tpu.web import loopback

    if quick:
        width, height, fps, frames = 128, 96, 30, 12
    else:
        width, height, fps, frames = 1920, 1080, 60, 120
    # dense ack sampling for the bench: the g2g percentiles need a
    # population, not the serving default's 1-in-8 trickle
    obsj.sample_every(2)
    cfg = loopback.serving_budget_config(width, height, fps)
    block = asyncio.run(loopback.run_serving_budget(
        cfg, frames=frames, timeout_s=budget_s * 0.8))

    active = next((r for r in block["rungs"].values() if r["active"]),
                  None)
    p50 = block.get("compute_p50_ms", 0.0)
    g2g = block.get("glass_to_glass", {})
    drops = block.get("trace_dropped_total", 0)
    RESULT.update({
        "metric": f"serving_budget_e2e_compute_p50_ms_"
                  f"{width}x{height}",
        "value": p50,
        "unit": "ms",
        "vs_baseline": (round(active["budget_ms"] / p50, 4)
                        if active and p50 > 0 else 0.0),
        "backend": _backend_name(),
        "serving_budget": block,
        # headline glass-to-glass view (full detail in the block):
        # delivery share = the client-closure stage's cut of the e2e
        "glass_to_glass": {
            "p50_ms": g2g.get("p50_ms"),
            "p95_ms": g2g.get("p95_ms"),
            "closed": g2g.get("closed"),
            "by_method": g2g.get("by_method"),
            "delivery_p50_ms": g2g.get("delivery_p50_ms"),
            "delivery_share_pct": (
                round(g2g["delivery_p50_ms"] / g2g["p50_ms"] * 100.0, 1)
                if g2g.get("delivery_p50_ms") and g2g.get("p50_ms")
                else None),
            "methodology": g2g.get("methodology"),
        },
        # silent-trace-loss gate (ISSUE 13 satellite): ring overwrite /
        # listener-flush loss over the bench window must be ZERO
        "trace_dropped_total": drops,
    })
    _stamp_obs(slo=True)
    signal.alarm(0)
    # closed journeys are required in quick mode (the loopback sink
    # acks every probe — zero closures means the probe/ack path broke)
    g2g_ok = not quick or bool(g2g.get("closed"))
    _emit_and_exit(0 if drops == 0 and g2g_ok else 1)


def chaos_main(quick: bool = False, continuity_only: bool = False,
               skip_continuity: bool = False) -> None:
    """Chaos-mode loopback bench (web/chaos): inject every registered
    fault point against the live serving path and assert bounded
    recovery; drive the degradation ladder down and back up, and run
    the session-continuity scenarios (device_preempt: checkpoint
    restore with SSRC/seq continuity; mesh_chip_lost: N->N-1 elastic
    re-bucket).

    Emits ONE JSON line whose ``chaos`` block carries per-fault
    {fired, recovered, recovery_ms}; value = faults recovered,
    vs_baseline = recovered/total (1.0 = every registered fault
    survived).  Exits non-zero when any recovery failed.
    ``--continuity-only`` restricts the run to the two continuity
    scenarios (the CI continuity-smoke step).
    """
    import asyncio

    if quick:
        # Forced host-platform devices give the mesh-failover scenario
        # a multi-chip mesh to lose a chip from.
        _force_cpu_mesh(4)
    budget_s = _arm_watchdog(420 if quick else 900)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    from docker_nvidia_glx_desktop_tpu.web import chaos

    report = asyncio.run(chaos.run_chaos(
        quick=quick, timeout_s=budget_s * 0.8,
        continuity=not skip_continuity,
        continuity_only=continuity_only))
    scored = dict(report["faults"])
    scored.update({k: v for k, v in report["continuity"].items()
                   if v.get("recovered") is not None})
    total = len(scored)
    recovered = sum(1 for f in scored.values() if f.get("recovered"))
    RESULT.update({
        "metric": ("continuity_faults_recovered" if continuity_only
                   else "chaos_faults_recovered"),
        "value": recovered,
        "unit": "faults",
        "vs_baseline": round(recovered / max(total, 1), 4),
        "backend": _backend_name(),
        "chaos": report,
    })
    signal.alarm(0)
    _emit_and_exit(0 if report.get("all_recovered") else 1)


def fleet_main(quick: bool = False) -> None:
    """Fleet churn bench (web/fleetbench) on a SIMULATED v5e-8.

    Always runs on forced host-platform devices (8, or 4 under --quick)
    so the admission/placement control plane is exercised against a real
    multi-chip mesh without touching shared TPU hardware — the same
    fake-backend strategy the chaos bench and the test suite use.  Emits
    ONE JSON line whose ``fleet`` block carries the churn report; value
    = peak sessions/chip, vs_baseline = 1 - rejection_rate.  Exits
    non-zero when any zero-crash/no-silent-hang invariant failed.
    """
    import asyncio

    _force_cpu_mesh(4 if quick else 8)
    budget_s = _arm_watchdog(420 if quick else 1800)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    from docker_nvidia_glx_desktop_tpu.web import fleetbench

    report = asyncio.run(fleetbench.run_fleet(
        quick=quick, timeout_s=budget_s * 0.8))
    RESULT.update({
        "metric": "fleet_peak_sessions_per_chip",
        "value": report["sessions_per_chip"],
        "unit": "sessions/chip",
        "vs_baseline": round(1.0 - report["rejection_rate"], 4),
        "backend": _backend_name(),
        "fleet": report,
    })
    signal.alarm(0)
    _emit_and_exit(0 if report.get("ok") else 1)


def _bdrate_frames(kind: str, w: int, h: int, n: int):
    """Synthetic content classes for the BD-rate harness (seeded, so
    every run scores the same pixels).

    - ``desktop_text``: window chrome + black-on-white glyph rows that
      scroll two px/frame (the remote-desktop workload: hard edges,
      skip-heavy background).
    - ``natural_gradients``: smooth low-frequency gradients with a slow
      global drift (flat-energy content where coarse quantization bands
      visibly — the AQ map's best case).
    - ``panning_motion``: band-limited texture panning 4 px/frame (ME
      stress: every MB moves, lambda MV costs dominate).
    - ``scrolling``: a static document vertically panned 8 px/frame
      (the scroll-wheel workload the damage mask prices: every MB row
      changes each frame — full damage — but the content is pure
      translation, so ME + skip should carry almost all of it; the
      class pins the mask's worst case in the BD-rate ledger).
    """
    import numpy as np

    r = np.random.default_rng(42)
    if kind == "desktop_text":
        # white page with CONTINUOUS micro-grain (real captures dither;
        # a 3-valued synthetic image resonates with the quant lattice at
        # specific QPs and makes PSNR(qp) non-monotonic), flat margins
        # (the AQ map's negative side needs genuinely flat MBs to act
        # on), and a scrolling text column.
        grain = r.normal(0.0, 2.0, (h, w, 1))
        base = np.clip(246.0 + grain, 0, 255).astype(np.uint8).repeat(3, 2)
        base[: h // 8] = (58, 62, 70)                 # title bar
        base[: h // 8] += r.integers(0, 3, (h // 8, w, 3)).astype(np.uint8)
        glyphs = (r.random((h, w)) < 0.18) & (
            (np.arange(h) % 8 < 5)[:, None])          # text lines
        glyphs[:, : w // 4] = False                   # left margin
        glyphs[:, w - w // 6:] = False                # right margin
        pane = slice(h // 8 + 8, h - 8)
        frames = []
        for i in range(n):
            f = base.copy()
            g = np.roll(glyphs, -2 * i, axis=0)       # scrolling pane
            f[pane][g[pane]] = (16, 16, 20)
            frames.append(f)
        return frames
    if kind == "natural_gradients":
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float64)
        frames = []
        for i in range(n):
            ph = i * 0.35
            g = (110 + 70 * np.sin(xx / w * 3.1 + ph)
                 + 55 * np.cos(yy / h * 2.3 + 0.4 * ph))
            f = np.stack([g, g * 0.92 + 12, g * 0.85 + 25], axis=-1)
            frames.append(np.clip(f, 0, 255).astype(np.uint8))
        return frames
    if kind == "panning_motion":
        # band-limited texture: blurred noise, tiled wide enough to pan
        big = r.integers(0, 256, (h, w * 2, 3)).astype(np.float64)
        k = 7
        kern = np.ones(k) / k
        for ax in (0, 1):
            big = np.apply_along_axis(
                lambda v: np.convolve(v, kern, mode="same"), ax, big)
        big = np.clip((big - big.mean()) * 3.0 + 128, 0, 255)
        big = big.astype(np.uint8)
        return [np.ascontiguousarray(big[:, 4 * i:4 * i + w])
                for i in range(n)]
    if kind == "scrolling":
        # a tall "document": white page, ruled text bands, occasional
        # figures (gray boxes) — scrolled vertically 8 px/frame.  Mild
        # grain keeps PSNR(qp) monotonic, same reasoning as
        # desktop_text.
        doc_h = h + 8 * n
        grain = r.normal(0.0, 2.0, (doc_h, w, 1))
        doc = np.clip(248.0 + grain, 0, 255).astype(np.uint8).repeat(3, 2)
        text = (r.random((doc_h, w)) < 0.16) & (
            (np.arange(doc_h) % 10 < 6)[:, None])
        text[:, : w // 6] = False
        text[:, w - w // 8:] = False
        doc[text] = (20, 20, 24)
        for fy in range(0, doc_h - h // 3, max(doc_h // 5, 1)):
            doc[fy:fy + h // 6, w // 3:w - w // 3] = (
                r.integers(96, 160, (1, 1, 3)).astype(np.uint8))
        return [np.ascontiguousarray(doc[8 * i:8 * i + h])
                for i in range(n)]
    raise ValueError(kind)


def _bd_rate_pct(rate_ref, psnr_ref, rate_new, psnr_new) -> float:
    """Bjontegaard rate delta of NEW vs REF, percent (negative = NEW
    spends fewer bits at equal quality).  Cubic log-rate fit over the
    overlapping PSNR interval — the standard BD-rate construction."""
    import numpy as np

    la, lb = np.log10(rate_ref), np.log10(rate_new)
    pa = np.polyfit(psnr_ref, la, 3)
    pb = np.polyfit(psnr_new, lb, 3)
    lo = max(np.min(psnr_ref), np.min(psnr_new))
    hi = min(np.max(psnr_ref), np.max(psnr_new))
    if hi - lo < 1e-6:
        return 0.0
    ia, ib = np.polyint(pa), np.polyint(pb)
    span = lambda p: np.polyval(p, hi) - np.polyval(p, lo)  # noqa: E731
    avg = (span(ib) - span(ia)) / (hi - lo)
    return float((10.0 ** avg - 1.0) * 100.0)


def bdrate_main(quick: bool = False) -> None:
    """BD-rate harness (ISSUE 15 / ROADMAP item 4): prove ENCODER_TUNE.

    Encodes four synthetic content classes over a 4-point QP ladder at
    three tuning tiers — ``off`` (the fixed-heuristic pre-tune encoder),
    ``hq_noaq`` (Lagrangian mode/MV/skip decisions at uniform slice qp),
    ``hq`` (lambda decisions + per-MB adaptive quantization) — and
    reports the Bjontegaard rate delta of each tuned tier against
    ``off``, the per-tier device step cost (the <=1.5x CI gate), and the
    obs/procstats CPU-energy proxy per frame.  Distortion is luma PSNR
    of the encoder's device reconstruction vs the device-converted
    source plane: one more device-side reduction (ops/aq.psnr_planes),
    no golden decoder in the rate loop.

    Scope note: ``keep_recon`` (the PSNR hook) disables the super-step
    ring, so this harness drives the per-frame path and the measured hq
    tier is AQ + lambda decisions WITHOUT the 1-frame lookahead bias —
    that rides only chunked serving, where its conformance is pinned by
    tests/test_tune.py's chunked-hq decode test.  The BD-rate numbers
    are therefore a floor for the chunked configuration, not a claim
    about the lookahead.

    Exit code: non-zero if tune=hq LOSES to tune=off (positive BD-rate)
    on any content class — the CI bdrate-smoke gate.
    """
    _force_cpu_mesh()
    _arm_watchdog(420 if quick else 1800)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    import numpy as np

    from docker_nvidia_glx_desktop_tpu.models.h264 import (
        H264Encoder, _yuv_stage)
    from docker_nvidia_glx_desktop_tpu.obs import budget as obs_budget
    from docker_nvidia_glx_desktop_tpu.obs import procstats
    from docker_nvidia_glx_desktop_tpu.ops import aq
    import jax.numpy as jnp

    w, h = (192, 112) if quick else (448, 256)
    n = 9 if quick else 12              # serving GOPs are long (gop=60):
    qps = (26, 30, 34, 38)              # give the I/P split room to pay
    tiers = ("off", "hq_noaq", "hq")
    classes = ("desktop_text", "natural_gradients", "panning_motion",
               "scrolling")

    def run_tier(frames, tier: str, qp: int, warm_only: bool = False):
        enc = H264Encoder(w, h, qp=qp, mode="cavlc", entropy="device",
                          gop=len(frames), keep_recon=True, tune=tier)
        if warm_only:                   # compile the I + P programs only
            for f in frames[:2]:
                enc.encode(f)
            return None
        src_y = [np.asarray(_yuv_stage(jnp.asarray(f), enc.pad_h,
                                       enc.pad_w)[0]) for f in frames]
        bits = 0
        psnrs = []
        times = []
        meter = procstats.CpuEnergyMeter()
        for i, f in enumerate(frames):
            t0 = time.perf_counter()
            ef = enc.encode(f)
            dt = (time.perf_counter() - t0) * 1e3
            if i:                       # steady-state P frames only
                times.append(dt)
            bits += len(ef.data) * 8
            psnrs.append(aq.psnr_planes(enc.last_recon[0], src_y[i]))
        # publish = read + the per-tune-tier /metrics energy gauges, so
        # the same numbers are scrapeable outside the bench (ISSUE 16)
        energy = meter.publish(frames=len(frames), tune=tier)
        return {
            "bits": bits,
            "psnr_y": round(float(np.mean(psnrs)), 3),
            "p_step_ms_p50": round(float(np.median(times)), 3),
            "energy": energy,
        }

    block = {
        "geometry": f"{w}x{h}",
        "frames": n,
        "qps": list(qps),
        "backend": _backend_name(),
        "quick": bool(quick),
        "classes": {},
    }
    worst_gain = None
    best_gain = None
    max_cost = 0.0
    for cls in classes:
        frames = _bdrate_frames(cls, w, h, n)
        per_tier = {t: {"rate_bits": [], "psnr_y": [],
                        "p_step_ms_p50": [], "joules_per_frame_proxy": []}
                    for t in tiers}
        for qp in qps:
            for t in tiers:
                # warm the compile before the timed pass so step cost
                # measures the step, not XLA
                run_tier(frames, t, qp, warm_only=True)
                r = run_tier(frames, t, qp)
                per_tier[t]["rate_bits"].append(r["bits"])
                per_tier[t]["psnr_y"].append(r["psnr_y"])
                per_tier[t]["p_step_ms_p50"].append(r["p_step_ms_p50"])
                per_tier[t]["joules_per_frame_proxy"].append(
                    r["energy"]["joules_per_frame_proxy"])
        crow = {"tiers": per_tier}
        off = per_tier["off"]
        for t in ("hq_noaq", "hq"):
            bd = _bd_rate_pct(off["rate_bits"], off["psnr_y"],
                              per_tier[t]["rate_bits"],
                              per_tier[t]["psnr_y"])
            crow[f"bd_rate_{t}_vs_off_pct"] = round(bd, 2)
        cost = (float(np.median(per_tier["hq"]["p_step_ms_p50"]))
                / max(float(np.median(off["p_step_ms_p50"])), 1e-9))
        crow["step_cost_ratio_hq"] = round(cost, 3)
        block["classes"][cls] = crow
        gain = -crow["bd_rate_hq_vs_off_pct"]
        worst_gain = gain if worst_gain is None else min(worst_gain, gain)
        best_gain = gain if best_gain is None else max(best_gain, gain)
        max_cost = max(max_cost, cost)
    block["best_gain_pct"] = round(best_gain, 2)
    block["worst_gain_pct"] = round(worst_gain, 2)
    block["max_step_cost_ratio"] = round(max_cost, 3)
    # the gates: hq must never LOSE to off; the acceptance headline is
    # >=15% on at least one class at <=1.5x device step cost
    block["ok"] = bool(worst_gain >= 0.0 and max_cost <= 1.5)
    block["meets_issue15"] = bool(best_gain >= 15.0 and max_cost <= 1.5)

    obs_budget.record_bdrate(block)
    RESULT.update({
        "metric": "h264_hq_best_bdrate_gain_pct",
        "value": block["best_gain_pct"],
        "unit": "pct_fewer_bits_at_equal_psnr",
        "vs_baseline": round(block["best_gain_pct"] / 15.0, 3),
        "backend": _backend_name(),
        "bdrate": block,
    })
    _stamp_obs(profile=False)
    signal.alarm(0)
    _emit_and_exit(0 if block["ok"] else 1)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--serving-budget", action="store_true",
                    help="loopback end-to-end serving bench "
                         "(serving_budget block + SLO verdicts)")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injection chaos bench: every registered "
                         "fault point must recover; degradation ladder "
                         "downshifts and restores")
    ap.add_argument("--continuity-only", action="store_true",
                    help="with --chaos: run only the session-continuity "
                         "scenarios (device_preempt checkpoint restore, "
                         "mesh_chip_lost elastic re-bucket)")
    ap.add_argument("--skip-continuity", action="store_true",
                    help="with --chaos: skip the continuity scenarios "
                         "(the pre-existing chaos-smoke scope)")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet churn bench: admission scheduler + "
                         "queue backpressure + churn-safe placement on "
                         "a simulated v5e-8 (chip loss + ws stalls "
                         "mid-churn)")
    ap.add_argument("--spatial", action="store_true",
                    help="spatial-shard bench: ONE session's 4K-class "
                         "frame split across a forced host-device "
                         "mesh (per-shard step/halo/stitch ms, "
                         "effective fps at 1/2/4 shards)")
    ap.add_argument("--bdrate", action="store_true",
                    help="BD-rate harness: tune=off/hq_noaq/hq over a "
                         "QP ladder on four synthetic content classes; "
                         "fails if hq loses to off on any class")
    ap.add_argument("--quick", action="store_true",
                    help="smoke geometry on the CPU backend (CI)")
    args = ap.parse_args()
    if args.bdrate:
        bdrate_main(quick=args.quick)
    elif args.spatial:
        spatial_main(quick=args.quick)
    elif args.fleet:
        fleet_main(quick=args.quick)
    elif args.chaos:
        chaos_main(quick=args.quick, continuity_only=args.continuity_only,
                   skip_continuity=args.skip_continuity)
    elif args.serving_budget:
        serving_budget_main(quick=args.quick)
    elif args.quick:
        # bare --quick: the CI perf-regression smoke (stage-budget
        # assertions against deploy/bench_quick_baseline.json)
        quick_main()
    else:
        main()
