#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the chip.

``python chip_smoke.py`` (one chip) starts the real server entry point,
``python -m docker_nvidia_glx_desktop_tpu.web.server_main``, as the ONE
chip-holding child at its shipped defaults and 1920x1080@60 — synthetic
source (no X socket), basic auth on, ``tpuh264enc`` with device CAVLC,
GOP 60, CBR 8000 kbps, deblock on, ENCODER_PREWARM on — and then speaks
to it the way a browser does: ``GET /`` without and with credentials,
``/ws`` (once 30 frames are encoded: the cold compile sits in front of
them) for the hello, the init segment and 150 media fragments, cv2 as
the independent decoder, ``/metrics`` for in-graph PSNR, compile-cache
counters, encode failures and the device-entropy overflow count over
that stream window, and finally the SIGTERM a pod deletion sends.  Any step that fails, any timeout, any child death
is a non-zero exit with the child's log tail; nothing carries on past a
failure.

This parent never imports JAX: a chip belongs to one process, and the
child needs it.  The device it reports is the one the CHILD logged at the
top of ``main()`` (``device: {...}``).

``python chip_smoke.py --chips 4`` runs, in this one process and with no
server child, ``parallel.batch.dryrun_full_geometry(4)``: four 1080p
sessions on a (4,1) mesh, then P frames on (2,2) with the reference halo
crossing chips, every access unit byte-identical to the single-device
encoder — that path and what it is compared with, nothing else.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``;
on any failure ``"ok"`` is false and the exit code is 1.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import pathlib
import re
import secrets
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
SERVER_MODULE = "docker_nvidia_glx_desktop_tpu.web.server_main"

WIDTH, HEIGHT, FPS = 1920, 1080, 60
N_FRAGMENTS = 150             # IDR, a run of P, the next IDR (GOP 60), more P
HEALTHY_TIMEOUT_S = 300.0     # jax import + chip attach + server bind
COMPILE_TIMEOUT_S = 600.0     # the cold compile of the served programs
                              # (1.5-3 min) in front of the first frames
WARM_FRAMES = 30              # frames encoded before the client joins
STREAM_TIMEOUT_S = 120.0      # 150 fragments from a warm session
EXIT_MARGIN_S = 45.0          # on top of DNGD_DRAIN_GRACE_S
DRAIN_GRACE_S = 8.0           # utils/config.py default (DNGD_DRAIN_GRACE_S)
# The device coder's static per-MB cap (2048 bits) overflows now and then on
# the synthetic source's noise band: whenever the host loop hiccups, the
# wall-clock source moves the band further than the motion search reaches,
# and at the fine end of the rate ladder the residual no longer fits.  Seen
# on the chip in steady state: 0, 0, 2 of 177, 0 frames (PERF.md, PR 22).
# Such a frame is entropy-coded on the host from the same levels — valid,
# counted, logged.  What must not pass is a device coder that gives way as a
# rule, so the smoke allows a small share and prints the count.
MAX_OVERFLOW_SHARE = 0.05
PSNR_FLOOR_DB = 30.0          # obs/content tier floor (DNGD_CONTENT_PSNR_FLOOR)

_DEVICE_RE = re.compile(r"device: (\{.*\})\s*$", re.M)


class SmokeFailure(Exception):
    """A step of the smoke did not hold.  Never caught to carry on."""


def note(msg: str) -> None:
    print(msg, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(base: dict, port: int, passwd: str, width: int, height: int,
              fps: int, platform: str) -> dict:
    """The server child's environment: shipped defaults everywhere except
    the geometry asked for, the listen socket, the password — and
    ``JAX_PLATFORMS``, which makes JAX refuse to start without that
    platform instead of falling back (and resolves ring donation on for
    ``tpu``).  ``JAX_COMPILATION_CACHE_DIR`` is inherited untouched."""
    env = dict(base)
    env.pop("DISPLAY", None)          # no X socket: the synthetic source
    env.update({
        "JAX_PLATFORMS": platform,
        "PYTHONPATH": os.pathsep.join(
            p for p in (str(ROOT), base.get("PYTHONPATH", "")) if p),
        "LISTEN_ADDR": "127.0.0.1",
        "LISTEN_PORT": str(port),
        "SIZEW": str(width), "SIZEH": str(height), "REFRESH": str(fps),
        "ENABLE_BASIC_AUTH": "true",
        "PASSWD": passwd,
    })
    env.pop("BASIC_AUTH_PASSWORD", None)
    return env


def spawn_server(env: dict, log_path: pathlib.Path,
                 argv: list | None = None) -> subprocess.Popen:
    argv = argv or [sys.executable, "-m", SERVER_MODULE]
    return subprocess.Popen(argv, env=env, cwd=str(ROOT),
                            stdout=log_path.open("wb"),
                            stderr=subprocess.STDOUT)


def log_tail(log_path: pathlib.Path, n: int = 6000) -> str:
    try:
        return log_path.read_text(errors="replace")[-n:]
    except OSError:
        return "<no child log>"


def _alive(proc: subprocess.Popen, what: str) -> None:
    rc = proc.poll()
    if rc is not None:
        raise SmokeFailure(f"server child died (rc={rc}) while {what}")


async def wait_healthy(http, port: int, proc: subprocess.Popen,
                       timeout_s: float) -> float:
    """Poll the auth-exempt /healthz until 200; seconds it took."""
    import aiohttp

    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        _alive(proc, "booting")
        try:
            async with http.get(f"http://127.0.0.1:{port}/healthz") as r:
                if r.status == 200:
                    return time.monotonic() - t0
        except aiohttp.ClientError:
            pass
        await asyncio.sleep(0.5)
    raise SmokeFailure(f"/healthz not 200 within {timeout_s:.0f} s")


def read_device(log_path: pathlib.Path) -> dict:
    """The ``device: {...}`` block the child logs at the top of main()."""
    m = _DEVICE_RE.search(log_tail(log_path, 1 << 20))
    if m is None:
        raise SmokeFailure("child logged no 'device:' line")
    return json.loads(m.group(1))


def device_summary(device: dict) -> dict:
    """``{"platform", "kind", "count"}`` as the last line carries them."""
    kinds = device.get("device_kinds") or {}
    return {"platform": device.get("backend"),
            "kind": next(iter(kinds), None),
            "count": device.get("device_count")}


def gate(device: dict, want_platform: str, want_count: int) -> bool:
    """The chip cannot be silently absent: the platform the child served
    from and the number of devices it saw must be the ones asked for, the
    P stages must donate their reference ring there, and the native
    entropy library must be what backs the host coder."""
    d = device_summary(device)
    return (d["platform"] == want_platform and d["count"] == want_count
            and d["kind"] is not None
            and bool(device.get("ring_donate")) == (want_platform != "cpu")
            and device.get("native_entropy") is True)


async def step_auth(http, port: int, passwd: str) -> None:
    """``GET /`` is 401 without credentials, 401 with a wrong password
    and 200 with the right one."""
    import aiohttp

    url = f"http://127.0.0.1:{port}/"
    async with http.get(url) as r:
        if r.status != 401:
            raise SmokeFailure(f"GET / without credentials: {r.status}")
    async with http.get(url, auth=aiohttp.BasicAuth("u", passwd + "x")) as r:
        if r.status != 401:
            raise SmokeFailure(f"GET / with a wrong password: {r.status}")
    async with http.get(url, auth=aiohttp.BasicAuth("u", passwd)) as r:
        if r.status != 200:
            raise SmokeFailure(f"GET / with the password: {r.status}")


def fragment_is_idr(frag: bytes) -> bool:
    """moof+mdat with one AVCC sample: any NAL of type 5 is an IDR."""
    moof_len = struct.unpack(">I", frag[:4])[0]
    if frag[4:8] != b"moof" or frag[moof_len + 4:moof_len + 8] != b"mdat":
        raise SmokeFailure("media message is not moof+mdat")
    pos = moof_len + 8
    while pos + 4 <= len(frag):
        n = struct.unpack(">I", frag[pos:pos + 4])[0]
        if frag[pos + 4] & 0x1F == 5:
            return True
        pos += 4 + n
    return False


async def step_stream(http, port: int, passwd: str, proc: subprocess.Popen,
                      n_fragments: int, width: int, height: int,
                      timeout_s: float) -> dict:
    """Join /ws like the web client: hello JSON, the init segment, then
    media fragments (fprobes acked).  Returns the bytes and arrivals."""
    import aiohttp

    ws = await http.ws_connect(f"http://127.0.0.1:{port}/ws",
                               auth=aiohttp.BasicAuth("u", passwd),
                               max_msg_size=0)
    hello, init, frags, arrivals = None, None, [], []
    deadline = time.monotonic() + timeout_s
    try:
        while len(frags) < n_fragments:
            _alive(proc, "streaming")
            left = deadline - time.monotonic()
            if left <= 0:
                raise SmokeFailure(
                    f"{len(frags)}/{n_fragments} fragments in "
                    f"{timeout_s:.0f} s")
            try:
                msg = await ws.receive(timeout=min(left, 5.0))
            except asyncio.TimeoutError:
                continue
            if msg.type == aiohttp.WSMsgType.TEXT:
                ctrl = json.loads(msg.data)
                if ctrl.get("type") == "hello" and hello is None:
                    hello = ctrl
                elif ctrl.get("type") == "fprobe":
                    await ws.send_json({"type": "ack", "id": ctrl["id"],
                                        "recv_ts": time.perf_counter()})
                elif ctrl.get("type") in ("evicted", "draining", "error",
                                          "busy"):
                    raise SmokeFailure(f"server sent {ctrl}")
            elif msg.type == aiohttp.WSMsgType.BINARY:
                if init is None:
                    init = msg.data
                else:
                    frags.append(msg.data)
                    arrivals.append(time.perf_counter())
            else:
                raise SmokeFailure(f"websocket ended: {msg.type}")
    finally:
        await ws.close()
    if hello is None or (hello.get("width"), hello.get("height")) != \
            (width, height) or not str(hello.get("codec")).startswith("h264"):
        raise SmokeFailure(f"bad hello: {hello}")
    if not init or init[4:8] != b"ftyp":
        raise SmokeFailure("first binary message is not an init segment")
    idr_at = [i for i, f in enumerate(frags) if fragment_is_idr(f)]
    if not idr_at or idr_at[0] != 0:
        raise SmokeFailure(f"stream did not open on an IDR (IDRs at {idr_at})")
    p_runs = [b - a - 1 for a, b in zip(idr_at, idr_at[1:])]
    if not p_runs or max(p_runs) < 10:
        raise SmokeFailure(
            f"no IDR / run of P / next IDR in {len(frags)} fragments "
            f"(IDRs at {idr_at})")
    gaps = sorted((b - a) * 1e3 for a, b in zip(arrivals, arrivals[1:]))
    return {"hello": hello, "init": init, "frags": frags, "idr_at": idr_at,
            "bytes": sum(len(f) for f in frags),
            "interarrival_p50_ms": gaps[len(gaps) // 2]}


def step_decode(path: pathlib.Path, init: bytes, frags: list, width: int,
                height: int) -> int:
    """Init segment + fragments in one file decode with cv2 (ffmpeg, the
    independent decoder) to one ``height x width`` frame per fragment."""
    import cv2

    path.write_bytes(init + b"".join(frags))
    cap = cv2.VideoCapture(str(path))
    n = 0
    try:
        while True:
            ok, img = cap.read()
            if not ok:
                break
            if img.shape[:2] != (height, width):
                raise SmokeFailure(f"decoded frame {n} is {img.shape}")
            n += 1
    finally:
        cap.release()
    if n != len(frags):
        raise SmokeFailure(f"cv2 decoded {n} frames of {len(frags)} fragments")
    return n


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {family: [values]} (labels folded away)."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, val = line.rpartition(" ")
        try:
            out.setdefault(name.split("{", 1)[0], []).append(float(val))
        except ValueError:
            continue
    return out


async def wait_flowing(http, port: int, proc: subprocess.Popen,
                       min_frames: int, timeout_s: float) -> dict:
    """Block until the session has encoded ``min_frames`` frames (it
    encodes with or without a client) and return /metrics as of then.
    The cold compile of the served programs sits in front of the first
    frames, and the synthetic source draws from the wall clock: the
    first P frame after a compile stall sees a band of per-pixel noise
    the motion search cannot find again, which is not what the stream
    window should be judged on."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        _alive(proc, "compiling the served programs")
        m = await step_metrics(http, port)
        if m["frames_encoded"] >= min_frames:
            return m
        await asyncio.sleep(1.0)
    raise SmokeFailure(f"fewer than {min_frames} frames encoded in "
                       f"{timeout_s:.0f} s")


async def sample_psnr(http, port: int, samples: list) -> None:
    """Poll the in-graph PSNR gauge while the stream runs (cancelled by
    the caller).  The gauge holds the LAST sampled frame, and under CBR
    the rate controller walks qp 20..44 inside every GOP on the
    synthetic source, so one reading says where the ladder stood, not
    whether the recon is sound; a run of readings does."""
    while True:
        async with http.get(f"http://127.0.0.1:{port}/metrics") as r:
            vals = parse_metrics(await r.text()).get("dngd_content_psnr_db")
        if vals and max(vals) >= 0:          # -1 = nothing sampled yet
            samples.append(max(vals))
        await asyncio.sleep(0.25)


async def step_metrics(http, port: int) -> dict:
    """What /metrics shows right now: the compile-cache counters,
    device-entropy overflow fallbacks, encode failures and frames (a
    family that is missing is a failure here; :func:`sample_psnr` reads
    the in-graph PSNR)."""
    async with http.get(f"http://127.0.0.1:{port}/metrics") as r:
        if r.status != 200:
            raise SmokeFailure(f"/metrics: {r.status}")
        m = parse_metrics(await r.text())
    for fam in ("jax_compile_cache_hits_total",
                "jax_compile_cache_requests_total",
                "jax_compile_cache_misses_total",
                "dngd_encoder_entropy_overflow_total",
                "dngd_encoder_submit_failures_total",
                "dngd_encoder_collect_failures_total",
                "dngd_encoder_frames_total"):
        if fam not in m:
            raise SmokeFailure(f"/metrics lacks {fam}")
    got = {"cache_hits": int(sum(m["jax_compile_cache_hits_total"])),
           "cache_requests": int(sum(m["jax_compile_cache_requests_total"])),
           "cache_misses": int(sum(m["jax_compile_cache_misses_total"])),
           "overflow_fallbacks": int(sum(
               m["dngd_encoder_entropy_overflow_total"])),
           "submit_failures": int(sum(
               m["dngd_encoder_submit_failures_total"])),
           "collect_failures": int(sum(
               m["dngd_encoder_collect_failures_total"])),
           "frames_encoded": int(sum(m["dngd_encoder_frames_total"]))}
    return got


def check_metrics(before: dict, got: dict, psnr: list) -> None:
    """A frame the encoder dropped while the stream ran, a device coder
    that gave way to the host on more than a small share of that
    window's frames, or an in-graph PSNR that never rose above the
    tier's floor in it, is not a pass."""
    if not psnr or not max(psnr) > PSNR_FLOOR_DB:
        raise SmokeFailure(f"in-graph PSNR never above the {PSNR_FLOOR_DB} "
                           f"dB floor in {len(psnr)} readings "
                           f"(max {max(psnr, default=None)})")
    for key in ("submit_failures", "collect_failures"):
        if got[key] != before[key]:
            raise SmokeFailure(f"{key} rose from {before[key]} to "
                               f"{got[key]} while the stream ran, must "
                               "not move")
    frames = got["frames_encoded"] - before["frames_encoded"]
    overflowed = got["overflow_fallbacks"] - before["overflow_fallbacks"]
    if overflowed > MAX_OVERFLOW_SHARE * frames:
        raise SmokeFailure(
            f"{overflowed} of {frames} frames fell back from the device "
            f"entropy coder to the host while the stream ran (more than "
            f"{MAX_OVERFLOW_SHARE:.0%}): the device coder is not what "
            "served this stream")


def step_sigterm(proc: subprocess.Popen, timeout_s: float) -> float:
    """The SIGTERM a pod deletion sends: drain, then exit code 0."""
    t0 = time.monotonic()
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"child still alive {timeout_s:.0f} s after "
                           "SIGTERM") from None
    if rc != 0:
        raise SmokeFailure(f"child exit code {rc} after SIGTERM")
    return time.monotonic() - t0


async def run_server_smoke(base_env: dict, workdir: pathlib.Path, *,
                           width: int = WIDTH, height: int = HEIGHT,
                           fps: int = FPS, n_fragments: int = N_FRAGMENTS,
                           platform: str = "tpu", want_platform: str = "tpu",
                           want_count: int = 1,
                           server_argv: list | None = None,
                           report: dict | None = None) -> tuple:
    """Every step against one server child; returns ``(ok, device)``
    where ``ok`` is the gate's verdict (all steps passed AND the device
    is the one wanted).  A failed step raises :class:`SmokeFailure` with
    the child's log tail already printed; ``report["device"]`` then
    still says what the child logged.  ``server_argv`` exists for the
    gate's own tests (a stub child)."""
    import aiohttp

    report = {} if report is None else report
    port = free_port()
    passwd = secrets.token_urlsafe(12)
    log_path = workdir / "server.log"
    env = child_env(base_env, port, passwd, width, height, fps, platform)
    t_start = time.monotonic()
    proc = spawn_server(env, log_path, server_argv)
    try:
        async with aiohttp.ClientSession() as http:
            healthy_s = await wait_healthy(http, port, proc,
                                           HEALTHY_TIMEOUT_S)
            device = read_device(log_path)
            report["device"] = device_summary(device)
            note(f"device line: {json.dumps(device, sort_keys=True)}")
            note(f"seconds to healthy: {healthy_s:.1f}")
            note(f"RING_DONATE resolved: {device.get('ring_donate')}")
            await step_auth(http, port, passwd)
            note("auth: GET / 401 without credentials, 401 with a wrong "
                 "password, 200 with PASSWD")
            note("ENCODER_PREWARM stays on, as shipped: the served path's "
                 "qp is a traced scalar, so the ladder has nothing to "
                 "compile ahead and the first frames carry the one cold "
                 "compile")
            warm = await wait_flowing(http, port, proc, WARM_FRAMES,
                                      COMPILE_TIMEOUT_S)
            note(f"{warm['frames_encoded']} frames encoded "
                 f"{time.monotonic() - t_start:.1f} s after spawn (the cold "
                 f"compile of the served programs sits in front of them): "
                 f"{warm['cache_requests']} cache-eligible compile "
                 f"requests, {warm['cache_hits']} persistent-cache hits; "
                 f"overflow fallbacks so far {warm['overflow_fallbacks']} "
                 f"(the wall-clock source jumps across a compile stall)")
            psnr: list = []
            sampler = asyncio.ensure_future(sample_psnr(http, port, psnr))
            try:
                stream = await step_stream(http, port, passwd, proc,
                                           n_fragments, width, height,
                                           STREAM_TIMEOUT_S)
            finally:
                sampler.cancel()
                await asyncio.gather(sampler, return_exceptions=True)
            note(f"stream: hello {stream['hello']['codec']} "
                 f"{width}x{height}, init segment {len(stream['init'])} B, "
                 f"{len(stream['frags'])} fragments, {stream['bytes']} bytes, "
                 f"IDRs at {stream['idr_at']}")
            note(f"client-side inter-arrival p50 (loopback websocket, not "
                 f"a benchmark): {stream['interarrival_p50_ms']:.2f} ms")
            decoded = step_decode(workdir / "stream.mp4", stream["init"],
                                  stream["frags"], width, height)
            note(f"cv2 decoded {decoded} frames of {width}x{height}")
            m = await step_metrics(http, port)
            note(f"compilations: {m['cache_requests']} cache-eligible "
                 f"compile requests, {m['cache_hits']} persistent-cache "
                 f"hits, {m['cache_misses']} misses, "
                 f"{time.monotonic() - t_start:.1f} s since spawn")
            if psnr:
                ps = sorted(psnr)
                note(f"in-graph PSNR (dngd_content_psnr_db, {len(ps)} "
                     f"readings of the last sampled frame while the stream "
                     f"ran): min {ps[0]:.2f} / median {ps[len(ps) // 2]:.2f}"
                     f" / max {ps[-1]:.2f} dB (floor {PSNR_FLOOR_DB} dB, "
                     f"gated on the max: the rate ladder moves the rest)")
            note(f"while the stream ran ({m['frames_encoded'] - warm['frames_encoded']} "
                 f"frames encoded): device-entropy overflow fallbacks "
                 f"{m['overflow_fallbacks'] - warm['overflow_fallbacks']}, "
                 f"submit failures "
                 f"{m['submit_failures'] - warm['submit_failures']}, "
                 f"collect failures "
                 f"{m['collect_failures'] - warm['collect_failures']} "
                 f"(since spawn: {m['overflow_fallbacks']} / "
                 f"{m['submit_failures']} / {m['collect_failures']} in "
                 f"{m['frames_encoded']} frames)")
            check_metrics(warm, m, psnr)
        exit_s = step_sigterm(proc, DRAIN_GRACE_S + EXIT_MARGIN_S)
        note(f"SIGTERM: child exit code 0 after {exit_s:.1f} s "
             f"(DNGD_DRAIN_GRACE_S {DRAIN_GRACE_S:.0f} s)")
    except BaseException:
        note("---- server child log tail ----")
        note(log_tail(log_path))
        note("---- end of child log ----")
        raise
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return gate(device, want_platform, want_count), device_summary(device)


def run_four_chips(n: int) -> tuple:
    """``--chips 4``: the cross-chip path and what it is compared with, in
    THIS process (no server child, so this process may hold the chips)."""
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path.insert(0, str(ROOT))
    import jax

    from docker_nvidia_glx_desktop_tpu.ops.h264_inter import RING_DONATE
    from docker_nvidia_glx_desktop_tpu.parallel import batch
    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)

    setup_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    note(f"device: {json.dumps(device)}; RING_DONATE resolved: "
         f"{list(RING_DONATE)}")
    if len(devs) != n:
        raise SmokeFailure(f"--chips {n} but jax.devices() shows {len(devs)}")
    t0 = time.monotonic()
    batch.dryrun_full_geometry(n)
    note(f"dryrun_full_geometry({n}): {time.monotonic() - t0:.1f} s, "
         "compilation included")
    return device["platform"] == "tpu" and bool(RING_DONATE), device


def main(argv: list | None = None, *,
         server_argv: list | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve through web.server_main on one chip "
                         "(default); 4: the cross-chip mesh path only")
    args = ap.parse_args(argv)
    ok, report = False, {}
    try:
        if args.chips == 4:
            ok, report["device"] = run_four_chips(4)
        else:
            with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
                ok, _ = asyncio.run(run_server_smoke(
                    dict(os.environ), pathlib.Path(tmp),
                    server_argv=server_argv, report=report))
    except SmokeFailure as e:
        note(f"FAILED: {e}")
    except Exception as e:         # an import, a start-up or a JAX error
        traceback.print_exc(file=sys.stdout)
        note(f"FAILED: {type(e).__name__}: {e}")
    print(json.dumps({"ok": bool(ok), "device": report.get("device")}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
