#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip (only the process that holds it can trace it): it
builds the served objects the way ``web/server_main.py:run()`` does
(``from_env``, ``setup_compile_cache``, ``StreamSession(cfg, source, loop=,
clock=)``, ``session.start()``, ``serve(cfg, session, ...)``) with the
benchmark's display where ``make_source`` would be.  The client is a child
process that never imports JAX (``client.py``).

Set-up is everything from process start to the start of the window: JAX
import, chip attach, compile or cache load, the warm-up of the encoder's
pull-size ladder, the client's join, until ``READY_AFTER`` fragments (two
GOPs) have arrived.  Then the window of ``--seconds``; then the client and the
session stop, and the stream is decoded and checked outside the window.  With
``--trace 1`` the profiler covers the last ``TRACE_S`` seconds of the window
(``stop_trace`` takes minutes and falls after it), the trace is reduced once
(``trace_reduce``: the device's totals; ``stage_reduce``: device time by
program and named stage, idle gaps by host span) and the line carries the
per-layer metrics and ``breakdown``; with ``--trace 0`` no profiler is loaded.
The last line of standard output is the one JSON object of the contract.  No
chip is an error, never XLA:CPU, except under ``--rehearse`` (for the tests),
which prints ``correct: false`` and no device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import os  # noqa: E402

# cv2 warns once a frame that it hands back the decoder's raw planes, which is
# what check.py asks it for
os.environ.setdefault("OPENCV_LOG_LEVEL", "ERROR")

import argparse  # noqa: E402
import asyncio  # noqa: E402
import faulthandler  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

READY_AFTER = 120           # fragments before the window opens: two GOPs
READY_TIMEOUT_S = 1100.0    # a cold compile sits in front of them
GRACE_S = 0.5               # frames in flight at the window's end still land
TRACE_S = 0.4               # the traced span, at the end of the window
TRACE_START_S = 0.1         # start_trace itself took 0.05-0.06 s on the chip
CLOSED_LOOP_FRAMES = 8      # one IDR and seven P frames
PULL_BUCKET = 1 << 16       # the step of the encoder's pull-size ladder
PULL_BUCKETS = 12           # sizes warmed: up to 768 KiB a frame
FAULT_LINES = 20            # frame order faults named one by one
STALL_S = 1.0               # no frame taken for this long: dump the stacks
STALL_LOOK_S = 0.25         # the watchdog's sleep between two looks

# Every number compared is exact, so every limit is 0 (PERF.md section 2).
LIMITS = {"undecoded_fragments": 0, "frame_order_faults": 0,
          "p_run_over_gop": 0, "compiles_in_window": 0,
          "closed_loop_luma_maxdiff": 0}


class BenchFailure(Exception):
    """The run cannot give a result.  Exit code 1, no result line."""


def note(msg: str) -> None:
    print(msg, flush=True)


# -- data: the manifest and the files it names ------------------------------

def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchFailure(f"no such file: {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def resolve_cell(name: str) -> dict:
    """The cell, its configuration and its traffic, each from its own file,
    found by the name BENCHMARK.json gives.  A name that resolves to nothing
    is an error, never a default."""
    manifest = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchFailure(f"BENCHMARK.json has no workload {name!r} "
                           f"(it has {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if cell["config"] not in configs:
        raise BenchFailure(f"workload {name!r} names configuration "
                           f"{cell['config']!r}, which BENCHMARK.json lacks")
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return {"manifest": manifest, "cell": cell, "config": config,
            "traffic": traffic}


def build_scene(traffic: dict, width: int, height: int, fps: int, seed: int):
    kind = traffic["generator"]
    try:
        gen = importlib.import_module(f"benchmark.traffic.gen_{kind}")
    except ModuleNotFoundError as e:
        raise BenchFailure(f"traffic generator {kind!r}: no "
                           f"benchmark/traffic/gen_{kind}.py") from e
    return gen.build(traffic["params"], width, height, fps, seed)


def load_by_file(directory: str, name: str):
    """``benchmark/<directory>/<name>.py`` as a module (a metric's name may
    hold '.' or '-', so it is loaded by path)."""
    path = HERE / directory / f"{name}.py"
    if not path.is_file():
        raise BenchFailure(f"{directory[:-1]} {name!r}: no "
                           f"benchmark/{directory}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{directory}.{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(cell_name: str, entries: list) -> list:
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


# -- the program's /metrics text (after chip_smoke.parse_metrics) -----------

def parse_metrics(text: str) -> dict:
    """Prometheus text -> {family: sum of its series}."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, val = line.rpartition(" ")
        if "_bucket{" in name:
            continue
        try:
            fam = name.split("{", 1)[0]
            out[fam] = out.get(fam, 0.0) + float(val)
        except ValueError:
            continue
    return out


def program_counters() -> dict:
    from docker_nvidia_glx_desktop_tpu.obs.metrics import REGISTRY
    return parse_metrics(REGISTRY.render())


# -- the device ---------------------------------------------------------------

def attach_device(chips: int, rehearse: bool) -> dict:
    """Import JAX and say what it found.  Outside a rehearsal JAX_PLATFORMS
    is ``tpu``, so JAX itself refuses to start without a chip."""
    try:
        import jax
        devs = jax.devices()
    except Exception as e:
        raise BenchFailure(f"JAX found no accelerator: {e}") from e
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearse:
        return device
    if device["platform"] != "tpu":
        raise BenchFailure(f"not an accelerator: {device}")
    if len(devs) < chips:
        raise BenchFailure(f"the cell asks for {chips} chip(s), JAX shows "
                           f"{len(devs)}")
    peaks = load_json(HERE / "peaks.json")
    if device["kind"] not in peaks["devices"]:
        raise BenchFailure(f"device kind {device['kind']!r} is not in "
                           "benchmark/peaks.json")
    return device


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


BACKEND_COMPILES: list = []     # (monotonic, seconds) of every XLA compile


def watch_compiles() -> None:
    """The benchmark's own record of backend compiles, with their times, to
    say what a compile request inside the window was."""
    from jax import monitoring

    def on_duration(event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            BACKEND_COMPILES.append((time.monotonic(), duration))

    monitoring.register_event_duration_secs_listener(on_duration)


def warm_pull_ladder(encoder, frames, buckets: int) -> None:
    """Warm every shape the window will use.  The encoder pulls each frame's
    bytes through a slice whose length is its guess of the frame's size,
    rounded up to 64 KiB, and every new length is a compile of a quarter of a
    second in the serving thread (my chip runs, PR 24).  Which lengths a
    window meets depends on its content, so set-up walks the ladder: the
    checkpoint interface (``export_state`` / ``import_state``) carries the
    two guesses, an imported checkpoint forces an IDR, and the frame after it
    is a P frame.  The first state is put back at the end."""
    first = encoder.export_state()
    for n in range(1, buckets + 1):
        encoder.import_state(dict(first, pull_guess=n * PULL_BUCKET,
                                  p_pull_guess=n * PULL_BUCKET))
        for rgb in frames[:2]:
            encoder.encode_collect(encoder.encode_submit(rgb))
    encoder.import_state(first)


def watch_for_a_stall(display, stop, stall_s: float = STALL_S,
                      look_s: float = STALL_LOOK_S) -> None:
    """The window's watchdog thread: when the session has taken no new frame
    from the display (``display.handed`` has not grown) for ``stall_s``, say
    so and write every thread's stack to standard error, once, and end.  One
    run in 62 of PR 33 took no frame for 14.6 s and nothing said where the
    session thread stood.  It reads one length every ``look_s`` and changes
    no number."""
    seen, since = len(display.handed), time.monotonic()
    while not stop.wait(look_s):
        n, now = len(display.handed), time.monotonic()
        if n != seen:
            seen, since = n, now
        elif now - since >= stall_s:
            note(f"STALL: no frame taken from the display for "
                 f"{now - since:.2f} s ({n} taken so far); every thread's "
                 "stack follows on standard error")
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
            return


# -- the run ------------------------------------------------------------------

async def serve_window(spec: dict, args, client,
                       workdir: pathlib.Path) -> dict:
    """Set-up, the window, teardown.  Returns what was observed."""
    import jax
    import numpy as np

    from docker_nvidia_glx_desktop_tpu.utils.config import from_env
    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    from docker_nvidia_glx_desktop_tpu.web.clock import MediaClock
    from docker_nvidia_glx_desktop_tpu.web.server import bound_port, serve
    from docker_nvidia_glx_desktop_tpu.web.session import StreamSession

    from benchmark.display import Display

    cfg = from_env()
    cache_dir = setup_compile_cache()
    watch_compiles()
    note(f"compile cache: {cache_dir}")
    if cfg.tpu_sessions > 1:
        # server_main.py:47-62 builds these through BucketedStreamManager
        raise NotImplementedError(
            "a configuration with TPU_SESSIONS > 1 is not built yet")
    loop = asyncio.get_running_loop()
    width, height, fps = cfg.sizew, cfg.sizeh, cfg.refresh
    scene = build_scene(spec["traffic"], width, height, fps, args.seed)
    display = Display(spec["traffic"], width, height, fps, args.seed)
    try:
        session = StreamSession(cfg, display, loop=loop, clock=MediaClock())
        if args.control:
            load_by_file("controls", args.control).apply(session)
            note(f"CONTROL {args.control!r} is in place: this run must come out "
                 "as not correct")
        warm = [np.zeros((height, width, 3), np.uint8) for _ in range(2)]
        for c, buf in enumerate(warm):
            scene.render(c, buf)
        t_warm = time.monotonic()
        warm_pull_ladder(session.encoder, warm,
                         2 if args.rehearse else PULL_BUCKETS)
        note(f"pull ladder warmed in {time.monotonic() - t_warm:.1f} s "
             f"({len(BACKEND_COMPILES)} backend compiles so far)")
        display.start()
        session.start()
        runner = await serve(cfg, session, None)
        obs: dict = {"display": display, "scene": scene, "cfg": cfg}
        window_over = threading.Event()
        try:
            client.stdin.write((json.dumps({
                "port": bound_port(runner), "user": "u",
                "passwd": os.environ["PASSWD"],
                "out": str(workdir / "stream"),
                "ready_after": READY_AFTER}) + "\n").encode())
            client.stdin.flush()
            line = await asyncio.wait_for(
                loop.run_in_executor(None, client.stdout.readline),
                READY_TIMEOUT_S)
            if not line.startswith(b"READY"):
                raise BenchFailure(f"the client said {line!r}")
            # ---- the window ----
            obs["epoch_k"] = display.mark_epoch()
            obs["counters_start"] = program_counters()
            t_start = time.monotonic()
            obs["t_start"], obs["t_end"] = t_start, t_start + args.seconds
            obs["setup_s"] = t_start - T_PROCESS_START
            watchdog = threading.Thread(
                target=watch_for_a_stall, args=(display, window_over),
                daemon=True)
            watchdog.start()
            if args.trace:
                # the traced span is the window's end: stop_trace takes
                # minutes (about 200 s for a second of trace: 850,000 device
                # operations a second, each named by its HLO text), and so it
                # falls after the window and disturbs nothing that is counted
                await asyncio.sleep(max(
                    0.0, args.seconds - TRACE_S - TRACE_START_S))
                tdir = str(workdir / "trace")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # 1,000,000 events a second
                opts.host_tracer_level = 2
                opts.enable_hlo_proto = False
                t_tr = time.monotonic()
                await loop.run_in_executor(
                    None, lambda: jax.profiler.start_trace(
                        tdir, profiler_options=opts))
                note(f"start_trace took {time.monotonic() - t_tr:.2f} s")
                obs["trace_dir"] = tdir
            await asyncio.sleep(max(0.0, obs["t_end"] - time.monotonic()))
            window_over.set()
            watchdog.join()
            obs["counters_end"] = program_counters()
            if args.trace:
                t_stop = time.monotonic()
                await loop.run_in_executor(None, jax.profiler.stop_trace)
                note(f"stop_trace took {time.monotonic() - t_stop:.1f} s")
            obs["memory_peak_bytes"] = memory_peak_bytes()
            await asyncio.sleep(GRACE_S)
        finally:
            window_over.set()
            try:
                client.stdin.write(b"\n")
                client.stdin.flush()
            except OSError:
                pass
            session.stop()
        # outside the window, on the encoder object the window drove
        from benchmark import barcode, check
        k_from = int((obs["t_start"] - display.t0) * fps) + 1
        k_to = int((obs["t_end"] - display.t0) * fps)
        obs["display_late_ms"] = display.late_ms(k_from, k_to)
        obs["display_refreshes"] = k_to - k_from
        obs["display_skipped"] = display.skipped
        obs["display_cpus"] = display.cpus_inherited
        display.close()
        frames = []
        for c in range(CLOSED_LOOP_FRAMES):
            buf = np.zeros((height, width, 3), np.uint8)
            scene.render(c, buf)
            barcode.draw(buf, c)
            frames.append(buf)
        obs["closed_loop_luma_maxdiff"] = check.closed_loop_maxdiff(
            session.encoder, frames, str(workdir / "closed_loop.h264"),
            width, height)
        session.close()
        await runner.cleanup()
        return obs
    finally:
        display.close()


def reduce_run(args, obs: dict, workdir: pathlib.Path) -> dict:
    """From the client's record, the display's log, the counters and the
    trace to the numbers.  Nothing here runs inside the window."""
    import numpy as np

    from benchmark import barcode, check, stats

    cfg, display, scene = obs["cfg"], obs["display"], obs["scene"]
    width, height, fps = cfg.sizew, cfg.sizeh, cfg.refresh
    t_start, t_end = obs["t_start"], obs["t_end"]
    rec = json.loads((workdir / "stream.json").read_text())
    stamps, lens = rec["stamps"], rec["lens"]
    blob = (workdir / "stream.mp4").read_bytes()
    frags, pos = [], rec["init_len"]
    for n in lens:
        frags.append(blob[pos:pos + n])
        pos += n
    hello = rec["hello"] or {}
    if (hello.get("width"), hello.get("height")) != (width, height):
        raise BenchFailure(f"bad hello: {hello}")

    epoch = obs["epoch_k"]
    buf = np.zeros((height, width, 3), np.uint8)

    def render_luma(k: int):
        scene.render(k - epoch if k >= epoch else k, buf)
        barcode.draw(buf, k)
        return check.source_luma(buf)

    ks, psnr = check.read_stream(
        str(workdir / "stream.mp4"), width, height, render_luma,
        psnr_every=5, in_window=lambda i: t_start <= stamps[i] < t_end)
    handed_at = dict(display.handed)
    faults = check.order_faults(ks, stamps, display.handed)
    compared = {
        "undecoded_fragments": abs(len(frags) - len(ks)),
        "frame_order_faults": len(faults),
        "p_run_over_gop": max(0, check.longest_p_run(frags)
                              - (cfg.encoder_gop - 1)),
        "compiles_in_window": int(
            obs["counters_end"]["jax_compile_cache_requests_total"]
            - obs["counters_start"]["jax_compile_cache_requests_total"]),
        "closed_loop_luma_maxdiff": obs["closed_loop_luma_maxdiff"],
    }
    seen = stats.delivered(zip(ks, stamps), t_start, t_end)
    lat = stats.latencies_ms(seen, display.t0, fps)
    arrived = {k for k in ks if k is not None}
    in_window = [i for i, s in enumerate(stamps) if t_start <= s < t_end]
    took = [(k, t) for k, t in display.handed if t_start <= t < t_end]
    take_gaps_ms = [(b[1] - a[1]) * 1e3 for a, b in zip(took, took[1:])]
    capture_age_ms = [(t - display.due(k)) * 1e3 for k, t in took]
    taken_to_glass_ms = [(stamp - handed_at[k]) * 1e3
                         for k, stamp in seen.items() if k in handed_at]
    taken = [k for k, _ in took]
    note(f"window {args.seconds:g} s: {len(in_window)} fragments arrived, "
         f"{len(seen)} distinct frames delivered, {len(taken)} taken from "
         f"the display, {len(lat)} latency samples, {len(psnr)} PSNR "
         f"samples; {len(frags)} fragments since the join, {len(ks)} decoded")
    if not lat or not psnr:
        raise BenchFailure("no frame was delivered inside the window")
    note(f"longest interval between two frames taken from the display: "
         f"{max(take_gaps_ms, default=0.0):.1f} ms; longest latency "
         f"{max(lat):.1f} ms; the display skipped "
         f"{obs['display_refreshes'] - len(obs['display_late_ms'])} of the "
         f"window's {obs['display_refreshes']} refreshes and "
         f"{obs['display_skipped']} since its start (its process started "
         f"on {obs['display_cpus']} of {os.cpu_count()} CPUs)")
    note(f"a frame's age when the session took it from the display: p50 "
         f"{stats.percentile(capture_age_ms, 50):.3f} ms; from there to the "
         f"client: p50 {stats.percentile(taken_to_glass_ms, 50):.3f} ms")
    for f in faults[:FAULT_LINES]:
        note(f"frame order fault: picture {f['picture']} reads k = {f['k']} "
             f"after k = {f['after']}; when it arrived, "
             f"{f['stamp'] - t_start:.4f} s into the window, the display had "
             f"last handed out k = {f['handed_k']}: {f['why']}")
    if len(faults) > FAULT_LINES:
        note(f"and {len(faults) - FAULT_LINES} more frame order faults")
    for name, value in compared.items():
        note(f"compared: {name} = {value} (limit {LIMITS[name]})")
    for t, secs in BACKEND_COMPILES:
        if t_start <= t < t_end + 1.0:
            note(f"a backend compile of {secs * 1e3:.1f} ms ended "
                 f"{t - t_start:.3f} s into the window")
    correct = all(compared[n] <= LIMITS[n] for n in compared)
    end_to_end = {
        "delivered_fps": (len(seen) / args.seconds, "frames/s"),
        "g2g_p50_ms": (stats.percentile(lat, 50), "ms"),
        "g2g_p95_ms": (stats.percentile(lat, 95), "ms"),
        "psnr_p50_db": (stats.percentile(list(psnr.values()), 50), "dB"),
        "setup_s": (obs["setup_s"], "s"),
    }
    run = {
        "seconds": args.seconds, "t_start": t_start, "t_end": t_end,
        "fps": fps, "counters_start": obs["counters_start"],
        "counters_end": obs["counters_end"],
        "display_late_ms": obs["display_late_ms"],
        "display_skipped": obs["display_skipped"],
        "take_gaps_ms": take_gaps_ms, "capture_age_ms": capture_age_ms,
        "taken_to_glass_ms": taken_to_glass_ms,
        "bytes_in_window": sum(lens[i] for i in in_window),
        "frames_delivered": len(seen), "trace": None, "stages": None,
    }
    return {"correct": correct, "compared": compared,
            "attempted": len(taken),
            "failed": sum(1 for k in taken if k not in arrived),
            "end_to_end": end_to_end, "run": run}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: XLA:CPU at --geometry, correct: false, "
                         "no device metric")
    ap.add_argument("--geometry", default=None,
                    help="WxH, with --rehearse only")
    ap.add_argument("--control", default=None,
                    help="put benchmark/controls/<name>.py in place: the run "
                         "must then come out as not correct")
    ap.add_argument("--resolve-only", action="store_true",
                    help="resolve the cell's files by name, print what was "
                         "found, and stop before JAX is touched")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the .xplane.pb of a traced run here")
    args = ap.parse_args(argv)
    if args.geometry and not args.rehearse:
        ap.error("--geometry is for --rehearse")
    client = None
    workdir = pathlib.Path(tempfile.mkdtemp(prefix="bench_run_"))
    try:
        spec = resolve_cell(args.workload)
        cell, config = spec["cell"], spec["config"]
        readers = [m["name"] for m in metrics_for(
            cell["name"], spec["manifest"]["per_layer"])]
        for name in readers:
            load_by_file("layer_metrics", name)        # fail before the chip
        if args.control:
            load_by_file("controls", args.control)
        if args.resolve_only:
            import numpy as np
            build_scene(spec["traffic"], 320, 240, 60, args.seed).render(
                0, np.zeros((240, 320, 3), np.uint8))
            print(json.dumps({
                "workload": cell["name"], "chips": cell["chips"],
                "config": config["name"], "env": config["env"],
                "generator": spec["traffic"]["generator"],
                "per_layer": readers}), flush=True)
            return 0
        # the server's environment, as the configuration file states it
        os.environ.pop("DISPLAY", None)
        os.environ.update(config["env"])
        os.environ.update({
            "LISTEN_ADDR": "127.0.0.1", "LISTEN_PORT": "0",
            "PASSWD": f"bench-{args.seed:x}",
            "JAX_PLATFORMS": "cpu" if args.rehearse else "tpu"})
        os.environ.pop("BASIC_AUTH_PASSWORD", None)
        if args.geometry:
            w, h = args.geometry.lower().split("x")
            os.environ.update({"SIZEW": w, "SIZEH": h})
        client = subprocess.Popen(
            [sys.executable, str(HERE / "client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        device = attach_device(cell["chips"], args.rehearse)
        note(f"device: {json.dumps(device)}")
        obs = asyncio.run(serve_window(spec, args, client, workdir))
        if client.wait(timeout=60) != 0:
            raise BenchFailure("the client did not end cleanly")
        out = reduce_run(args, obs, workdir)
        run = out["run"]
        if args.trace:
            from benchmark import stage_reduce, trace_reduce
            found = sorted(glob.glob(os.path.join(
                obs["trace_dir"], "plugins", "profile", "*", "*.xplane.pb")))
            if not found:
                raise BenchFailure("the profiler wrote no .xplane.pb")
            if args.keep_trace:
                shutil.copy(found[-1], args.keep_trace)
            t_red = time.monotonic()
            run["trace"] = trace_reduce.reduce(found[-1])
            run["stages"] = stage_reduce.reduce(found[-1])
            note(f"the trace ({os.path.getsize(found[-1]) >> 20} MiB) was "
                 f"reduced in {time.monotonic() - t_red:.1f} s")
        device["memory_peak_bytes"] = obs["memory_peak_bytes"]
        names = spec["manifest"]["per_layer" if args.trace else "end_to_end"]
        metrics = {}
        if args.trace:
            for m in metrics_for(cell["name"], names):
                value = load_by_file("layer_metrics", m["name"]).read(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in metrics_for(cell["name"], names):
                value, unit = out["end_to_end"][m["name"]]
                metrics[m["name"]] = {"value": value, "unit": unit}
        result = {"correct": out["correct"], "attempted": out["attempted"],
                  "failed": out["failed"], "metrics": metrics,
                  "device": device}
        if args.rehearse:
            # a CPU run gives no device number and is never a result
            result["rehearsal"] = {"correct_before_override": out["correct"],
                                   "compared": out["compared"]}
            result["correct"] = False
            traced = {m["name"] for m in spec["manifest"]["per_layer"]
                      if m["source"] == "device_trace"}
            metrics = {k: v for k, v in metrics.items() if k not in traced}
            result["metrics"] = metrics
            device.pop("memory_peak_bytes", None)
        elif args.trace:
            tr = run["trace"]
            if not tr["busy_s"] > 0:
                raise BenchFailure("the trace shows no device operation")
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
            result["breakdown"] = {
                "device_ops": stage_reduce.device_ops(run["stages"])[:10],
                "idle_gaps": run["stages"]["idle_gaps"][:10]}
        note(f"end to end: {json.dumps({k: v[0] for k, v in out['end_to_end'].items()})}")
        # each number compared beside its limit: last in the line, and the
        # last lines on standard error
        result["compared"] = {n: {"value": v, "limit": LIMITS[n]}
                              for n, v in out["compared"].items()}
        print(json.dumps(result), flush=True)
        for n, c in result["compared"].items():
            print(f"compared: {n} = {c['value']} (limit {c['limit']})",
                  file=sys.stderr, flush=True)
        return 0
    except BenchFailure as e:
        note(f"FAILED: {e}")
        return 1
    finally:
        if client is not None and client.poll() is None:
            client.kill()
            client.wait()
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
