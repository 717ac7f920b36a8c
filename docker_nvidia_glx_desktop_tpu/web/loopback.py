"""Loopback end-to-end serving bench: the full path, measured locally.

Device-only numbers prove the kernels (devloop) and say nothing about
the host stages.  This module drives
the REAL serving path end to end on one box — synthetic X source ->
StreamSession (pipelined encode) -> muxer -> aiohttp server -> a local
WebSocket media sink — and reads the serving-budget ledger (obs/budget)
the session fed while it ran.  The result is the ``serving_budget``
block BENCH emits: per-stage p50s with the host<->device link cost
separated out (devloop round-trip probe), and the BASELINE ladder SLO
verdicts with per-stage attribution.

Everything uses the production code paths: the same SubscriberSet
fan-out, the same Mp4Muxer/WebM fragmenting, the same /ws handler a
browser speaks.  Only the pixels (SyntheticSource) and the sink (a
loopback aiohttp client) are synthetic.
"""

from __future__ import annotations

import asyncio
import time
from typing import Optional

from ..obs.budget import LEDGER
from ..rfb.source import SyntheticSource
from ..utils.config import Config, from_env
from ..utils.timing import percentile

__all__ = ["run_serving_budget", "serving_budget_config"]


def serving_budget_config(width: int, height: int, fps: int = 60,
                          extra: Optional[dict] = None) -> Config:
    """Bench config: auth off (the sink is loopback), ephemeral port,
    CQP (no rate-control qp ladder to prewarm), short GOP so both frame
    types are measured."""
    env = {
        "SIZEW": str(width), "SIZEH": str(height), "REFRESH": str(fps),
        "ENABLE_BASIC_AUTH": "false",
        "LISTEN_ADDR": "127.0.0.1", "LISTEN_PORT": "0",
        "ENCODER_PREWARM": "false",
        "ENCODER_BITRATE_KBPS": "0",
        "ENCODER_GOP": "30",
        # the bench MEASURES the budget; the degradation ladder reacting
        # to it mid-run would distort the very numbers being taken
        "DEGRADE_ENABLE": "false",
    }
    env.update(extra or {})
    return from_env(env)


async def _drain_ws(ws, n_frames: int, timeout_s: float,
                    has_init: bool = True) -> dict:
    """Consume the media websocket like a browser: hello JSON, init
    segment (fMP4/WebM codecs only), then media fragments.  ``fprobe``
    control messages are echoed back as acks exactly like the web
    client does, so the server's glass-to-glass journeys close through
    the REAL loopback round trip.  Returns sink-side arrival stats —
    the only numbers the server-side ledger cannot know."""
    import json

    import aiohttp

    frags = 0
    nbytes = 0
    acks = 0
    skip = 1 if has_init else 0       # init segment carries no samples
    arrivals = []
    deadline = time.perf_counter() + timeout_s
    while frags < n_frames:
        left = deadline - time.perf_counter()
        if left <= 0:
            break
        try:
            msg = await ws.receive(timeout=left)
        except asyncio.TimeoutError:
            break
        if msg.type == aiohttp.WSMsgType.BINARY:
            arrivals.append(time.perf_counter())
            if len(arrivals) > skip:
                frags += 1
                nbytes += len(msg.data)
        elif msg.type == aiohttp.WSMsgType.TEXT:
            try:
                ctrl = json.loads(msg.data)
            except ValueError:
                continue
            if ctrl.get("type") == "fprobe":
                await ws.send_json({"type": "ack", "id": ctrl["id"],
                                    "recv_ts": time.perf_counter()})
                acks += 1
        elif msg.type in (aiohttp.WSMsgType.CLOSED,
                          aiohttp.WSMsgType.ERROR):
            break
    media = arrivals[skip:]
    intervals = sorted((b - a) * 1e3 for a, b in zip(media, media[1:]))
    return {
        "frags": frags,
        "bytes": nbytes,
        "acks_sent": acks,
        "interarrival_p50_ms": round(percentile(intervals, 50), 3),
        "fps": (round(1e3 / percentile(intervals, 50), 2)
                if intervals and percentile(intervals, 50) > 0 else 0.0),
    }


async def run_serving_budget(cfg: Optional[Config] = None,
                             frames: int = 120,
                             width: int = 1920, height: int = 1080,
                             fps: int = 60,
                             probe_link: bool = True,
                             timeout_s: float = 300.0) -> dict:
    """Run the loopback bench and return the ``serving_budget`` block.

    The ledger window is cleared first so the block reflects exactly
    this run; the link probe runs AFTER the media loop so its dispatch
    RTT samples see the same device load the frames did.
    """
    import aiohttp

    from .server import bound_port, serve
    from .session import StreamSession

    if cfg is None:
        cfg = serving_budget_config(width, height, fps)
    width, height, fps = cfg.sizew, cfg.sizeh, cfg.refresh

    LEDGER.clear()
    from ..obs import trace as obst
    drops0 = obst.dropped_total()
    loop = asyncio.get_running_loop()
    source = SyntheticSource(width, height, fps=float(fps))
    session = StreamSession(cfg, source, loop=loop)
    session.start()
    runner = await serve(cfg, session)
    sink = {}
    mtext = ""
    cquality: dict = {}
    cdamage = None
    t0 = time.perf_counter()
    try:
        port = bound_port(runner)
        async with aiohttp.ClientSession() as http:
            async with http.ws_connect(
                    f"http://127.0.0.1:{port}/ws",
                    max_msg_size=0) as ws:
                hello = await ws.receive_json(timeout=timeout_s)
                assert hello.get("type") == "hello", hello
                sink = await _drain_ws(
                    ws, frames, timeout_s,
                    has_init=bool(session.init_segment))
            # content-plane visibility (ISSUE 17), captured while the
            # session still serves: the quality gauges on a LIVE
            # /metrics scrape plus the plane's rolling verdict — the
            # keys the CI serving-budget smoke asserts non-empty
            try:
                async with http.get(
                        f"http://127.0.0.1:{port}/metrics") as resp:
                    mtext = await resp.text()
            except Exception:
                mtext = ""
        try:
            from ..obs import content as obsc
            cquality = obsc.PLANE.quality_state().get(
                session.journeys.session) or {}
            cdamage = obsc.PLANE.mean_damage_fraction()
        except Exception:
            cquality, cdamage = {}, None
    finally:
        wall = time.perf_counter() - t0
        # glass-to-glass: captured BEFORE teardown (close_book drops the
        # book); acks closed journeys through the real ws round trip,
        # the rest (unsampled frames) stay open by design
        g2g = session.journeys.summary()
        session.stop()
        await runner.cleanup()

    if probe_link:
        LEDGER.probe_link()
    from ..obs import budget as obsb
    from ..obs import journey as obsj
    block = {
        "mode": "loopback-ws",
        "codec": session.codec_name,
        "geometry": f"{width}x{height}@{fps}",
        "frames_requested": frames,
        "wall_s": round(wall, 2),
        "sink": sink,
        # silent trace loss gate: the serving-budget smoke asserts 0
        # (drops accrued over THIS run, not process lifetime)
        "trace_dropped_total": obst.dropped_total() - drops0,
        # content & quality plane (ISSUE 17): in-graph PSNR/damage must
        # have flowed for this run and be scrapable while serving
        "content": {
            "metrics_visible": (
                "dngd_content_psnr_db" in mtext
                and "dngd_content_damage_fraction" in mtext),
            "psnr_p50_db": cquality.get("psnr_p50"),
            "verdict": cquality.get("verdict"),
            "frames": cquality.get("n", 0),
            "damage_fraction_mean": (round(cdamage, 4)
                                     if cdamage is not None else None),
        },
    }
    # the shared emitter (/debug/budget?format=json renders the same
    # function) — called before close_book so the live journey book is
    # flattened into glass_to_glass; the g2g captured pre-teardown wins
    # if the book already vanished
    block.update(obsb.serving_budget_block(
        session=session.journeys.session))
    if "glass_to_glass" not in block:
        block["glass_to_glass"] = dict(
            g2g, sample_every=obsj.sample_every(),
            methodology=obsb.G2G_METHODOLOGY)
    session.journeys.close_book()
    return block
