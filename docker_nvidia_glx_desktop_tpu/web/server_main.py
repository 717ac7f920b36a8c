"""CLI entry for the streaming server (the ``streamer`` program in the boot
plan — the selkies-gstreamer-entrypoint.sh:43-47 role): capture the
configured display (synthetic source when no X), encode on TPU, serve the
web client + websocket on ``LISTEN_PORT``."""

from __future__ import annotations

import asyncio
import logging

from ..rfb.source import make_source
from ..utils.config import from_env
from .input import make_injector
from .server import serve
from .session import StreamSession

log = logging.getLogger(__name__)


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    cfg = from_env()
    # Persistent XLA compile cache: restarts and the qp-ladder prewarm
    # skip every compile a previous process already did.
    from ..utils.jaxcache import setup_compile_cache
    setup_compile_cache()
    # Which device this process serves from, once, before anything
    # compiles: a chip that is silently absent (a CPU fallback, a mesh
    # on the first chip only) must be readable from the first log lines
    # — chip_smoke.py gates on this line.
    import json

    from ..native import lib as native_lib
    from ..obs.provenance import topology
    from ..ops.h264_inter import RING_DONATE
    log.info("device: %s", json.dumps(
        {**topology(), "ring_donate": list(RING_DONATE),
         "native_entropy": native_lib.available()}, sort_keys=True))

    async def run():
        from .clock import MediaClock

        loop = asyncio.get_running_loop()
        clock = MediaClock()        # ONE A/V timeline for every transport
        manager = None
        session = None
        if cfg.tpu_sessions > 1:
            # BASELINE config 5: N sessions, one batched device program.
            # Session 0 captures the real display when one exists; the
            # rest are synthetic until multi-display provisioning lands.
            # Only session 0 gets a real input path (cross-session input
            # isolation).
            from .multisession import BucketedStreamManager
            sizes = cfg.session_sizes()
            sources = [make_source(cfg.display if i == 0 else None,
                                   sizes[i][0], sizes[i][1])
                       for i in range(cfg.tpu_sessions)]
            injectors = [make_injector(cfg.display) if i == 0 else None
                         for i in range(cfg.tpu_sessions)]
            manager = BucketedStreamManager(cfg, sources, loop=loop,
                                            injectors=injectors)
            manager.start()
            injector = None      # per-hub injectors own all input routing
        else:
            source = make_source(cfg.display, cfg.sizew, cfg.sizeh)
            session = StreamSession(cfg, source, loop=loop, clock=clock)
            session.start()
            injector = make_injector(cfg.display)
        from .joystick import JoystickHub
        joystick = JoystickHub()
        try:
            await joystick.start()
        except OSError:
            logging.exception("joystick hub disabled")
            joystick = None
        from .audio import AudioSession, make_audio_source
        audio_src = make_audio_source(cfg.pulse_server)
        audio = None
        if audio_src is not None:
            audio = AudioSession(
                audio_src, loop=loop,
                source_factory=lambda: make_audio_source(cfg.pulse_server),
                codec=cfg.audio_codec, bitrate=cfg.audio_bitrate,
                clock=clock)
            audio.start()
        else:
            logging.info("no PulseAudio capture; audio track disabled")
        runner = await serve(cfg, session, injector, joystick=joystick,
                             audio=audio, manager=manager)
        logging.info("streaming server on %s:%d (%d session(s), %dx%d)",
                     cfg.listen_addr, cfg.listen_port,
                     cfg.tpu_sessions if manager else 1,
                     cfg.sizew, cfg.sizeh)
        # Startup memory picture (VERDICT r5 weak #4): peak host RSS +
        # compile-cache hit/miss, logged once and live on /metrics as
        # process_peak_rss_bytes / jax_compile_cache_*_total.
        from ..obs.procstats import log_startup
        log_startup()

        # Graceful drain on SIGTERM (k8s pod deletion; see the preStop
        # hook in deploy/xgl-tpu.yml).  With DNGD_HANDOFF_DIR/_SOCK set
        # this MIGRATES: snapshot sessions + wire continuity for the
        # successor, hand each client a resume token, then exit once
        # the snapshot is safely spooled/streamed.  Without it, legacy
        # drain: stop admitting, tell clients to pre-connect elsewhere,
        # flush DRAIN_GRACE_S, exit — either way well inside
        # terminationGracePeriodSeconds, so SIGKILL never lands.
        stop = asyncio.Event()

        def _drain_then_stop(signame: str) -> None:
            from .server import _spawn_bg

            migrate = runner.app.get("handoff_migrate")
            handoff = runner.app.get("handoff")
            if migrate is not None and handoff is not None \
                    and handoff.enabled:
                async def _migrate_then_stop():
                    try:
                        await migrate(signame)
                        # short flush: the migrate message must reach
                        # every client socket before the process dies
                        await asyncio.sleep(
                            min(cfg.drain_grace_s, 2.0))
                    except Exception:
                        log.exception("handoff migrate failed; "
                                      "exiting after the grace window")
                        await asyncio.sleep(cfg.drain_grace_s)
                    stop.set()

                _spawn_bg(_migrate_then_stop())
                return
            begin = runner.app.get("begin_drain")
            if begin is not None:
                begin(signame)

            async def _grace():
                await asyncio.sleep(cfg.drain_grace_s)
                stop.set()

            # keep a strong ref: a bare ensure_future is only weakly
            # held by the loop and GC could collect the grace timer —
            # the pod would then drain forever instead of exiting
            # (analysis finding async-task-leak)
            _spawn_bg(_grace())

        # SIGTERM only: Ctrl-C (SIGINT) keeps its immediate
        # KeyboardInterrupt teardown for local iteration — the drain
        # grace is for orchestrated shutdowns, not developer loops
        import signal
        try:
            loop.add_signal_handler(
                signal.SIGTERM, _drain_then_stop, "SIGTERM")
        except (NotImplementedError, RuntimeError):
            pass                           # non-unix event loop
        try:
            await stop.wait()
        finally:
            # full close (not bare stop): releases the per-session
            # observability state so a supervised restart in the same
            # process never accumulates registry leftovers
            if session is not None:
                session.close()
            if manager is not None:
                manager.close()
            await runner.cleanup()

    asyncio.run(run())


if __name__ == "__main__":
    main()
