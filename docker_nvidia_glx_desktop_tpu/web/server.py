"""First-party streaming web server — the selkies-gstreamer role.

One aiohttp application on the single exposed port (8080, reference
Dockerfile:535) provides everything the reference's web layer does
(selkies-gstreamer-entrypoint.sh:43-47):

- **HTTP basic auth** on every route when ``ENABLE_BASIC_AUTH`` (password
  chain ``BASIC_AUTH_PASSWORD <- PASSWD``, selkies-gstreamer-entrypoint.sh:20);
- **/** the built-in web client (MSE player + input capture);
- **/manifest.json** PWA manifest honoring ``PWA_APP_NAME``/``PWA_APP_SHORT_NAME``/
  ``PWA_START_URL`` (the manifest-rewrite parity, selkies-gstreamer-entrypoint.sh:27-38);
- **/turn** RTCConfiguration JSON (TURN REST-API credentials, ``web/turn.py``);
- **/stats** live session metrics (fps, encode-ms percentiles, bitrate —
  SURVEY.md §5 observability parity) — a JSON view over the obs registry;
- **/metrics** Prometheus text exposition (incl. the ``slo_*`` gauges
  evaluating the BASELINE ladder), **/debug/trace** Chrome trace-event
  JSON of the per-frame pipeline ring buffer, and **/debug/budget** the
  serving-budget ledger with link-separated per-stage p50s and SLO
  verdicts (``obs/``); all auth-exempt like ``/healthz``;
- **/ws** the session websocket: JSON control messages down, binary fMP4
  media down, compact input messages up (``web/input.py`` protocol).

HTTPS via ``ENABLE_HTTPS_WEB``/``HTTPS_WEB_CERT``/``HTTPS_WEB_KEY``
(xgl.yml:68-74).  The media transport is MSE-over-WebSocket — TPU-encoded
H.264 in fMP4 fragments — which needs no GStreamer/SRTP on either end; the
signaling surface (SDP offer/answer message types) is kept so a webrtcbin
bridge can slot in where GStreamer exists.
"""

from __future__ import annotations

import base64
import hmac
import importlib.resources
import json
import logging
import ssl
import time
from typing import Optional

from aiohttp import WSMsgType, web

from ..obs.http import OBS_EXEMPT_PATHS, add_obs_routes
from ..obs.metrics import REGISTRY
from ..obs.trace import M_WS_SEND_MS
# Imported for the metric-registration side effect: the dngd_sctp_* /
# dngd_datachannel_* families (and the sctp_drop_burst/dcep_open_stall
# fault points) must exist on /metrics from server start — a dashboard
# watching retransmits cannot wait for the first stock client to
# connect.  Deliberately NOT webrtc.peer: that pulls in dtls, which
# dlopens libssl.so.3 and must stay lazy for libssl-less images.
from ..webrtc import datachannel as _datachannel  # noqa: F401
from ..webrtc import sctp as _sctp  # noqa: F401
# Same PR-13 lesson for the content & quality plane: the dngd_content_*
# families and the psnr_floor_breach/damage_spike event-kind series
# register at import (plus the flight-recorder state provider), so
# /metrics and /debug/events carry them from boot, not first frame.
from ..obs import content as _content  # noqa: F401
# ... and for the client-QoE gauges (dngd_client_qoe_*), which would
# otherwise only register when the first stock client connects
from . import selkies_shim as _selkies  # noqa: F401
from ..resilience import faults as rfaults
# Ingress governor: imported eagerly so the dngd_ingress_* violation /
# quarantine families exist on /metrics from boot (same boot-visibility
# lesson), and used per-connection below (PeerBudget / ProbeWindow).
from ..resilience import ingress as ringress
# Handoff plane: eager so the dngd_handoff_* families are scrape-
# visible from boot (the successor's CI smoke asserts them on /metrics
# before any client resumes), and used below for drain-to-migrate.
from ..resilience import handoff as rhandoff
from ..resilience.continuity import DrainState
from ..utils.config import Config
from .input import Injector, make_injector
from .turn import ice_servers

log = logging.getLogger(__name__)

__all__ = ["make_app", "serve", "basic_auth_middleware",
           "handle_input_text", "spawn_bg"]

# Strong refs to fire-and-forget tasks (shed-eviction notifies): the
# event loop keeps only a weak reference to scheduled tasks, so a bare
# ensure_future can be garbage-collected mid-flight and the eviction
# close never reaches the client (analysis finding async-task-leak).
_BG_TASKS: set = set()


def spawn_bg(coro):
    import asyncio

    task = asyncio.ensure_future(coro)
    _BG_TASKS.add(task)
    task.add_done_callback(_BG_TASKS.discard)
    return task


_spawn_bg = spawn_bg     # data-channel binders (selkies_shim) share it


def basic_auth_middleware(cfg: Config):
    """401-challenge everything unless the basic-auth password matches.
    Any username is accepted — the reference authenticates by password only
    (README.md:23: the selkies login is PASSWD with user ignored)."""

    expected = cfg.effective_basic_auth_password

    @web.middleware
    async def mw(request: web.Request, handler):
        # k8s probes, Prometheus scrapers and trace pulls run without the
        # session password (same contract as the reference's probes).
        # READ-ONLY methods only: the exemption is for telemetry, and
        # /debug/faults carries a state-mutating POST (arming a fault)
        # that must clear BOTH the DNGD_FAULT_INJECTION gate and auth.
        if request.method in ("GET", "HEAD") and (
                request.path == "/healthz"
                or request.path in OBS_EXEMPT_PATHS):
            return await handler(request)
        if not cfg.enable_basic_auth:
            return await handler(request)
        hdr = request.headers.get("Authorization", "")
        ok = False
        if hdr.startswith("Basic "):
            try:
                decoded = base64.b64decode(hdr[6:]).decode()
                _, _, password = decoded.partition(":")
                ok = hmac.compare_digest(password, expected)
            except Exception:
                ok = False
        if not ok:
            return web.Response(
                status=401,
                headers={"WWW-Authenticate":
                         'Basic realm="tpu-desktop", charset="UTF-8"'})
        return await handler(request)

    return mw


def _client_html(cfg: Config) -> str:
    try:
        return (importlib.resources.files(__package__)
                .joinpath("static/index.html").read_text())
    except Exception:
        return "<html><body>client assets missing</body></html>"


def make_app(cfg: Config, session=None,
             injector: Optional[Injector] = None,
             supervisor=None, joystick=None,
             audio=None, manager=None) -> web.Application:
    app = web.Application(middlewares=[basic_auth_middleware(cfg)])
    # In manager (multi-session) mode input routing is per-hub; a global
    # injector would open a second uinput/X connection that nothing uses.
    if injector is None and manager is None:
        injector = make_injector(cfg.display)

    # SLO-driven degradation ladder (resilience/degrade): reacts to the
    # serving-budget ledger + per-peer RTCP loss by shedding quality
    # through the session's own control paths.  DEGRADE_ENABLE=false
    # (or no session to execute on) leaves the controller off.
    app["degrade"] = None
    # Single-session only: a batched manager shares one device budget
    # across N sessions, and degrading only hub 0 would punish one
    # client without relieving the breach — a manager-level executor
    # (degrade the whole bucket, re-bucket via batch.degraded_geometry)
    # is the follow-up, not a session(0) special case.
    degrade_target = session
    if manager is not None and cfg.degrade_enable:
        log.info("degradation ladder not wired in multi-session mode "
                 "(needs a manager-level executor)")
    if cfg.degrade_enable and degrade_target is not None:
        from ..resilience.degrade import DegradeController, SessionExecutor

        ctl = DegradeController(SessionExecutor(degrade_target, cfg=cfg))
        app["degrade"] = ctl

        async def _start_degrade(app_):
            import asyncio

            app_["degrade_task"] = asyncio.ensure_future(
                ctl.run(cfg.degrade_interval_s))

        async def _stop_degrade(app_):
            ctl.stop()
            task = app_.get("degrade_task")
            if task is not None:
                task.cancel()

        app.on_startup.append(_start_degrade)
        app.on_cleanup.append(_stop_degrade)

    # -- fleet admission & overload protection (fleet/) ----------------
    # Capacity-aware scheduler between /ws and the managers: admit /
    # queue / reject-with-retry_after_s, queue-depth backpressure into
    # the degrade ladder fleet-wide, newest/lowest-tier-first shedding.
    app["fleet"] = None
    if cfg.fleet_enable:
        from ..fleet.capacity import CapacityModel
        from ..fleet.scheduler import FleetScheduler

        def _chips() -> int:
            if manager is not None and hasattr(manager, "surviving_chips"):
                return manager.surviving_chips()
            return 1

        def _fleet_degrade(level: int) -> None:
            # manager mode: MB-snapped geometry re-bucket (one shared
            # compiled step per rung, parallel/batch.DEGRADE_SCALES);
            # single-session mode: the PR 3 qp/fps executors directly —
            # but ONLY when the SLO DegradeController is off, because it
            # owns the same knobs and a backpressure restore here would
            # silently undo its engaged rung (overload surfaces as a
            # budget breach it already walks its own ladder for)
            if manager is not None:
                if hasattr(manager, "request_degrade_level"):
                    manager.request_degrade_level(level)
                return
            if session is None or app["degrade"] is not None:
                return
            from ..resilience.degrade import SessionExecutor
            if hasattr(session, "set_qp_offset"):
                session.set_qp_offset(
                    SessionExecutor.QP_STEP if level >= 1 else 0)
            if hasattr(session, "set_fps_cap"):
                session.set_fps_cap(
                    max(cfg.refresh / 2.0, 5.0) if level >= 2 else None)

        fleet = FleetScheduler(
            model=CapacityModel(
                max_sessions_override=cfg.fleet_max_sessions,
                per_chip_override=cfg.fleet_sessions_per_chip,
                tune=getattr(cfg, "encoder_tune", "off")),
            chips_fn=_chips,
            geometry=(cfg.sizew, cfg.sizeh), fps=cfg.refresh,
            queue_depth=cfg.fleet_queue_depth,
            queue_timeout_s=cfg.fleet_queue_timeout_s,
            retry_after_s=cfg.fleet_retry_after_s,
            on_degrade=_fleet_degrade,
            max_degrade_level=cfg.fleet_backpressure_level,
            # only the batch managers' MB-snapped re-bucket actually
            # shrinks the serving geometry, and only with resize on;
            # the single-session qp/fps executors change cost, not MBs
            degrade_shrinks_geometry=(manager is not None
                                      and cfg.webrtc_enable_resize),
            # capacity follows the rung the mesh is ACTUALLY serving —
            # the manager may refuse a requested re-bucket
            applied_level_fn=(manager.applied_degrade_level
                              if manager is not None
                              and hasattr(manager, "applied_degrade_level")
                              else None))
        app["fleet"] = fleet
        # flight-recorder postmortems embed the live fleet picture
        from ..obs import flight as obsf
        obsf.register_state_provider("fleet", fleet.snapshot)

        async def _start_fleet(app_):
            import asyncio

            app_["fleet_task"] = asyncio.ensure_future(fleet.run(0.5))

        async def _stop_fleet(app_):
            fleet.stop()
            task = app_.get("fleet_task")
            if task is not None:
                task.cancel()

        app.on_startup.append(_start_fleet)
        app.on_cleanup.append(_stop_fleet)

    def resolve_session(request):
        """Single session, or ``?session=i`` into a BatchStreamManager;
        under fleet admission an unqualified join is assigned the
        least-loaded hub (the scheduler decides WHETHER, this decides
        WHERE)."""
        if manager is not None:
            q = request.query.get("session")
            if q is None and app["fleet"] is not None:
                best, best_n, i = None, None, 0
                while True:
                    hub = manager.session(i)
                    if hub is None:
                        break
                    n = len(hub._subscribers)
                    if best is None or n < best_n:
                        best, best_n = hub, n
                    i += 1
                return best
            try:
                idx = int(q or "0")
            except ValueError:
                return None
            return manager.session(idx)
        return session

    # -- graceful drain (SIGTERM / POST /debug/drain) ------------------
    # Draining flips one flag: new websocket sessions are refused with a
    # {"type": "draining"} answer, and every CONNECTED subscriber gets a
    # ("draining",) control item so its client can pre-connect elsewhere
    # while the last in-flight frames keep flushing.  The process exits
    # only when the caller (server_main's SIGTERM handler, or the k8s
    # preStop hook's sleep) decides the grace period is over.
    drain = DrainState()
    app["drain"] = drain

    def _drain_sessions():
        if manager is not None:      # Batch or Bucketed manager shapes
            mgrs = getattr(manager, "managers", None) or [manager]
            return [h for m in mgrs for h in getattr(m, "hubs", [])]
        return [session] if session is not None else []

    def begin_drain(reason: str = "drain") -> bool:
        fresh = drain.begin(reason)
        if fresh:
            from ..obs import events as obsev
            obsev.emit("drain", reason=reason)
            # a drain-initiated disconnect is a deploy, not an incident:
            # it lands in shed_total under its own reason label
            if app["fleet"] is not None:
                app["fleet"].account_drain("drain")
            for sess in _drain_sessions():
                subs = getattr(sess, "_subscribers", None)
                if subs is not None:
                    subs.broadcast_all([("draining", reason)])
        return fresh

    app["begin_drain"] = begin_drain

    # -- zero-downtime handoff (resilience/handoff) --------------------
    # With DNGD_HANDOFF_DIR (or _SOCK) set, drain MIGRATES instead of
    # shedding: snapshot encoder + wire continuity per connection, hand
    # it to the successor, tell each client to reconnect with a resume
    # token.  Without it, the legacy drain-and-shed above runs.
    hmgr = rhandoff.HandoffManager(
        handoff_dir=getattr(cfg, "handoff_dir", ""),
        sock_path=getattr(cfg, "handoff_sock", ""),
        token_ttl_s=getattr(cfg, "handoff_token_ttl_s", 45.0))
    app["handoff"] = hmgr

    def _adopt_imported(entries):
        """Queue imported encoder lineages onto this process's hubs
        (index-aligned with the predecessor's hub list); the encode
        threads adopt between frames."""
        hubs = _drain_sessions()
        for ent in entries or []:
            try:
                idx = int(ent.get("index") or 0)
            except (TypeError, ValueError):
                idx = 0
            if 0 <= idx < len(hubs) and \
                    hasattr(hubs[idx], "adopt_handoff"):
                hubs[idx].adopt_handoff(ent.get("state") or {})

    if hmgr.enabled:
        from ..obs import flight as obsf
        obsf.register_state_provider("handoff", hmgr.snapshot)
        # restart-in-place successor: consume whatever a predecessor
        # spooled before we started accepting /ws joins
        _adopt_imported(hmgr.load_spool())
        if hmgr.sock_path:
            async def _start_handoff_sock(app_):
                app_["handoff_sock_srv"] = await rhandoff.serve_socket(
                    hmgr, _adopt_imported)

            async def _stop_handoff_sock(app_):
                srv = app_.get("handoff_sock_srv")
                if srv is not None:
                    srv.close()

            app.on_startup.append(_start_handoff_sock)
            app.on_cleanup.append(_stop_handoff_sock)

    async def handoff_migrate(reason: str = "migrate") -> dict:
        """Drain-to-migrate: freeze the encode threads, export session
        + wire snapshots, spool/stream them, then hand every connected
        client its resume token.  A transfer failure falls back to the
        legacy shed — accounted as ``handoff_failed`` and flight-dumped
        (``handoff-failed`` is a trigger kind)."""
        import asyncio

        from ..obs import events as obsev

        if not hmgr.enabled:
            begin_drain(reason)
            return {"enabled": False, "migrated": 0}
        # refuse new joins, but QUIETLY: clients get migrate tokens
        # below, not the pre-connect-elsewhere shed broadcast
        if drain.begin(reason):
            obsev.emit("drain", reason=reason, mode="migrate")
        loop = asyncio.get_running_loop()
        hubs = _drain_sessions()
        t0 = time.monotonic()

        def _freeze_and_export():
            # export_state walks encoder internals: park the encode
            # threads first (stop() joins; this runs in the executor so
            # the event loop keeps serving in-flight sockets meanwhile)
            for h in hubs:
                try:
                    h.stop()
                except Exception:
                    log.exception("session stop failed during handoff")
            return hmgr.export(hubs)

        snapshot = await loop.run_in_executor(None, _freeze_and_export)
        try:
            if hmgr.sock_path:
                await rhandoff.send_over_socket(hmgr.sock_path, snapshot)
                dest = hmgr.sock_path
            else:
                dest = await loop.run_in_executor(
                    None, hmgr.spool, snapshot)
        except Exception as e:
            log.exception("handoff transfer failed; falling back to "
                          "legacy drain-and-shed")
            obsev.emit("handoff-failed", reason="transfer_error",
                       error=str(e))
            if app["fleet"] is not None:
                app["fleet"].account_drain("handoff_failed")
            for sess in hubs:
                subs = getattr(sess, "_subscribers", None)
                if subs is not None:
                    subs.broadcast_all([("draining", reason)])
            return {"enabled": True, "migrated": 0, "failed": True}
        notified = hmgr.notify_all(retry_after_s=0.5)
        obsev.emit("handoff-export",
                   sessions=len(snapshot["sessions"]),
                   conns=len(snapshot["conns"]), notified=notified,
                   dest=dest,
                   ms=round((time.monotonic() - t0) * 1e3, 1))
        return {"enabled": True, "migrated": len(snapshot["conns"]),
                "sessions": len(snapshot["sessions"]),
                "notified": notified, "dest": dest}

    app["handoff_migrate"] = handoff_migrate

    async def drain_handler(request):
        if hmgr.enabled:
            if drain.draining:           # idempotent like legacy drain
                body = drain.snapshot()
                body["initiated"] = False
                return web.json_response(body)
            result = await handoff_migrate("POST /debug/drain")
            body = drain.snapshot()
            body["initiated"] = True
            body["handoff"] = result
            return web.json_response(body)
        fresh = begin_drain("POST /debug/drain")
        body = drain.snapshot()
        body["initiated"] = fresh
        return web.json_response(body)

    async def drain_status(request):
        return web.json_response(drain.snapshot())

    async def handoff_status(request):
        return web.json_response(hmgr.snapshot())

    # Read once at app build (sync context): serving it from the async
    # handler re-read the file from disk per request on the event loop
    # (analysis finding async-blocking-call server.py/index).
    client_html = _client_html(cfg)

    async def index(request):
        return web.Response(text=client_html, content_type="text/html")

    async def manifest(request):
        return web.json_response({
            "name": cfg.pwa_app_name,
            "short_name": cfg.pwa_app_short_name,
            "start_url": cfg.pwa_start_url,
            "display": "standalone",
            "background_color": "#000000",
            "theme_color": "#000000",
        })

    async def service_worker(request):
        # PWA parity: the reference rewrites manifest AND service worker
        # (selkies-gstreamer-entrypoint.sh:27-38).  Network-first with an
        # offline shell fallback; cache name tracks the configured app so
        # renames invalidate stale shells.
        cache = f"tpu-desktop-{cfg.pwa_app_short_name}-v1".replace(" ", "-")
        js = (
            'const CACHE = %r;\n'
            'self.addEventListener("install", (e) => {\n'
            '  e.waitUntil(caches.open(CACHE).then(\n'
            '    (c) => c.addAll(["%s", "manifest.json"])));\n'
            '  self.skipWaiting();\n'
            '});\n'
            'self.addEventListener("activate", (e) => {\n'
            '  e.waitUntil(caches.keys().then((ks) => Promise.all(\n'
            '    ks.filter((k) => k !== CACHE)\n'
            '      .map((k) => caches.delete(k)))));\n'
            '});\n'
            'self.addEventListener("fetch", (e) => {\n'
            '  if (e.request.method !== "GET") return;\n'
            '  e.respondWith(fetch(e.request).catch(\n'
            '    () => caches.match(e.request)));\n'
            '});\n' % (cache, cfg.pwa_start_url))
        return web.Response(text=js, content_type="application/javascript")

    async def turn(request):
        return web.json_response(ice_servers(cfg))

    async def stats(request):
        if manager is not None:
            payload = manager.stats_summary()
        else:
            payload = {"session": (session.stats_summary()
                                   if session is not None else None)}
        if supervisor is not None:
            payload["programs"] = supervisor.status()
        # /stats is a JSON view over the same registry /metrics exposes
        # (one source of truth for dashboards and the web client alike)
        payload["metrics"] = REGISTRY.snapshot()
        # the serving-budget ledger (obs/budget): per-stage p50s with
        # link cost separated + SLO verdicts — the same shared emitter
        # /debug/budget?format=json renders and bench.py snapshots
        from ..obs.budget import serving_budget_block
        payload["serving_budget"] = serving_budget_block()
        if app["degrade"] is not None:
            payload["degrade"] = app["degrade"].snapshot()
        if app["fleet"] is not None:
            payload["fleet"] = app["fleet"].snapshot()
        return web.json_response(payload)

    async def ws_handler(request):
        import asyncio

        ws = web.WebSocketResponse(heartbeat=20.0, max_msg_size=0)
        await ws.prepare(request)
        if drain.draining:
            # stop admitting: the client gets an explicit reason (so it
            # can pre-connect to another replica) instead of a refused
            # socket it would retry against this same dying pod
            await ws.send_json({"type": "draining",
                                "reason": drain.reason or "drain"})
            await ws.close()
            return ws
        # handoff resume (resilience/handoff): a client carrying a
        # predecessor's resume token redeems it here — single-use,
        # TTL-bounded.  An unknown/expired token degrades to a normal
        # join (counted on dngd_handoff_resume_total), never a refusal.
        resume_entry = None
        resume_token = request.query.get("resume")
        if resume_token and hmgr.enabled:
            resume_entry = hmgr.claim(resume_token)
        # fleet admission: every join is admitted, queued (acquire
        # blocks up to the queue timeout), or cleanly rejected with a
        # retry_after_s the client backs off against — never a silent
        # hang, never an unexplained refusal.  A migrating-in session
        # bypasses both gates at its recorded tier: it already held a
        # slot on the predecessor.
        fleet = app["fleet"]
        adm = None
        if fleet is not None:
            if resume_entry is not None:
                try:
                    mtier = int(resume_entry.get("tier") or 0)
                except (TypeError, ValueError):
                    mtier = 0
                adm = fleet.admit_migration(tier=mtier)
            else:
                try:
                    tier = int(request.query.get("tier", "0"))
                except ValueError:
                    tier = 0
                adm = await fleet.acquire(tier=tier)
            if not adm.admitted:
                await ws.send_json(adm.payload())
                await ws.close()
                return ws
        sess = resolve_session(request)
        if sess is None:
            if adm is not None:
                fleet.release(adm)
            await ws.send_json({"type": "error",
                                "reason": "no active session"})
            await ws.close()
            return ws
        if adm is not None:
            # shedding path: the scheduler evicts THIS connection with a
            # busy/retry_after_s answer the client treats like any other
            # rejection (reconnect with jittered backoff; the hub keeps
            # its encoder checkpoint, so re-admission resumes the stream
            # from a recovery IDR — shed, not killed)
            def _evict(retry_after: float, _ws=ws) -> None:
                async def _go():
                    try:
                        await _ws.send_json({
                            "type": "busy", "reason": "shed",
                            "retry_after_s": round(retry_after, 2),
                            "reconnect": True})
                        await _ws.close()
                    except Exception:
                        pass
                _spawn_bg(_go())

            adm.evict = _evict
        # from here on the admission slot is held: EVERY exit — a client
        # that vanished mid-handshake included — must release it, or
        # churn slowly eats capacity with dead admissions
        try:
            hello = (sess.hello() if hasattr(sess, "hello") else
                     {"type": "hello", "codec": sess.codec_name,
                      # the codec string the muxer derives from the SPS
                      # it was given (web/mp4.py): Main for CABAC; the
                      # literal is for a session double without one
                      "mime": getattr(sess, "mime", None) or getattr(
                          getattr(sess, "muxer", None), "mime",
                          'video/mp4; codecs="avc1.42E01E"'),
                      "width": sess.source.width,
                      "height": sess.source.height})
            hello["audio"] = audio is not None
            # every connection joins the handoff set: the resume token
            # in the hello is what the client presents to the successor
            # if THIS process is the one that dies next
            handoff_token = None
            if hmgr.enabled:
                def _notify_migrate(tok, retry_s, _ws=ws):
                    async def _go():
                        try:
                            await _ws.send_json({
                                "type": "migrate", "resume": tok,
                                "retry_after_s": round(retry_s, 2)})
                        except Exception:
                            pass
                    _spawn_bg(_go())

                handoff_token = hmgr.register(
                    sid=(adm.sid if adm is not None
                         else f"ws-{request.remote or 'local'}"),
                    tier=(adm.tier if adm is not None else 0),
                    notify=_notify_migrate)
                hello["resume"] = handoff_token
            if resume_entry is not None:
                hello["resumed"] = True
                from ..obs import events as obsev
                obsev.emit("handoff-resume",
                           session=resume_entry.get("sid"),
                           tier=resume_entry.get("tier"))
            await ws.send_json(hello)
            if resume_entry is not None and hasattr(sess, "request_idr"):
                # exactly one recovery IDR on resume: the rate-limited
                # request_idr dedupes a reconnect storm into one grant
                sess.request_idr("handoff")
            # Per-hub injectors prevent cross-session input leaks: a
            # client on a synthetic session must not drive session 0's
            # real desktop.
            sess_injector = getattr(sess, "injector", None)
            if sess_injector is None and manager is None:
                sess_injector = injector
            queue = sess.subscribe()
            # trust boundary (resilience/ingress): one abuse governor +
            # one outstanding-probe window per connection.  EVICT rides
            # the same busy/shed payload as scheduler shedding (without
            # the reconnect invitation); the "shed" event the budget
            # emits on the way dumps the flight recorder.
            probes = ringress.ProbeWindow()

            def _ingress_evict(bud, reason, _ws=ws):
                async def _go():
                    try:
                        await _ws.send_json({
                            "type": "busy", "reason": "shed",
                            "retry_after_s": 30.0, "reconnect": False})
                        await _ws.close()
                    except Exception:
                        pass
                _spawn_bg(_go())

            budget = ringress.PeerBudget(
                f"ws-{request.remote or 'local'}",
                on_evict=_ingress_evict)
            sender = asyncio.ensure_future(_pump_media(ws, queue, probes))
            loop = asyncio.get_running_loop()
            # per-connection state: WebRTC peer + taps, MSE queue handle
            sockname = (request.transport.get_extra_info("sockname")
                        if request.transport is not None else None)
            from .turn import server_turn_config
            conn = {"peer": None, "on_au": None, "on_audio": None,
                    "queue": queue, "audio": audio,
                    "budget": budget, "probes": probes,
                    "injector": sess_injector,
                    "advertise_ip": (sockname[0] if sockname
                                     else "127.0.0.1"),
                    "turn": server_turn_config(cfg),
                    # the client's address as this server sees it — a
                    # TURN permission for it covers the common NAT case
                    # even before any trickled candidates arrive
                    "client_ip": request.remote,
                    # wire continuity from the predecessor's peer (same
                    # SSRC / seq frontier / ROC / SCTP counters), applied
                    # to the successor peer before its offer is answered
                    "resume_wire": (resume_entry or {}).get("wire"),
                    # once a peer exists, its wire exporter registers
                    # under this connection's token so a FUTURE migrate
                    # snapshots it
                    "handoff_attach": (
                        (lambda fn, _t=handoff_token:
                         hmgr.attach_wire(_t, fn))
                        if handoff_token is not None else None)}
            try:
                async for msg in ws:
                    if msg.type == WSMsgType.TEXT:
                        if joystick is not None and msg.data.startswith("j"):
                            joystick.handle_message(msg.data)
                            continue
                        await _handle_client_msg(msg.data, ws, sess,
                                                 sess_injector, loop, conn)
                    elif msg.type in (WSMsgType.CLOSE, WSMsgType.ERROR):
                        break
            finally:
                if handoff_token is not None:
                    # a connection that closes normally is NOT migrated;
                    # one closing because migrate() just notified it has
                    # already been snapshotted — detach is accounting
                    # either way
                    hmgr.detach(handoff_token)
                _teardown_peer(conn, sess)
                sess.unsubscribe(queue)
                sender.cancel()
                budget.close()
        finally:
            if adm is not None:
                # slot freed -> the scheduler promotes the next queued
                # joiner (an evicted session releases here too, once its
                # socket close lands)
                fleet.release(adm)
        return ws

    async def audio_handler(request):
        import asyncio

        ws = web.WebSocketResponse(heartbeat=20.0, max_msg_size=0)
        await ws.prepare(request)
        if drain.draining:
            # same admission gate as /ws: a draining pod must not bind
            # a fresh audio track it will drop within the grace window
            await ws.send_json({"type": "draining",
                                "reason": drain.reason or "drain"})
            await ws.close()
            return ws
        if audio is None:
            await ws.send_json({"type": "error", "reason": "no audio"})
            await ws.close()
            return ws
        await ws.send_json(audio.header)
        queue = audio.subscribe()

        async def pump():
            try:
                while True:
                    await ws.send_bytes(await queue.get())
            except (ConnectionError, asyncio.CancelledError):
                pass

        sender = asyncio.ensure_future(pump())
        try:
            # Drain incoming frames so the close handshake is processed —
            # a send-only handler would hang the client's close forever.
            async for _ in ws:
                pass
        finally:
            sender.cancel()
            audio.unsubscribe(queue)
        return ws

    # A wedged device RPC leaves the encode thread alive but frameless —
    # the exact failure a liveness probe must catch on a flaky
    # interconnect — so health = thread alive AND frames not stale.
    # (Before the first frame the codec may still be jit-compiling;
    # that window is covered by the probe's initialDelaySeconds.)
    # HEALTHZ_STALL_S; default 30 s — the reference's noVNC heartbeat
    # is 10 s (entrypoint.sh:124).
    STALL_S = cfg.healthz_stall_s

    def _loop_healthy(obj, stats) -> bool:
        import time as _time

        thread = getattr(obj, "_thread", None)
        if thread is not None and not thread.is_alive():
            return False
        # A fresh codec build may be jit-compiling for longer than the
        # stall threshold (e.g. right after a resize): grace period.
        if _time.monotonic() < getattr(obj, "_healthz_grace_until", 0.0):
            return True
        # Prefer the loop's progress tick (refreshed on frame delivery
        # and on legitimate idleness, but NOT while spinning on encode
        # failures or wedged inside a device RPC).
        tick = getattr(obj, "_last_tick", None)
        if tick is not None and thread is not None:
            return (_time.monotonic() - tick) <= STALL_S
        if stats is not None and thread is not None:
            age = stats.last_frame_age_s()
            if age is not None and age > STALL_S:
                return False
        return True

    async def clipboard(request):
        """Desktop clipboard -> client (GET); runs xclip off-loop."""
        import asyncio as aio

        if injector is None:
            return web.json_response({"text": None})
        loop = aio.get_running_loop()
        text = await loop.run_in_executor(None, injector.read_clipboard)
        return web.json_response({"text": text})

    async def healthz(request):
        """Liveness with a degraded/unhealthy distinction (ISSUE 3):
        a pod shedding load through the degradation ladder is doing its
        JOB — it answers 200 with ``state: "degraded"`` so a K8s
        liveness probe never kills it for degrading correctly; only a
        genuinely wedged loop (stalled frames, dead thread) answers
        503 ``unhealthy``.  A FULL pod (fleet admission at capacity,
        ISSUE 6) is likewise healthy — 200 ``state: "at_capacity"`` so
        a capacity-aware balancer can route new joins elsewhere without
        liveness ever killing a pod for being popular."""
        healthy = True
        if manager is not None:
            # one encode thread feeds every hub; any hub's stats show it
            hub = manager.session(0)
            healthy = _loop_healthy(manager,
                                    getattr(hub, "stats", None))
        elif session is not None:
            healthy = _loop_healthy(session,
                                    getattr(session, "stats", None))
        ctl = app["degrade"]
        degraded = ctl is not None and ctl.level > 0
        fleet = app["fleet"]
        at_capacity = fleet is not None and fleet.at_capacity
        # draining stays 200: the pod is doing its job (flushing) and
        # liveness must not kill it before the grace period; the state
        # field lets a readiness-aware probe pull it from the Service
        state = ("unhealthy" if not healthy
                 else "draining" if drain.draining
                 else "at_capacity" if at_capacity
                 else "degraded" if degraded else "ok")
        body = {"ok": healthy, "state": state}
        if degraded:
            body["degrade"] = {"level": ctl.level, "step": ctl.step_name}
        if at_capacity:
            body["fleet"] = {"active": fleet.active,
                             "capacity": fleet.capacity,
                             "queued": fleet.queued,
                             "retry_after_s": round(
                                 fleet.retry_after_s(), 2)}
        return web.json_response(body, status=200 if healthy else 503)

    async def fleet_status(request):
        """``/debug/fleet``: the admission scheduler's live picture —
        capacity model inputs, active/queued sessions, backpressure
        level, shed/migration counts.  Text by default, ``?format=json``
        for the structured block (same shape the fleet bench reports)."""
        fleet = app["fleet"]
        if fleet is None:
            return web.json_response({"enabled": False})
        if request.query.get("format") == "json":
            snap = fleet.snapshot()
            snap["enabled"] = True
            return web.json_response(snap)
        from ..fleet.scheduler import render_fleet_text
        return web.Response(text=render_fleet_text(fleet),
                            content_type="text/plain")

    app.router.add_get("/", index)
    app.router.add_get("/index.html", index)
    app.router.add_get("/manifest.json", manifest)
    app.router.add_get("/sw.js", service_worker)
    app.router.add_get("/turn", turn)
    app.router.add_get("/stats", stats)
    app.router.add_get("/clipboard", clipboard)
    app.router.add_get("/healthz", healthz)
    add_obs_routes(app)                  # /metrics + /debug/trace
    rfaults.add_fault_routes(app)        # /debug/faults (POST env-gated)
    # graceful drain: GET = status, POST = initiate (behind basic auth
    # like every state-mutating route; the k8s preStop hook carries the
    # credential — see deploy/xgl-tpu.yml)
    app.router.add_get("/debug/drain", drain_status)
    app.router.add_post("/debug/drain", drain_handler)
    # handoff status (read-only): live registrations, pending resume
    # tokens, export/import/failure counts
    app.router.add_get("/debug/handoff", handoff_status)
    # fleet admission report (read-only, auth-exempt like /debug/budget)
    app.router.add_get("/debug/fleet", fleet_status)
    app.router.add_get("/ws", ws_handler)
    app.router.add_get("/audio", audio_handler)
    if session is not None:
        # stock selkies web-client signaling (role-inverted offer flow;
        # the shared injector feeds its SCTP input channels)
        from .selkies_shim import register_selkies_routes
        register_selkies_routes(app, cfg, session, audio,
                                injector=injector)
    return app


async def _pump_media(ws: web.WebSocketResponse, queue,
                      probes=None) -> None:
    import asyncio

    from ..obs import journey as obsj

    try:
        while True:
            item = await queue.get()  # ("kind", data[, keyframe[, fid]])
            kind, data = item[0], item[1]
            spec = rfaults.fire("ws_send_stall")
            if spec is not None:
                # simulated wedged client/socket: the queue behind this
                # pump fills, exercising eviction + slow-subscriber
                # eviction exactly as a real stall would
                await asyncio.sleep(
                    float(spec.get("delay_ms", 1000.0)) / 1e3)
            if kind == "evicted":
                # SubscriberSet gave up on this queue (sustained slow
                # streak); tell the client why, then close — reconnect
                # is immediate and re-admits with a fresh IDR-gated queue
                await ws.send_json({"type": "evicted", "reason": data,
                                    "reconnect": True})
                await ws.close()
                return
            if kind == "draining":
                # the server is going away: advise the client to pre-
                # connect elsewhere, but KEEP this socket flushing —
                # in-flight frames deliver until the process exits
                await ws.send_json({"type": "draining", "reason": data})
                continue
            if kind == "json":            # mid-stream control (e.g. resize)
                await ws.send_json(data)
            else:
                # glass-to-glass probe: every DNGD_JOURNEY_SAMPLE-th
                # frame's fragment is preceded by an fprobe the client
                # echoes back as {"type": "ack", "id": fid} — the
                # journey's client-side closure (obs/journey)
                if (kind == "frag" and len(item) > 3 and item[3]
                        and obsj.probe_due(item[3])):
                    # record the outstanding fid BEFORE the probe can
                    # race its own ack: only ids in this window may
                    # close journeys (resilience/ingress ack gating)
                    if probes is not None:
                        probes.add(item[3])
                    await ws.send_json({"type": "fprobe", "id": item[3]})
                await ws.send_bytes(data)
                if kind == "frag" and len(item) > 4 and item[4]:
                    M_WS_SEND_MS.observe(
                        (time.perf_counter() - item[4]) * 1e3)
    except Exception:
        pass


def _teardown_peer(conn: dict, session) -> None:
    if conn.get("on_au") is not None and hasattr(session,
                                                 "remove_au_listener"):
        session.remove_au_listener(conn["on_au"])
        conn["on_au"] = None
    audio = conn.get("audio")
    if conn.get("on_audio") is not None and audio is not None:
        audio.remove_listener(conn["on_audio"])
        conn["on_audio"] = None
    if conn.get("peer") is not None:
        conn["peer"].close()
        conn["peer"] = None


async def _handle_offer(msg: dict, ws, session, conn: dict) -> None:
    """SDP offer -> first-party WebRTC media plane when the session can
    feed it, else the MSE-over-WS capability statement (the fallback the
    client already speaks)."""
    sdp_text = msg.get("sdp", "")
    codec_name = getattr(session, "codec_name", "")
    rtc_codec = ("H264" if codec_name.startswith("h264") else
                 "VP8" if codec_name.startswith("vp8") else None)
    can_rtc = (conn is not None and sdp_text and rtc_codec is not None
               and hasattr(session, "add_au_listener"))
    if not can_rtc:
        await ws.send_json({"type": "answer", "transport": "mse-ws"})
        return
    audio = conn.get("audio")
    rtc_audio = audio is not None and getattr(audio, "format", "") == "opus"
    peer = None
    try:
        from ..webrtc.peer import WebRtcPeer

        _teardown_peer(conn, session)        # renegotiation replaces peer
        peer = WebRtcPeer(clock=getattr(session, "clock", None),
                          video_codec=rtc_codec,
                          sps=getattr(getattr(session, "muxer", None),
                                      "sps", None),
                          advertise_ip=conn["advertise_ip"],
                          with_audio=rtc_audio,
                          turn=conn.get("turn"))
        # RTCP journey closure: the peer maps RR extended-highest-seq
        # back to frame pts and closes through the session's book
        peer.journeys = getattr(session, "journeys", None)
        # the connection's abuse governor covers this peer's RTCP/SCTP/
        # DCEP ingest too, and stats-channel acks gate on the same
        # outstanding-probe window as /ws acks (resilience/ingress)
        peer.set_ingress_budget(conn.get("budget"))
        peer.ingress_probes = conn.get("probes")
        # data-channel input (if the offer carries m=application): same
        # binder as the stock-selkies shim, so both clients' channel
        # input exercises one path
        from .selkies_shim import attach_input_channels
        import asyncio
        attach_input_channels(peer, session, conn.get("injector"),
                              loop=asyncio.get_running_loop())
        # resumed connection (resilience/handoff): seed the predecessor
        # peer's wire continuity BEFORE the offer — the answer SDP must
        # advertise the same SSRCs the client was already decoding
        if conn.get("resume_wire"):
            peer.import_wire(conn["resume_wire"])
            conn["resume_wire"] = None       # single-shot
        answer_sdp = await peer.handle_offer(sdp_text)
        if conn.get("client_ip"):
            # cover the pre-trickle window: the client's checks will come
            # from (at least) the address its websocket came from
            await peer.add_remote_candidate_ip(conn["client_ip"])
    except Exception as e:
        from ..webrtc.sdp import SdpError
        if peer is not None:
            # release the socket AND the peer's per-ssrc metric series —
            # a leaked half-built peer would be scraped stale forever
            peer.close()
        if isinstance(e, SdpError):
            # hostile/corrupt offer rejected at the trust boundary: a
            # clean signaling error + violation score, not a stack
            # trace and not a silent mse-ws downgrade the client
            # would then negotiate against forever
            log.warning("offer rejected at trust boundary: %s (%s)",
                        e.reason, e)
            budget = conn.get("budget")
            if budget is not None:
                budget.violation(e.reason, weight=5.0)
            await ws.send_json({"type": "error", "reason": e.reason})
            return
        log.exception("webrtc offer failed; answering mse-ws")
        await ws.send_json({"type": "answer", "transport": "mse-ws"})
        return
    conn["peer"] = peer
    # this peer's wire state becomes migratable: if THIS process drains
    # next, its RTP/SRTP/SCTP frontier rides the snapshot
    if conn.get("handoff_attach") is not None:
        conn["handoff_attach"](peer.export_wire)

    def on_au(au, keyframe, pts):
        peer.send_video_au(au, pts)

    conn["on_au"] = on_au
    session.add_au_listener(on_au)
    if rtc_audio:
        def on_audio(pts, packet):
            peer.send_audio(packet, pts)

        conn["on_audio"] = on_audio
        audio.add_listener(on_audio)
    # first IDR right when SRTP comes up so video starts instantly
    if hasattr(session, "request_keyframe"):
        peer.on_ready = session.request_keyframe
    # PLI/FIR land on the session's rate-limited request_idr so a
    # client's keyframe storm dedupes against the degrade ladder's IDR
    # rung and the collect-failure resync (webrtc/feedback)
    from .session import keyframe_requester
    peer.on_keyframe_request = keyframe_requester(session)
    # media now rides SRTP; stop duplicating fMP4 frags to this client
    session.unsubscribe(conn["queue"])
    await ws.send_json({"type": "answer", "transport": "webrtc",
                        "sdp": answer_sdp})


async def _handle_client_msg(text: str, ws, session, injector: Injector,
                             loop=None, conn: Optional[dict] = None):
    """Control-plane messages: JSON signaling or compact input strings."""
    budget = conn.get("budget") if conn is not None else None
    if text.startswith("{"):
        if budget is not None and not budget.allow_nonmedia():
            # quarantined: control-plane JSON drops, and a peer that
            # keeps hammering THROUGH its cooldown climbs toward the
            # evict rung instead of parking at quarantine forever
            budget.violation("quarantine_ingest", weight=0.2)
            return
        if budget is not None and not budget.charge("signal"):
            # over the signaling rate: drop (already counted); raw
            # input below keeps its own parse hardening + bounded queue
            return
        try:
            msg = json.loads(text)
        except ValueError:
            if budget is not None:
                budget.violation("signal_bad_json")
            return
        if not isinstance(msg, dict):
            if budget is not None:
                budget.violation("signal_bad_json", weight=0.5)
            return
        mtype = msg.get("type")
        if mtype == "ping":
            await ws.send_json({"type": "pong", "t": msg.get("t")})
        elif mtype == "ack":
            # client ack of a sampled frame probe: closes the frame's
            # journey at SERVER receipt time (no clock sync needed; the
            # measured g2g honestly includes the ack's uplink).  Only
            # fids THIS connection was probed with may close — spoofed,
            # replayed or future ids would otherwise fabricate the g2g
            # p50 the SLO verdict admits against.
            if budget is not None and not budget.charge("ack"):
                return
            try:
                fid = int(msg.get("id", 0))
            except (TypeError, ValueError):
                if budget is not None:
                    budget.violation("ack_spoof", weight=0.5)
                return
            probes = conn.get("probes") if conn is not None else None
            if probes is not None and not probes.take(fid):
                if budget is not None:
                    budget.violation("ack_spoof", weight=0.5)
                return
            book = getattr(session, "journeys", None)
            if book is not None:
                book.close(fid, method="client")
        elif mtype == "offer":
            await _handle_offer(msg, ws, session, conn)
        elif mtype == "candidate":
            # ICE-lite: the peer address comes from checks; but when our
            # media is relayed, the TURN server drops a new address's
            # checks until a permission exists for it (RFC 5766 §9)
            cand = msg.get("candidate") or ""
            if isinstance(cand, dict):
                cand = cand.get("candidate", "") or ""
            peer = conn.get("peer") if conn is not None else None
            parts = cand.split() if isinstance(cand, str) else []
            if peer is not None and len(parts) >= 5:
                await peer.add_remote_candidate_ip(parts[4])
        elif mtype == "stats":
            data = session.stats_summary()
            if conn is not None and conn.get("peer") is not None:
                data["webrtc"] = conn["peer"].stats()
            await ws.send_json({"type": "stats", "data": data})
        return
    # A bound WebRTC peer serializes ALL input for this connection
    # through its per-peer worker (selkies_shim.attach_input_channels):
    # without it, events spanning the WS -> data-channel switchover
    # would be injected by two concurrent executor hops out of order.
    peer = conn.get("peer") if conn is not None else None
    enqueue = getattr(peer, "input_enqueue", None)
    if enqueue is not None:
        enqueue(text)
        return
    await handle_input_text(text, session, injector, loop)


async def handle_input_text(text: str, session,
                            injector: Optional[Injector],
                            loop=None) -> None:
    """One compact CSV input message -> injection + codec control.

    The SINGLE input path: the /ws handler and the SCTP data-channel
    binders (selkies_shim.attach_input_channels) both land here, so a
    keystroke arriving over either transport reaches the X backend
    through identical parsing, hardening and executor offload."""
    if injector is None:
        # Session without an input path (e.g. a synthetic batch session):
        # still honor the codec-control messages below.
        from .input import parse_message
        event = parse_message(text)
    # Injection backends may block (xdotool subprocess): keep them off the
    # event loop so one hung X call can't stall media delivery to everyone.
    elif loop is not None:
        event = await loop.run_in_executor(None, injector.handle_message,
                                           text)
    else:
        event = injector.handle_message(text)
    if event is not None and event.get("type") == "keyframe":
        # session-level request (wakes an idle encode loop) when offered
        if hasattr(session, "request_keyframe"):
            session.request_keyframe()
        else:
            session.encoder.request_keyframe()
    elif event is not None and event.get("type") == "resize":
        ok = (session.request_resize(event["width"], event["height"])
              if hasattr(session, "request_resize") else False)
        if not ok:
            log.info("resize to %dx%d rejected (WEBRTC_ENABLE_RESIZE off "
                     "or source not resizable)",
                     event["width"], event["height"])


def _ssl_context(cfg: Config) -> Optional[ssl.SSLContext]:
    if not cfg.enable_https_web:
        return None
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(cfg.https_web_cert, cfg.https_web_key)
    return ctx


async def serve(cfg: Config, session=None, injector=None,
                supervisor=None, joystick=None, audio=None,
                manager=None) -> web.AppRunner:
    runner = web.AppRunner(make_app(cfg, session, injector, supervisor,
                                    joystick, audio, manager))
    await runner.setup()
    site = web.TCPSite(runner, cfg.listen_addr, cfg.listen_port,
                       ssl_context=_ssl_context(cfg))
    await site.start()
    return runner


def bound_port(runner: web.AppRunner) -> int:
    for site in runner.sites:
        server = site._server  # noqa: SLF001
        if server and server.sockets:
            return server.sockets[0].getsockname()[1]
    raise RuntimeError("server not bound")
