"""Handoff smoke (ISSUE 19, CI ``handoff-smoke`` step): a REAL rolling
restart across two OS processes.  Generation A boots through the actual
CLI entrypoint (``python -m ...web.server_main``), a websocket client
joins and collects its resume token, then A gets the same SIGTERM k8s
sends on pod deletion.  With ``DNGD_HANDOFF_DIR`` set the drain path
migrates instead of shedding: A spools a versioned session snapshot,
pushes a ``migrate`` message to the client, and exits.  Generation B
boots against the same spool directory, imports the snapshot at serve
time, and must honour the resume token — ``resumed: true`` in the hello,
``dngd_handoff_*`` families visible on /metrics, imports counted on
/debug/handoff.

Everything here goes through the public surface (subprocess + HTTP +
websocket); no in-process shortcuts, so this is the closest a test gets
to the deploy/xgl-tpu.yml preStop flow without a cluster.  Set
``DNGD_HANDOFF_REPORT=<path>`` (CI does) to drop a JSON report of the
run for the build artifact.
"""

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time

import aiohttp
import pytest

BOOT_TIMEOUT_S = 240          # jax import + first compile in the child
EXIT_TIMEOUT_S = 60           # SIGTERM -> spool -> flush -> exit


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(port: int, spool_dir: str) -> dict:
    env = dict(os.environ)
    # no X on CI boxes: force the synthetic-source fallback
    env.pop("DISPLAY", None)
    # keep the smoke test off any chip; the compile cache stays where
    # utils/jaxcache puts it (JAX_COMPILATION_CACHE_DIR when the caller
    # set it, else the fixed in-checkout directory)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "LISTEN_ADDR": "127.0.0.1",
        "LISTEN_PORT": str(port),
        "SIZEW": "128", "SIZEH": "96", "REFRESH": "30",
        "ENABLE_BASIC_AUTH": "false",
        "ENCODER_PREWARM": "false",
        "ENCODER_GOP": "120",
        "DEGRADE_ENABLE": "false",
        "FLEET_ENABLE": "true",
        "DNGD_HANDOFF_DIR": spool_dir,
        "DNGD_HANDOFF_TOKEN_TTL_S": "600",
        # fast exit after the migrate flush — the snapshot is already
        # spooled by then, so a short grace only trims test wall-clock
        "DNGD_DRAIN_GRACE_S": "1",
    })
    return env


def _spawn(port: int, spool_dir: str, logfile) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-m",
         "docker_nvidia_glx_desktop_tpu.web.server_main"],
        env=_child_env(port, spool_dir),
        stdout=logfile, stderr=subprocess.STDOUT)


async def _wait_healthy(http: aiohttp.ClientSession, port: int,
                        proc: subprocess.Popen, log_path) -> None:
    deadline = time.monotonic() + BOOT_TIMEOUT_S
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise AssertionError(
                "server died during boot; log:\n"
                + log_path.read_text()[-2000:])
        try:
            async with http.get(
                    f"http://127.0.0.1:{port}/healthz") as r:
                if r.status == 200:
                    return
        except aiohttp.ClientError:
            pass
        await asyncio.sleep(0.5)
    raise AssertionError("server never became healthy; log:\n"
                         + log_path.read_text()[-2000:])


def _write_report(report: dict) -> None:
    path = os.environ.get("DNGD_HANDOFF_REPORT")
    if path:
        with open(path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)


@pytest.mark.slow
def test_two_process_sigterm_migrate(tmp_path):
    spool = tmp_path / "spool"
    spool.mkdir()
    port_a, port_b = _free_port(), _free_port()
    log_a = tmp_path / "gen-a.log"
    log_b = tmp_path / "gen-b.log"
    report = {"scenario": "two_process_sigterm_migrate"}

    async def go():
        proc_a = proc_b = None
        try:
            # ---- generation A: boot, join, collect the resume token
            proc_a = _spawn(port_a, str(spool), log_a.open("wb"))
            async with aiohttp.ClientSession() as http:
                await _wait_healthy(http, port_a, proc_a, log_a)
                ws = await http.ws_connect(
                    f"http://127.0.0.1:{port_a}/ws")
                hello = json.loads((await ws.receive()).data)
                assert hello.get("type") == "hello", hello
                token = hello.get("resume")
                assert token, ("handoff disabled on A "
                               "(no resume token in hello)")
                report["token_issued"] = True

                # ---- the k8s pod-deletion path: SIGTERM, not an RPC
                os.kill(proc_a.pid, signal.SIGTERM)
                migrate = None
                deadline = time.monotonic() + EXIT_TIMEOUT_S
                while time.monotonic() < deadline:
                    msg = await ws.receive(
                        timeout=max(1.0, deadline - time.monotonic()))
                    if msg.type == aiohttp.WSMsgType.TEXT:
                        data = json.loads(msg.data)
                        if data.get("type") == "migrate":
                            migrate = data
                            break
                    elif msg.type in (aiohttp.WSMsgType.CLOSED,
                                      aiohttp.WSMsgType.CLOSE,
                                      aiohttp.WSMsgType.ERROR):
                        break
                assert migrate is not None, (
                    "no migrate message before the socket closed; log:\n"
                    + log_a.read_text()[-2000:])
                token = migrate.get("resume") or token
                report["migrate_received"] = True
                await ws.close()
            rc = proc_a.wait(timeout=EXIT_TIMEOUT_S)
            report["predecessor_exit_code"] = rc
            assert rc == 0, ("predecessor exited dirty; log:\n"
                             + log_a.read_text()[-2000:])
            spooled = list(spool.glob("handoff-*.json"))
            assert spooled, "predecessor exited without spooling"

            # ---- generation B: same spool dir, must import + resume
            proc_b = _spawn(port_b, str(spool), log_b.open("wb"))
            async with aiohttp.ClientSession() as http:
                await _wait_healthy(http, port_b, proc_b, log_b)
                ws = await http.ws_connect(
                    f"http://127.0.0.1:{port_b}/ws?resume={token}")
                hello_b = json.loads((await ws.receive()).data)
                assert hello_b.get("type") == "hello", hello_b
                assert hello_b.get("resumed") is True, (
                    "successor did not honour the resume token; log:\n"
                    + log_b.read_text()[-2000:])
                report["resumed"] = True
                await ws.close()

                async with http.get(
                        f"http://127.0.0.1:{port_b}/metrics") as r:
                    metrics = await r.text()
                for family in ("dngd_handoff_sessions_total",
                               "dngd_handoff_resume_total"):
                    assert family in metrics, family
                report["metrics_visible"] = True
                async with http.get(
                        f"http://127.0.0.1:{port_b}/debug/handoff") as r:
                    status = await r.json()
                assert status.get("enabled") is True, status
                assert int(status.get("imports") or 0) >= 1, status
                report["successor_imports"] = int(status["imports"])
            report["ok"] = True
        finally:
            for proc in (proc_a, proc_b):
                if proc is not None and proc.poll() is None:
                    proc.kill()
                    proc.wait(timeout=10)
            _write_report(report)

    asyncio.new_event_loop().run_until_complete(
        asyncio.wait_for(go(), BOOT_TIMEOUT_S * 2 + EXIT_TIMEOUT_S * 2))
