"""System tier (SURVEY.md §4): boot the platform through the real
entrypoint on this box — no X binaries exist here, so the boot plan
degrades to the streamer program only — and verify the supervised streamer
subprocess serves the web surface end-to-end (auth, healthz, stats, client
page).  This is the M0 'container boots' bar run as a test."""

import asyncio
import os

import pytest
from aiohttp import BasicAuth, ClientSession

from docker_nvidia_glx_desktop_tpu.platform import entrypoint
from docker_nvidia_glx_desktop_tpu.platform.supervisor import Supervisor
from docker_nvidia_glx_desktop_tpu.utils.config import from_env


@pytest.mark.slow
def test_supervised_boot_serves_http(tmp_path):
    env = {
        "PASSWD": "bootpw",
        "SIZEW": "128", "SIZEH": "96", "REFRESH": "10",
        "LISTEN_ADDR": "127.0.0.1", "LISTEN_PORT": "18099",
        "SUPERVISOR_LOGDIR": str(tmp_path),
    }

    async def go():
        cfg = from_env({**os.environ, **env})
        plan = entrypoint.plan(cfg)
        # no X on this box: the delivery layer is the streamer (dbus may
        # exist); supervise just the streamer to keep the test hermetic
        assert "streamer" in plan.names(), plan.names()
        assert "vncserver" not in plan.names()

        sup = Supervisor(logdir=str(tmp_path))
        for p in plan.programs:
            if p.name != "streamer":
                continue
            # child must inherit the test geometry + run jax on CPU
            child_env = dict(p.environment or {})
            child_env.update(env)
            child_env.update({"JAX_PLATFORMS": "cpu"})
            p.environment = child_env
            sup.add(p)
        await sup.start()
        try:
            url = "http://127.0.0.1:18099"
            # Wait for the server (jax import + first compile in the child).
            async with ClientSession(auth=BasicAuth("u", "bootpw")) as s:
                ok = False
                for _ in range(240):
                    try:
                        async with s.get(f"{url}/healthz") as r:
                            if r.status == 200:
                                ok = True
                                break
                    except Exception:
                        pass
                    await asyncio.sleep(1.0)
                assert ok, ("streamer never came up; log:\n"
                            + (tmp_path / "streamer.log").read_text()[-2000:])
                # auth enforced
                async with ClientSession() as anon:
                    async with anon.get(f"{url}/stats") as r:
                        assert r.status == 401
                async with s.get(f"{url}/") as r:
                    assert r.status == 200
                    assert "TPU Desktop" in await r.text()
                # frames flowing (synthetic source; give the codec time)
                for _ in range(120):
                    async with s.get(f"{url}/stats") as r:
                        data = await r.json()
                    if (data["session"]
                            and data["session"]["frames_total"] > 0):
                        break
                    await asyncio.sleep(1.0)
                assert data["session"]["frames_total"] > 0, data
        finally:
            await sup.stop()
        # the supervisor's stop tore the child down
        assert not sup.state("streamer").running

    asyncio.new_event_loop().run_until_complete(
        asyncio.wait_for(go(), 600))
