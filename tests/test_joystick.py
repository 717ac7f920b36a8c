"""Joystick path tests: hub event packing + fan-out over the unix socket,
the wire protocol, and (when a C toolchain exists) an end-to-end check
through the LD_PRELOAD interposer binary (reference Dockerfile:473-476)."""

import asyncio
import os
import shutil
import struct
import subprocess
import sys

import pytest

from docker_nvidia_glx_desktop_tpu.web.joystick import (
    JS_EVENT_AXIS, JS_EVENT_BUTTON, JS_EVENT_INIT, JoystickHub,
    parse_js_message)


def run(coro):
    # Close the loop after use: each abandoned loop leaks its selector +
    # self-pipe fds for the rest of the pytest process, and the preload
    # e2e below is fd-budget-sensitive (it was the suite's flaky test).
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 30))
    finally:
        loop.close()


class TestProtocol:
    def test_axis(self):
        assert parse_js_message("ja,0,0.5") == {"type": "axis", "number": 0,
                                                "value": 0.5}

    def test_axis_clamped(self):
        assert parse_js_message("ja,1,7.0")["value"] == 1.0

    def test_button(self):
        assert parse_js_message("jb,3,1") == {"type": "button", "number": 3,
                                              "down": True}

    def test_garbage(self):
        assert parse_js_message("ja,x") is None
        assert parse_js_message("zz") is None


class TestHub:
    def test_subscriber_receives_events(self, tmp_path):
        async def go():
            hub = JoystickHub(socket_dir=str(tmp_path))
            await hub.start()
            reader, writer = await asyncio.open_unix_connection(hub.path)
            # init burst: 8 axes + 16 buttons, 8 bytes each
            init = await reader.readexactly(24 * 8)
            _, _, etype, num = struct.unpack("<IhBB", init[:8])
            assert etype == (JS_EVENT_AXIS | JS_EVENT_INIT) and num == 0
            await asyncio.sleep(0.1)   # let the hub register the writer
            hub.handle_message("jb,2,1")
            hub.handle_message("ja,1,-1.0")
            ev1 = struct.unpack("<IhBB", await reader.readexactly(8))
            ev2 = struct.unpack("<IhBB", await reader.readexactly(8))
            assert (ev1[2], ev1[3], ev1[1]) == (JS_EVENT_BUTTON, 2, 1)
            assert (ev2[2], ev2[3], ev2[1]) == (JS_EVENT_AXIS, 1, -32767)
            writer.close()
            await hub.close()

        run(go())


@pytest.mark.skipif(shutil.which("gcc") is None, reason="no C toolchain")
class TestInterposer:
    def test_preload_shim_end_to_end(self, tmp_path):
        """Compile the shim, run a subprocess under LD_PRELOAD that opens
        /dev/input/js0, answers the capability ioctls, and reads one event
        injected through the hub."""
        import docker_nvidia_glx_desktop_tpu.native as native_pkg

        src = os.path.join(os.path.dirname(native_pkg.__file__),
                           "joystick_interposer.c")
        so = tmp_path / "ji.so"
        subprocess.run(["gcc", "-shared", "-fPIC", "-o", str(so), src,
                        "-ldl"], check=True)

        probe = tmp_path / "probe.py"
        probe.write_text(
            "import fcntl, os, struct, sys\n"
            "fd = os.open('/dev/input/js0', os.O_RDONLY)\n"
            "buf = bytearray(1)\n"
            "fcntl.ioctl(fd, 0x80016a11, buf)      # JSIOCGAXES\n"
            "axes = buf[0]\n"
            "buf = bytearray(1)\n"
            "fcntl.ioctl(fd, 0x80016a12, buf)      # JSIOCGBUTTONS\n"
            "buttons = buf[0]\n"
            "def readexact(n):                     # the shim fd is a\n"
            "    out = b''                         # socket: short reads\n"
            "    while len(out) < n:               # happen under suite\n"
            "        c = os.read(fd, n - len(out)) # load (the old one-\n"
            "        if not c: raise EOFError      # shot read was the\n"
            "        out += c                      # order-dep flake)\n"
            "    return out\n"
            "readexact(8 * 24)                     # init burst, exactly\n"
            "ev = readexact(8)                     # the injected event\n"
            "t, v, et, num = struct.unpack('<IhBB', ev)\n"
            "print(axes, buttons, et, num, v)\n")

        # socket dir UNIQUE to this test run (tmp_path) + a minimal,
        # explicit environment: inheriting the suite's os.environ made
        # the probe's startup depend on whatever neighboring tests
        # exported (accelerator plugin vars, compile-cache paths, ...).
        env = {k: v for k, v in os.environ.items()
               if k in ("PATH", "HOME", "LANG", "TMPDIR")}
        env.update(LD_PRELOAD=str(so), JOYSTICK_SOCKET_DIR=str(tmp_path))

        async def go():
            hub = JoystickHub(socket_dir=str(tmp_path))
            await hub.start()
            # -S skips site initialisation: the probe needs nothing from it
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-S", str(probe), env=env,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE)
            try:
                # wait until the interposed fd is registered
                # (load-tolerant)
                for _ in range(150):
                    if hub._writers:
                        break
                    await asyncio.sleep(0.1)
                assert hub._writers, "probe never connected to the hub"
                # The injected event is ordered AFTER the init burst on
                # the stream; the probe reads the burst exactly, so no
                # drain-delay is needed for correctness.
                hub.handle_message("jb,5,1")
                out, err = await asyncio.wait_for(proc.communicate(), 15)
            finally:
                if proc.returncode is None:
                    proc.kill()          # never leak a wedged probe into
                    await proc.wait()    # the rest of the suite
                await hub.close()
            assert proc.returncode == 0, err.decode()
            return out.decode().split()

        axes, buttons, etype, num, val = run(go())
        assert (axes, buttons) == ("8", "16")
        assert (etype, num, val) == ("1", "5", "1")   # BUTTON 5 down
