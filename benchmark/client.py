#!/usr/bin/env python3
"""The browser's side of the socket, as a process of its own.

It never imports JAX (the chip belongs to the parent) and shares no GIL with
the session thread.  It speaks ``/ws`` with the basic-auth header as the web
client does: hello, the init segment, then media fragments; ``fprobe``
messages are acked.  Every binary message is stamped with ``time.monotonic()``
on arrival (system-wide CLOCK_MONOTONIC on Linux, so the parent's stamps
compare) and kept; nothing is decoded here.

stdin, one JSON line: ``{"port", "user", "passwd", "out", "ready_after"}``.
stdout: ``READY <n>`` once ``ready_after`` fragments have arrived.  A second
stdin line (or EOF) stops it: it closes the socket, writes ``<out>.mp4`` (init
segment and fragments, back to back) and ``<out>.json`` (their lengths, the
stamps, the hello) and exits 0.
"""

from __future__ import annotations

import asyncio
import json
import sys
import threading
import time

CONNECT_TIMEOUT_S = 900.0


async def run(job: dict) -> int:
    import aiohttp

    loop = asyncio.get_running_loop()
    stop = asyncio.Event()

    def wait_stdin():
        sys.stdin.readline()
        loop.call_soon_threadsafe(stop.set)

    threading.Thread(target=wait_stdin, daemon=True).start()
    url = f"http://127.0.0.1:{job['port']}/ws"
    auth = aiohttp.BasicAuth(job["user"], job["passwd"])
    hello, init, frags, stamps, other = None, None, [], [], []
    announced = False
    async with aiohttp.ClientSession() as http:
        ws, deadline = None, time.monotonic() + CONNECT_TIMEOUT_S
        while ws is None:
            try:
                ws = await http.ws_connect(url, auth=auth, max_msg_size=0)
            except aiohttp.ClientError:
                if time.monotonic() > deadline or stop.is_set():
                    print("FAILED connect", flush=True)
                    return 1
                await asyncio.sleep(0.2)
        stopper = asyncio.ensure_future(stop.wait())
        try:
            while not stop.is_set():
                recv = asyncio.ensure_future(ws.receive())
                done, _ = await asyncio.wait(
                    {recv, stopper}, return_when=asyncio.FIRST_COMPLETED)
                if recv not in done:
                    recv.cancel()
                    break
                msg = recv.result()
                if msg.type == aiohttp.WSMsgType.BINARY:
                    now = time.monotonic()
                    if init is None:
                        init = msg.data
                        continue
                    frags.append(msg.data)
                    stamps.append(now)
                    if not announced and len(frags) >= job["ready_after"]:
                        announced = True
                        print(f"READY {len(frags)}", flush=True)
                elif msg.type == aiohttp.WSMsgType.TEXT:
                    ctrl = json.loads(msg.data)
                    if ctrl.get("type") == "hello" and hello is None:
                        hello = ctrl
                    elif ctrl.get("type") == "fprobe":
                        await ws.send_json({"type": "ack", "id": ctrl["id"],
                                            "recv_ts": time.perf_counter()})
                    else:
                        other.append(ctrl)
                else:
                    other.append({"type": f"ws-{msg.type.name}"})
                    break
        finally:
            stopper.cancel()
            await ws.close()
    with open(job["out"] + ".mp4", "wb") as f:
        f.write(init or b"")
        for frag in frags:
            f.write(frag)
    with open(job["out"] + ".json", "w") as f:
        json.dump({"hello": hello, "init_len": len(init or b""),
                   "lens": [len(x) for x in frags], "stamps": stamps,
                   "other": other}, f)
    print(f"DONE {len(frags)}", flush=True)
    return 0


def main() -> int:
    job = json.loads(sys.stdin.readline())
    return asyncio.run(run(job))


if __name__ == "__main__":
    sys.exit(main())
