#!/usr/bin/env python3
"""The display: a framebuffer with latest-frame semantics and its own clock.

An X server renders whether or not the encoder keeps up, and it is another
process.  So is this: a child (no JAX, and no share of the session thread's
GIL) renders frame ``k`` from ``(seed, k)`` into a fresh buffer of a ring in
shared memory and swaps it in at ``t0 + k / fps``.  ``Display.frame()``, in the
serving process, returns the latest buffer and ``k``.  That is the open loop:
the offered rate is the refresh, a slower system delivers fewer of the same
frames, and a delivered frame's latency counts from when frame ``k`` was due.
The child records how late it swapped every frame in.

The ring is indexed by frames RENDERED, not by ``k``: the child's ``n``-th
render goes into ``ring[n % RING]`` whatever ``k`` it shows, and ``k`` and the
slot are published together in one control word, so the serving process never
pairs a ``k`` with another frame's buffer.  The guarantee: a buffer handed out
as frame ``k`` is not written again before ``RING - 1`` further frames have
been rendered, however many refreshes the display skipped (indexed by ``k`` it
was: a display that skipped exactly 11 or 23 refreshes rendered ``k + 12`` or
``k + 24`` into the buffer it had just handed out, PERF.md section 6, PR 34).
The child writes every buffer once before it says it is ready, so no frame
pays for the first touch of its pages.

``mark_epoch()`` (called at the start of the measured window) restarts the
content at its first frame from the refresh after next, so that every run of
one seed shows the window the same pictures whatever its set-up took; the
frame index ``k`` in the barcode keeps counting.

As a program (``python benchmark/display.py``) this file is the child: it
reads one JSON line from stdin and renders until the stop flag is set.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time
from multiprocessing import resource_tracker, shared_memory

import numpy as np

RING = 12           # buffers: the encoder may still hold the frames in flight
MAX_FRAMES = 1 << 17                      # 36 minutes of refreshes at 60 Hz
TOUCHED = 128       # what a buffer holds between its first touch and a frame
# the control block, int64 words; LATEST is k * RING + slot, one store
LATEST, EPOCH_NEXT, STOP, T0_NS, SKIPPED, READY, CPUS, LATE_US = \
    0, 1, 3, 4, 5, 6, 7, 16
READY_TIMEOUT_S = 120.0


def _attach(name: str, shape, dtype):
    shm = shared_memory.SharedMemory(name=name)
    # the parent made the segment and unlinks it; without this the child's
    # resource tracker would try to, at its exit, and warn
    resource_tracker.unregister(shm._name, "shared_memory")
    return shm, np.ndarray(shape, dtype, buffer=shm.buf)


class Display:
    """The ``rfb.source.FrameSource`` interface (``width``, ``height``,
    ``frame() -> (rgb, seq)``, ``close()``) over the child's ring."""

    def __init__(self, traffic: dict, width: int, height: int, fps: int,
                 seed: int):
        self.width, self.height, self.fps = width, height, fps
        self._job = {"traffic": traffic, "width": width, "height": height,
                     "fps": fps, "seed": seed}
        self._shm_ring = shared_memory.SharedMemory(
            create=True, size=RING * height * width * 3)
        self._shm_ctl = shared_memory.SharedMemory(
            create=True, size=8 * (LATE_US + MAX_FRAMES))
        self._ring = np.ndarray((RING, height, width, 3), np.uint8,
                                buffer=self._shm_ring.buf)
        self._ctl = np.ndarray((LATE_US + MAX_FRAMES,), np.int64,
                               buffer=self._shm_ctl.buf)
        self._ctl[:] = 0
        self._ctl[LATEST] = self._ctl[EPOCH_NEXT] = -1
        self._child = None
        self._handed_k = -1
        self.handed = []                # (k, monotonic) of each new k given out
        self.t0 = None

    def start(self) -> None:
        self._job.update(ring=self._shm_ring.name, ctl=self._shm_ctl.name)
        self._child = subprocess.Popen(
            [sys.executable, str(pathlib.Path(__file__).resolve())],
            stdin=subprocess.PIPE)
        self._child.stdin.write((json.dumps(self._job) + "\n").encode())
        self._child.stdin.flush()
        deadline = time.monotonic() + READY_TIMEOUT_S
        while not self._ctl[READY]:
            if self._child.poll() is not None or time.monotonic() > deadline:
                raise RuntimeError("the display process did not come up")
            time.sleep(0.01)
        self.t0 = int(self._ctl[T0_NS]) / 1e9

    def due(self, k: int) -> float:
        return self.t0 + k / self.fps

    def mark_epoch(self) -> int:
        """Content restarts at the refresh after next; returns that ``k``."""
        k = int((time.monotonic() - self.t0) * self.fps) + 2
        self._ctl[EPOCH_NEXT] = k
        return k

    def frame(self):
        word = int(self._ctl[LATEST])
        if word < 0:
            return self._ring[0], -1
        k, slot = divmod(word, RING)
        if k != self._handed_k:
            self._handed_k = k
            self.handed.append((k, time.monotonic()))
        return self._ring[slot], k

    @property
    def skipped(self) -> int:
        return int(self._ctl[SKIPPED])

    @property
    def cpus_inherited(self) -> int:
        """How many CPUs the child was allowed when it started."""
        return int(self._ctl[CPUS])

    def late_ms(self, k_from: int, k_to: int) -> list:
        """Swap minus due, in ms, of the frames shown with k in the range."""
        late = self._ctl[LATE_US + max(k_from, 0):LATE_US + max(k_to, 0)]
        return [us / 1e3 for us in late.tolist() if us != 0]

    def close(self) -> None:
        if self._child is not None:
            self._ctl[STOP] = 1
            try:
                self._child.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self._child.kill()
                self._child.wait()
            self._child = None
        self._ring = self._ctl = None
        for shm in (self._shm_ring, self._shm_ctl):
            if shm is None:
                continue
            shm.unlink()
            try:
                shm.close()
            except BufferError:
                pass                    # the encoder still holds a frame
        self._shm_ring = self._shm_ctl = None


def child_main() -> int:
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmark import barcode
    from benchmark.run import build_scene

    job = json.loads(sys.stdin.readline())
    # the parent holds the chip, and its runtime may have narrowed the CPUs of
    # the thread that started this process: record what was inherited, then
    # take every CPU, so that the display never shares one core with a busy
    # thread of the program (PERF.md, Open questions: the half-rate display)
    inherited = len(os.sched_getaffinity(0))
    try:
        os.sched_setaffinity(0, range(os.cpu_count() or 1))
    except OSError:
        pass
    h, w, fps = job["height"], job["width"], job["fps"]
    scene = build_scene(job["traffic"], w, h, fps, job["seed"])
    shm_ring, ring = _attach(job["ring"], (RING, h, w, 3), np.uint8)
    shm_ctl, ctl = _attach(job["ctl"], (LATE_US + MAX_FRAMES,), np.int64)
    # the first write to a buffer faults its pages in (0.2 s a 12.3 MB buffer
    # in some checkouts, PR 32): paid here, not by the run's first frames
    ring.fill(TOUCHED)
    t0 = time.monotonic()
    ctl[T0_NS] = int(t0 * 1e9)
    ctl[CPUS] = inherited
    ctl[READY] = 1
    k, epoch, n = 0, 0, 0               # n: frames rendered
    while not ctl[STOP] and k < MAX_FRAMES:
        nxt = int(ctl[EPOCH_NEXT])
        if 0 <= nxt <= k:
            epoch, ctl[EPOCH_NEXT] = nxt, -1
        slot = n % RING
        buf = ring[slot]
        scene.render(k - epoch, buf)
        barcode.draw(buf, k)
        wait = t0 + k / fps - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        ctl[LATEST] = k * RING + slot
        now = time.monotonic()
        ctl[LATE_US + k] = max(1, int((now - (t0 + k / fps)) * 1e6))
        # a display that overran its refresh shows the frame that is due now,
        # as a compositor that missed a vblank does
        due_now = int((now - t0) * fps) + 1
        if due_now > k + 1:
            ctl[SKIPPED] += due_now - (k + 1)
        k, n = max(k + 1, due_now), n + 1
    del ring, ctl
    shm_ring.close()
    shm_ctl.close()
    return 0


if __name__ == "__main__":
    sys.exit(child_main())
