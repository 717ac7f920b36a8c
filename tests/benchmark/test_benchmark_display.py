"""The display's ring (PR 34), on the CPU at 128x96: a real child process
whose renderer stalls, so that the display skips exactly 11, 12 or 23
refreshes, while the test holds the buffer it was handed; every buffer
touched before the display is ready; and ``run.py``'s watchdog for a session
that takes no frame, driven with a fake display."""

import importlib.util
import json
import pathlib
import shutil
import sys
import threading
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import barcode, display as shipped  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

W, H, FPS = 128, 96, 10     # a refresh of 100 ms: a stall lands 30 ms inside
RING = shipped.RING
STALL_AT = 3                # the frame whose render stalls

# A traffic generator that is the fault: frame ``at`` takes until ``skip``
# refreshes after it was due (and 0.3 of one more) to render.  It takes the
# display's t0 from its own first call, microseconds after the child set it.
GEN_STALL = '''
import time


class Scene:
    def __init__(self, at, skip, fps):
        self.at, self.skip, self.fps, self.t0 = at, skip, fps, None

    def render(self, c, out):
        if self.t0 is None:
            self.t0 = time.monotonic()
        out[:] = 60 + c % 100
        if c == self.at:
            until = self.t0 + (c + self.skip + 0.3) / self.fps
            time.sleep(max(0.0, until - time.monotonic()))


def build(params, width, height, fps, seed):
    return Scene(params["at"], params["skip"], fps)
'''


@pytest.fixture(scope="module")
def stalling(tmp_path_factory):
    """A copy of the benchmark with the stalling generator beside the
    shipped ones (files added, none edited), and its ``display`` module: the
    child it starts is the copy's ``display.py``, which finds the generator
    by name as every generator is found."""
    root = tmp_path_factory.mktemp("stalling")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    (root / "benchmark" / "traffic" / "gen_stall.py").write_text(GEN_STALL)
    spec = importlib.util.spec_from_file_location(
        "stalling_display", root / "benchmark" / "display.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def slot_of(display, buf) -> int:
    """Which buffer of the ring ``buf`` is, from its address."""
    at = buf.__array_interface__["data"][0]
    base = display._ring.__array_interface__["data"][0]
    return (at - base) // (H * W * 3)


def next_frame(display, after_k: int, timeout_s: float = 10.0):
    """Poll as the session does until the display shows a frame past
    ``after_k``."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        buf, k = display.frame()
        if k > after_k:
            return buf, k
        time.sleep(0.001)
    raise AssertionError(f"the display showed nothing after k = {after_k}")


@pytest.mark.parametrize("skip,k_mod_ring_collides", [
    (11, True),         # k + 12: the buffer just handed out (PR 32's fault)
    (12, False),        # k + 13: the next buffer, safe by the residue alone
    (23, True)])        # k + 24: the same buffer again
def test_a_held_buffer_is_not_written_for_ring_less_one_renders(
        stalling, skip, k_mod_ring_collides):
    d = stalling.Display({"generator": "stall",
                          "params": {"at": STALL_AT, "skip": skip}},
                         W, H, FPS, seed=2**31 + 34)
    try:
        d.start()
        held, k = next_frame(d, STALL_AT - 1)
        assert k == STALL_AT                    # the late frame itself
        held_slot, shown = slot_of(d, held), []
        assert barcode.read(held[:, :, 0]) == STALL_AT
        while len(shown) < RING:
            buf, k = next_frame(d, k)
            shown.append((k, slot_of(d, buf)))
            if len(shown) <= RING - 2:
                # RING - 1 further frames rendered or being rendered: the
                # buffer still shows the frame it was handed out as
                assert barcode.read(held[:, :, 0]) == STALL_AT, shown
        assert shown[0][0] == STALL_AT + skip + 1       # the overrun, exactly
        assert d.skipped == skip
        # where the child wrote, without a race: the RING - 1 frames after
        # the held one went to the other buffers, the next one to this
        assert held_slot not in [s for _, s in shown[:RING - 1]], shown
        assert shown[RING - 1][1] == held_slot
        assert barcode.read(held[:, :, 0]) == shown[RING - 1][0]
        # the parent's indexing on the same log of frames shown: ring[k %
        # RING] would have taken the held buffer within those RING - 1
        # renders when the skip is 11 or 23, and not when it is 12
        assert [k for k, _ in d.handed][-RING:] == [k for k, _ in shown]
        reused = [k for k, _ in shown[:RING - 1]
                  if k % RING == STALL_AT % RING]
        assert bool(reused) is k_mod_ring_collides
        if k_mod_ring_collides:
            assert reused == [STALL_AT + skip + 1]      # at the next render
    finally:
        d.close()


def test_every_buffer_is_written_before_the_display_is_ready():
    traffic = json.loads(
        (ROOT / "benchmark" / "traffic" / "fulldamage.json").read_text())
    d = shipped.Display(traffic, W, H, FPS, seed=2**31 + 34)
    try:
        assert not d._ring.any()                # a new segment is zeros
        d.start()
        # start() returns within 10 ms of READY and a refresh is 100 ms:
        # frame 0 is shown and frame 1 rendered ahead of its swap, every
        # other buffer still holds the touch
        touched = [bool((d._ring[s] == shipped.TOUCHED).all())
                   for s in range(RING)]
        assert touched[2:] == [True] * (RING - 2), touched
        buf, k = next_frame(d, -1)
        assert k == 0 and slot_of(d, buf) == 0
        assert barcode.read(buf[:, :, 0]) == 0
    finally:
        d.close()


class FakeDisplay:
    def __init__(self):
        self.handed = []


def run_watchdog(display, feed_s: float, quiet_s: float, capfd):
    """The watchdog on ``display`` while a feeder takes a frame every 10 ms
    for ``feed_s`` and then none for ``quiet_s``."""
    stop = threading.Event()
    t = threading.Thread(target=bench_run.watch_for_a_stall,
                         args=(display, stop),
                         kwargs={"stall_s": 0.4, "look_s": 0.02})
    t.start()
    until = time.monotonic() + feed_s
    while time.monotonic() < until:
        display.handed.append((len(display.handed), time.monotonic()))
        time.sleep(0.01)
    time.sleep(quiet_s)
    ended_by_itself = not t.is_alive()
    stop.set()
    t.join(timeout=5)
    assert not t.is_alive()
    return ended_by_itself, capfd.readouterr()


def test_the_watchdog_dumps_every_stack_once_when_no_frame_is_taken(capfd):
    d = FakeDisplay()
    ended, said = run_watchdog(d, feed_s=0.3, quiet_s=0.9, capfd=capfd)
    assert ended                                # once a run
    assert said.out.count("STALL: no frame taken from the display") == 1
    assert f"({len(d.handed)} taken so far)" in said.out
    # every thread's stack, this one's and the watchdog's own among them
    assert said.err.count("most recent call first") >= 2
    assert "watch_for_a_stall" in said.err and "run_watchdog" in said.err


def test_the_watchdog_says_nothing_while_frames_are_taken(capfd):
    ended, said = run_watchdog(FakeDisplay(), feed_s=0.6, quiet_s=0.0,
                               capfd=capfd)
    assert not ended
    assert "STALL" not in said.out and said.err == ""
