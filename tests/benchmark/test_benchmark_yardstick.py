"""The yardstick's own arithmetic: the generators are pure functions of
(seed, frame), the barcode reads back, percentiles and due times on a
hand-made arrival list, and the trace reduction on a small recorded trace."""

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import barcode, check, stats, trace_reduce  # noqa: E402
from benchmark.run import build_scene  # noqa: E402

TRACE = ROOT / "benchmark" / "testdata" / "v5e_desk1080_357ms.xplane.pb"


def _traffic(name):
    return json.loads((ROOT / "benchmark" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", ["desktop", "fulldamage"])
def test_generator_is_a_pure_function_of_seed_and_frame(name):
    big = 2**31 + 12345                       # the driver's seeds are large
    a = build_scene(_traffic(name), 320, 240, 60, big)
    b = build_scene(_traffic(name), 320, 240, 60, big)
    other = build_scene(_traffic(name), 320, 240, 60, big + 1)
    fa, fb, fo = (np.zeros((240, 320, 3), np.uint8) for _ in range(3))
    for c in (0, 1, 59, 130, 777, 1200 + 59):
        a.render(c, fa)
        a.render(5, fb)                       # no memory of the frame before
        b.render(c, fb)
        other.render(c, fo)
        assert np.array_equal(fa, fb)
        assert not np.array_equal(fa, fo)
    a.render(3, fa)
    a.render(4, fb)
    changed = np.any(fa != fb, axis=-1)
    if name == "fulldamage":                  # every macroblock changes
        assert changed.reshape(15, 16, 20, 16).any(axis=(1, 3)).all()
    else:                                     # a desktop: a few of them do
        assert 0 < changed.reshape(15, 16, 20, 16).any(axis=(1, 3)).sum() < 30


def test_desktop_script_has_the_stated_shares():
    eps = _traffic("desktop")["params"]["episodes"]
    total = sum(e["frames"] for e in eps)
    share = {k: sum(e["frames"] for e in eps if e["kind"] == k) / total
             for k in ("calm", "scroll", "drag", "video")}
    assert total == 1200
    assert share == {"calm": 0.6, "scroll": 0.2, "drag": 0.1, "video": 0.1}
    assert all(60 <= e["frames"] <= 180 for e in eps)


@pytest.mark.parametrize("width", [320, 1920])
def test_barcode_reads_back_and_its_check_byte_catches_a_flipped_bit(width):
    frame = np.full((240, width, 3), 90, np.uint8)
    for k in (0, 1, 1199, 54321, 2**24 - 1):
        barcode.draw(frame, k)
        luma = check.source_luma(frame)
        assert barcode.read(luma) == k
    rows, cols = barcode.layout(width)
    assert rows * cols >= 32
    luma = luma.copy()
    luma[:16, 16:32] = 255 - luma[:16, 16:32]       # bit 1 flips
    assert barcode.read(luma) is None


def test_percentiles_due_times_and_the_window_on_a_hand_made_list():
    assert stats.percentile([4, 1, 3, 2], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95
    t0, fps = 100.0, 50.0                           # frame k is due at t0 + k/50
    arrivals = [(10, 100.25), (11, 100.27), (11, 100.28),    # 11 twice
                (None, 100.30), (13, 100.31), (40, 100.95)]  # 40: too late
    seen = stats.delivered(arrivals, 100.2, 100.9)
    assert seen == {10: 100.25, 11: 100.27, 13: 100.31}
    lat = stats.latencies_ms(seen, t0, fps)
    assert [round(x, 6) for x in lat] == [50.0, 50.0, 50.0]
    assert stats.psnr_db(np.zeros((4, 4)), np.zeros((4, 4))) == 99.0
    assert round(stats.psnr_db(np.zeros((4, 4)), np.full((4, 4), 255)), 6) == 0


HANDED = [(5, 1.0), (6, 1.1), (7, 1.2)]


@pytest.mark.parametrize("ks,stamps,faults", [
    ([5, 6, 7], [1.05, 1.15, 1.25], 0),
    ([5, 5, 7], [1.05, 1.15, 1.25], 1),         # a picture sent again
    ([5, None, 7], [1.05, 1.15, 1.25], 1),      # a barcode that does not read
    ([5, 6, 9], [1.05, 1.15, 1.25], 1),         # never handed out
    ([5, 6, 7], [1.05, 1.05, 1.25], 1)])        # arrived before it was handed
def test_order_faults_counts_what_a_broken_stream_shows(ks, stamps, faults):
    assert len(check.order_faults(ks, stamps, HANDED)) == faults


def test_a_fault_names_the_k_read_and_the_k_handed():
    """A picture AHEAD of what the display had handed out (its buffer changed
    under the session: the ring's fault of PR 32) and one BEHIND (the
    program sent a picture again) read differently."""
    ahead, = check.order_faults([5, 7, None], [1.05, 1.15, 1.16], HANDED)[:1]
    assert (ahead["picture"], ahead["k"], ahead["after"], ahead["handed_k"],
            ahead["stamp"]) == (1, 7, 5, 6, 1.15)
    assert ahead["k"] > ahead["handed_k"] and "before" in ahead["why"]
    behind, = check.order_faults([5, 6, 6], [1.05, 1.15, 1.25], HANDED)
    assert (behind["picture"], behind["k"], behind["after"],
            behind["handed_k"]) == (2, 6, 6, 7)
    assert behind["k"] <= behind["after"] and "rise" in behind["why"]
    unread, = check.order_faults([None], [0.5], HANDED)
    assert unread["k"] is None and unread["handed_k"] is None


def test_union_busy_and_window_on_made_up_planes():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_encode_p(1)", 0.0, 10e6),
                            ("jit_deblock(2)", 10e6, 14e6),
                            ("jit_encode_p(1)", 20e6, 30e6)]},
        "/host:CPU": {"python3": [("dngd.encode_collect", 13e6, 19e6),
                                  ("dngd.encode_submit", 19e6, 21e6),
                                  ("other", 0.0, 40e6)]}}
    r = trace_reduce.reduce_planes(planes)
    assert r == {"frames": 2, "devices": 1, "busy_s": pytest.approx(0.024),
                 "window_s": pytest.approx(0.030)}
    # a device that idles at the trace's edge while the host is in a stage
    # is idle inside the window
    planes["/host:CPU"]["python3"].append(("dngd.pull", 29e6, 32e6))
    assert trace_reduce.reduce_planes(planes)["window_s"] == pytest.approx(
        0.032)
    assert trace_reduce.reduce_planes({}) == {
        "frames": 0, "devices": 0, "busy_s": 0.0, "window_s": 0.0}


def test_trace_reduction_on_a_recorded_v5e_trace():
    """A trace of PR 24's harness (its host spans are run.py's ``bench.*``
    wrappers): the numbers the accepted reduction gave."""
    r = trace_reduce.reduce(str(TRACE))
    assert r == {"devices": 1, "frames": 7, "busy_s": 0.118501651,
                 "window_s": 0.40135692}
    # it passes over the operations, which are nearly all of a trace
    assert list(trace_reduce.load(str(TRACE), ops=False)["/device:TPU:0"]) \
        == ["XLA Modules"]
    assert "XLA Ops" in trace_reduce.load(str(TRACE))["/device:TPU:0"]


def test_per_layer_readers_return_nothing_when_there_is_nothing_to_read():
    from benchmark.run import load_by_file
    run = {"trace": None, "stages": None, "counters_start": {},
           "counters_end": {}, "display_late_ms": [], "capture_age_ms": [],
           "bytes_in_window": 2500000, "seconds": 20.0, "take_gaps_ms": []}
    for name in ("device_ms_per_frame", "device_idle_pct", "submit_mean_ms",
                 "overflow_fallback_pct", "display_late_p95_ms",
                 "loop_stall_max_ms", "me_subpel_ms", "deblock_ms",
                 "unscoped_ms", "capture_age_p50_ms"):
        assert load_by_file("layer_metrics", name).read(run) is None
    assert load_by_file("layer_metrics", "kbps").read(run) == 1000.0
    run["counters_start"] = {"dngd_encoder_submit_ms_sum": 10.0,
                             "dngd_encoder_submit_ms_count": 1.0}
    run["counters_end"] = {"dngd_encoder_submit_ms_sum": 130.0,
                           "dngd_encoder_submit_ms_count": 11.0}
    assert load_by_file("layer_metrics", "submit_mean_ms").read(run) == 12.0
