"""The check and the command, driven on the CPU at a tiny geometry: the
barcode through the encoder's coarse end and cv2, PSNR in the decoder's own
space, the control that must fail, and ``run.py --rehearse`` end to end, once
sound and once with the timed path broken underneath."""

import json
import pathlib
import re
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import barcode, check, stats  # noqa: E402
from benchmark.run import build_scene, load_by_file  # noqa: E402

W, H = 128, 96


def _frames(n, seed=3):
    traffic = json.loads((ROOT / "benchmark" / "traffic" / "fulldamage.json")
                         .read_text())
    scene = build_scene(traffic, W, H, 60, seed)
    out = []
    for c in range(n):
        buf = np.zeros((H, W, 3), np.uint8)
        scene.render(c, buf)
        barcode.draw(buf, 1000 + c)
        out.append(buf)
    return out


def _encoder(qp, bitrate_kbps=0):
    """Built with the arguments models/__init__.py:make_encoder passes."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
    return H264Encoder(W, H, qp=qp, mode="cavlc", entropy="device",
                       host_color=True, gop=60, bitrate_kbps=bitrate_kbps,
                       fps=60, deblock=True, intra_modes="auto",
                       superstep_chunk=0, spatial_shards="0", tune="off")


def test_barcode_survives_qp_44_and_cv2(tmp_path):
    frames = _frames(4)
    enc = _encoder(qp=44)
    data = enc.headers() + b"".join(
        enc.encode_collect(enc.encode_submit(f)).data for f in frames)
    path = tmp_path / "s.h264"
    path.write_bytes(data)
    ks = [barcode.read(y) for y in check.decode_luma(str(path), W, H)]
    assert ks == [1000, 1001, 1002, 1003]


def test_psnr_is_taken_in_the_decoders_own_space(tmp_path):
    """A still frame at the fine end of the ladder: the PSNR the benchmark
    reads from cv2's luma is the PSNR of the encoder's own reference picture
    against the same source, so it measures the codec and not a colour
    conversion; and the source's luma is the one the encoder was fed."""
    from docker_nvidia_glx_desktop_tpu.utils.hostcolor import (
        rgb_to_yuv420_host)
    still = _frames(1)[0]
    assert np.array_equal(check.source_luma(still),
                          rgb_to_yuv420_host(still, H, W)[0])
    enc = _encoder(qp=20)
    data, refs = enc.headers(), []
    for _ in range(3):
        data += enc.encode_collect(enc.encode_submit(still)).data
        refs.append(np.array(enc.export_state()["ref"][0][:H, :W]))
    path = tmp_path / "s.h264"
    path.write_bytes(data)
    src = check.source_luma(still)
    for luma, ref in zip(check.decode_luma(str(path), W, H), refs):
        assert stats.psnr_db(luma, src) == stats.psnr_db(ref, src)
        assert stats.psnr_db(luma, src) > 38.0


@pytest.mark.parametrize("control,sound", [(None, True),
                                           ("deblock_skipped", False)])
def test_closed_loop_check_and_its_control(tmp_path, control, sound):
    """The decoder's pictures are the encoder's reference pictures, bit for
    bit; with the loop filter skipped on the encoder's side they are not."""
    enc = _encoder(qp=30, bitrate_kbps=300)
    if control:
        load_by_file("controls", control).apply(
            types.SimpleNamespace(encoder=enc))
    worst = check.closed_loop_maxdiff(enc, _frames(8), str(tmp_path / "c.h264"),
                                      W, H)
    assert (worst == 0) is sound
    if not sound:
        assert worst >= 3


def _rehearse(*extra):
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "desk1080.desktop", "--seed", str(2**31 + 7), "--seconds", "2",
         "--rehearse", "--geometry", "320x240", *extra],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    # each number compared beside its limit: the line's last key, and the
    # last lines of standard error
    assert list(line)[-1] == "compared" and len(line["compared"]) == 5
    assert r.stderr.strip().splitlines()[-5:] == [
        f"compared: {n} = {c['value']} (limit {c['limit']})"
        for n, c in line["compared"].items()]
    return line, r.stdout


def test_rehearsal_prints_the_contract_line_and_is_never_a_result():
    line, out = _rehearse("--trace", "0")
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False                 # a CPU run never counts
    assert line["rehearsal"]["correct_before_override"] is True, out
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count"}
    assert line["compared"]["closed_loop_luma_maxdiff"] == {
        "value": 0, "limit": 0}
    assert set(line["metrics"]) == {"delivered_fps", "g2g_p50_ms",
                                    "g2g_p95_ms", "psnr_p50_db", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert "compared: closed_loop_luma_maxdiff = 0 (limit 0)" in out


def test_a_broken_timed_path_comes_out_as_not_correct():
    """Every fourth collect hands back the frame before it (a step that
    returns its state unchanged), under the rest of a real run."""
    line, out = _rehearse("--trace", "1", "--control", "stale_frame")
    assert line["rehearsal"]["correct_before_override"] is False
    assert line["rehearsal"]["compared"]["frame_order_faults"] > 0
    # every fault is named: the picture was BEHIND what the display had
    # handed out (a frame sent again), not ahead of it (a buffer overwritten)
    named = re.findall(
        r"frame order fault: picture \d+ reads k = (\d+) after k = (\d+); "
        r"when it arrived, -?[\d.]+ s into the window, the display had last "
        r"handed out k = (\d+): k does not rise", out)
    assert named and len(named) == min(
        line["rehearsal"]["compared"]["frame_order_faults"], 20)
    assert all(int(k) <= int(after) <= int(handed)
               for k, after, handed in named)
    assert not {"device_ms_per_frame", "device_idle_pct", "me_subpel_ms",
                "deblock_ms", "unscoped_ms"} & set(line["metrics"])
    assert 0 <= line["metrics"]["capture_age_p50_ms"]["value"] < 100
    assert 0 < line["metrics"]["taken_to_glass_p50_ms"]["value"] < 1000
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_no_chip_is_an_error_outside_a_rehearsal():
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "desk1080.desktop", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
