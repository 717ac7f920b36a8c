"""The deployment ``desk1080-hq`` (PR 48): its configuration is ``desk1080``'s
with ``ENCODER_TUNE=hq``, its two cells resolve with the unlisted readers and
their seven, the seven on hand-made runs (scopes there, scopes absent,
counters missing), loading them refuses a program whose per-frame step is
specialized on ``qp`` under hq, a traced rehearsal of each cell at 320x240
ends with all five compared numbers 0 and gives the counters' readers a value,
and the by-hand reference check passes there and fails when the loop filter
is handed one qp for every edge."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench_run  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = ["desk1080-hq.fulldamage", "desk1080-hq.desktop"]
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "desk1080-hq.json").read_text())
CONTROL = json.loads((ROOT / "benchmark" / "configs"
                      / "desk1080.json").read_text())
DEVICE, RATE = "device programs", "rate control"
# reader -> (layer, source, unit, better, moves)
READERS = {
    "hq_aq_ms": (DEVICE, "device_trace", "ms", "lower", "g2g_p50_ms"),
    "hq_mode_decision_ms": (DEVICE, "device_trace", "ms", "lower",
                            "g2g_p50_ms"),
    "hq_search_ms": (DEVICE, "device_trace", "ms", "lower", "g2g_p50_ms"),
    "hq_deblock_ms": (DEVICE, "device_trace", "ms", "lower", "g2g_p50_ms"),
    "hq_deblock_hbm_pct": (DEVICE, "device_trace", "%", "higher",
                           "g2g_p50_ms"),
    "hq_p_intra_mb_pct": (RATE, "program_counter", "%", "lower",
                          "psnr_p50_db"),
    "hq_coded_qp_delta": (RATE, "program_counter", "qp", "lower",
                          "psnr_p50_db"),
}
COUNTERS = ["dngd_encoder_p_mbs_total", "dngd_encoder_p_intra_mbs_total",
            "dngd_encoder_coded_qp_sum_total",
            "dngd_encoder_slice_qp_sum_total"]
CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def reader(name):
    return bench_run.load_by_file("layer_metrics", name)


def test_the_configuration_is_desk1080_with_the_tier_on():
    assert CONFIG["name"] == "desk1080-hq" and CONFIG["reduced"] == []
    assert CONFIG["env"] == dict(CONTROL["env"], ENCODER_TUNE="hq")
    assert CONFIG["chips"] == 1
    assert CONFIG["geometry"] == dict(CONTROL["geometry"], rows=68)
    # the control's guarantees, and the tier's own behind them
    n = len(CONTROL["guarantees"])
    assert CONFIG["guarantees"][:n] == CONTROL["guarantees"]
    said = " ".join(CONFIG["guarantees"][n:])
    for phrase in ("mb_qp_delta", "I_16x16", "bit for bit", "at any qp"):
        assert phrase in said, phrase
    assert any("not checked" in a for a in CONFIG["assumed"])


def test_the_entries_are_appended_and_well_formed():
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == "desk1080-hq"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert "deploy/xgl-tpu.yml#L157-L167" in entry["source"]
    assert entry["file"] == "benchmark/configs/desk1080-hq.json"
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    for name in CELLS:
        assert cells[name]["config"] == "desk1080-hq"
        assert cells[name]["chips"] == 1 and len(cells[name]["why"]) <= 200
        assert cells[name]["traffic"] == name.split(".")[1]
    # behind every entry they are the controls of or share a layer with
    order = [w["name"] for w in MANIFEST["workloads"]]
    assert order.index(CELLS[0]) > order.index("desk1080.fulldamage")
    assert order.index(CELLS[1]) > order.index("desk1080.desktop")
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in READERS}
    for name, (layer, source, unit, better, moves) in READERS.items():
        (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
        assert m == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": CELLS}
        assert layer in layers
    # no accepted list was lengthened: the cells report what every cell owes
    for m in MANIFEST["per_layer"]:
        if m["name"] not in READERS and "workloads" in m:
            assert not set(CELLS) & set(m["workloads"]), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_resolves_with_the_unlisted_readers_and_its_seven(cell):
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         cell, "--seed", str(2**31 + 48), "--seconds", "1", "--resolve-only"],
        capture_output=True, text=True, timeout=120, env=CHILD_ENV)
    assert r.returncode == 0, r.stdout + r.stderr
    found = json.loads(r.stdout.strip().splitlines()[-1])
    assert found["env"]["ENCODER_TUNE"] == "hq"
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert found["per_layer"] == unlisted + list(READERS)


@pytest.mark.parametrize("tunes", [("off",), None])
def test_a_program_with_a_static_qp_under_hq_is_refused(tunes, monkeypatch):
    """``hq`` not among the tunes the per-frame step traces ``qp`` under, or
    a program that does not say (every tree before PR 48)."""
    from benchmark.layer_metrics import _hq
    from docker_nvidia_glx_desktop_tpu.ops import cavlc_p_device

    _hq.require_traced_hq_step()                     # this tree: it resolves
    if tunes is None:
        monkeypatch.delattr(cavlc_p_device, "DYNQP_STEP_TUNES")
    else:
        monkeypatch.setattr(cavlc_p_device, "DYNQP_STEP_TUNES", tunes)
    with pytest.raises(_hq.StaticHqStep, match="cannot run an hq cell"):
        _hq.require_traced_hq_step()


def test_a_step_of_before_pr_48_ends_the_cell_before_the_chip(tmp_path):
    """The cell through run.py on a program that says nothing of its step's
    tunes: exit code 1 within seconds, no result line, JAX's devices never
    asked for; the control lists none of the readers and resolves there."""
    pkg = tmp_path / "docker_nvidia_glx_desktop_tpu" / "ops"
    pkg.mkdir(parents=True)
    (pkg.parent / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "cavlc_p_device.py").write_text(
        "def encode_p_cavlc_frame_dynqp(*a, **k):\n"
        "    raise TypeError('needs a static qp')\n")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", CELLS[0], "--seed", "97804848", "--seconds", "20",
         "--trace", "1"], capture_output=True, text=True, timeout=60,
        env=CHILD_ENV)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "StaticHqStep" in r.stderr and "serving thread" in r.stderr
    assert "device:" not in r.stdout and '"metrics"' not in r.stdout
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "desk1080.fulldamage", "--seed", "1", "--seconds", "1",
         "--resolve-only"], capture_output=True, text=True, timeout=60,
        env=CHILD_ENV)
    assert r.returncode == 0, r.stdout + r.stderr
    assert not set(READERS) & set(json.loads(
        r.stdout.strip().splitlines()[-1])["per_layer"])


# the chip's view of a traced span of ten frames: an IDR and nine P frames,
# the loop filter behind every one, the statistics' program beside them
PROGRAMS = {
    "jit_encode_p_cavlc_frame": {"device_s": 0.0900, "runs": 9, "scopes": {
        "dngd.me_int": 0.0200, "dngd.me_subpel": 0.0150, "dngd.mc": 0.0050,
        "dngd.mode_decision": 0.0100, "dngd.aq": 0.0010,
        "dngd.slots": 0.0300, "(no scope)": 0.0030}},
    "jit_encode_intra_cavlc_frame_yuv": {"device_s": 0.0120, "runs": 1,
                                         "scopes": {"dngd.intra": 0.0110,
                                                    "dngd.aq": 0.0002}},
    "jit_deblock_frame": {"device_s": 0.0030, "runs": 10, "scopes": {
        "dngd.deblock_edges": 0.0009, "dngd.deblock_thr": 0.0006,
        "dngd.deblock_tile": 0.0008, "dngd.deblock_bs": 0.0004}},
    "jit_frame_stats": {"device_s": 0.0005, "runs": 10, "scopes": {
        "dngd.frame_stats": 0.0005}},
}
# a window of 1,180 P frames of 8,160 macroblocks at slice qp 40: one in
# twenty coded intra, the mean macroblock three quarters of a qp finer
MBS = 1_180 * 8_160
FAMILIES = {"dngd_encoder_p_mbs_total": MBS,
            "dngd_encoder_p_intra_mbs_total": MBS // 20,
            "dngd_encoder_coded_qp_sum_total": MBS * 40 - 3 * MBS // 4,
            "dngd_encoder_slice_qp_sum_total": MBS * 40}


def hand_run(programs=PROGRAMS, frames=10, share=0.95, **families):
    return {"stages": {"frames": frames, "scoped_share": share,
                       "programs": programs},
            "counters_start": {k: 7.0 for k in families},
            "counters_end": {k: 7.0 + v for k, v in families.items()},
            "device_kind": "TPU v5 lite", "width": 1920, "height": 1080}


def test_the_device_readers_on_a_hand_made_run():
    from benchmark.layer_metrics import _hq

    run = hand_run()
    assert reader("hq_aq_ms").read(run) == pytest.approx(0.12)
    assert reader("hq_mode_decision_ms").read(run) == pytest.approx(1.0)
    assert reader("hq_search_ms").read(run) == pytest.approx(4.0)
    assert reader("hq_deblock_ms").read(run) == pytest.approx(0.3)
    # 68 x 120 macroblocks: 384 samples in and out, 23 bytes of edge inputs
    assert _hq.filter_bytes(1920, 1080) == 8160 * (768 + 23)
    want = 10 * _hq.filter_bytes(1920, 1080) / 819e9 / 0.0009
    assert reader("hq_deblock_hbm_pct").read(run) == pytest.approx(100 * want)
    assert 0 < 100 * want < 100
    # the whole picture filtered in ten microseconds would still read under
    # 100: the count is the least a filter can move
    assert 100 * _hq.filter_bytes(1920, 1080) / 819e9 / 10e-6 < 100


def test_the_counter_readers_on_a_hand_made_run():
    run = hand_run(**FAMILIES)
    assert reader("hq_p_intra_mb_pct").read(run) == pytest.approx(5.0)
    assert reader("hq_coded_qp_delta").read(run) == pytest.approx(-0.75)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """Untraced; the parent's programs and families (no scope of the tier's,
    no counter of its); a trace under nine tenths scoped; a window without a
    P frame; a device ``peaks.json`` does not hold."""
    read = reader(name).read
    if READERS[name][1] == "program_counter":
        assert read(hand_run(dngd_encoder_frames_total=1200)) is None
        assert read(hand_run(**dict.fromkeys(FAMILIES, 0))) is None
        return
    assert read(hand_run(**FAMILIES) | {"stages": None}) is None
    assert read(hand_run(share=0.5)) is None
    parent = {"jit_encode_p_cavlc_frame": {
        "device_s": 0.08, "runs": 9, "scopes": {"dngd.slots": 0.03,
                                                "dngd.tq": 0.01}}}
    assert read(hand_run(parent)) is None
    if name == "hq_deblock_hbm_pct":
        assert read(hand_run() | {"device_kind": "cpu"}) is None


@pytest.mark.parametrize("family", COUNTERS)
def test_the_program_renders_the_families_from_import_on(family):
    from docker_nvidia_glx_desktop_tpu.models import h264  # noqa: F401

    assert family in bench_run.program_counters()


@pytest.mark.parametrize("cell", CELLS)
def test_a_rehearsal_of_the_cell_ends_with_all_five_numbers_0(cell):
    """One TRACED rehearsal at 320x240: the served path from ``from_env`` to
    the client with the tier on, the CBR ladder walking under it."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         cell, "--seed", str(2**31 + 480), "--seconds", "3", "--trace", "1",
         "--rehearse", "--geometry", "320x240"],
        capture_output=True, text=True, timeout=900, env=CHILD_ENV)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False                  # a CPU run never is
    assert line["rehearsal"]["correct_before_override"] is True
    assert line["rehearsal"]["compared"] == {
        "undecoded_fragments": 0, "frame_order_faults": 0,
        "p_run_over_gop": 0, "compiles_in_window": 0,
        "closed_loop_luma_maxdiff": 0}
    assert line["attempted"] > 0 and line["failed"] == 0
    got = line["metrics"]
    assert "kbps" in got and "hq_deblock_ms" not in got    # no device number
    assert got["hq_coded_qp_delta"]["value"] != 0
    if cell.endswith("fulldamage"):
        assert 0 < got["hq_p_intra_mb_pct"]["value"] < 50
    else:
        assert 0 <= got["hq_p_intra_mb_pct"]["value"] < 50


def reference(*extra):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "hq_reference.py"),
         "--workload", CELLS[0], "--seed", str(2**31 + 481), "--rehearse",
         "--geometry", "192x104", "--frames", "4", *extra],
        capture_output=True, text=True, timeout=900, env=CHILD_ENV)


def test_the_reference_check_passes_at_192x104():
    """Seven macroblock rows, the last one half padding: (a) the plane by the
    rule, (b) every QPY the stream carries, (c) every picture."""
    r = reference()
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["frames"] == 5 == line["pictures_decoded"]
    assert line["macroblocks_compared"] > 200
    assert line["qp_differing"] == 0 == line["qp_near_half"]
    assert line["i16_in_p"] > 0
    assert line["luma_maxdiff"] == 0
    assert len(line["slice_qps"]) > 1                # the controller walked


def test_the_reference_check_fails_under_uniform_thresholds():
    r = reference("--fault", "uniform_thresholds")
    assert r.returncode == 1, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    # the stream is the same stream: what differs is the encoder's picture
    assert line["qp_differing"] == 0 and line["pictures_decoded"] == 5
    assert line["luma_maxdiff"] > 0


def test_the_plain_reader_follows_the_programs_own_writer():
    """(b)'s reader on slices the program's PYTHON coder wrote from hand-made
    levels: every kind of macroblock, every QPY."""
    import numpy as np

    from benchmark import hq_reference as ref
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    rng = np.random.default_rng(48)
    enc = H264Encoder(64, 48, qp=30, entropy="python", gop=4, tune="hq")
    sp, t = ref.stream_parameters(enc.headers()), ref._tables()
    assert (sp["mb_w"], sp["mb_h"], sp["init_qp"]) == (4, 3, 30)
    frames = [rng.integers(0, 256, (48, 64, 3), np.uint8) for _ in range(2)]
    frames.append(frames[-1].copy())                 # a frame of skips
    kinds = set()
    for rgb in frames:
        for row in ref.read_picture(enc.encode(rgb).data, sp, t):
            assert len(row["mbs"]) == 4 and row["qp"] in (27, 30)
            kinds |= {kind for kind, _, _ in row["mbs"]}
            assert all(1 <= qpy <= 51 for _, qpy, _ in row["mbs"])
    assert {"skip", "i16"} <= kinds
