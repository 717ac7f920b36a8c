"""The deployment ``desk1080-cabac`` (PR 28): its configuration file and its two
cells resolve by name, a rehearsal at 128x96 prints the contract line with
the per-layer metrics the cells owe, the stream it serves says Main profile
and CABAC, the six readers on hand-made runs, the scopes they read in the
programs, and the by-hand reference check on the CPU."""

import json
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench_run  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = ["desk1080-cabac.fulldamage", "desk1080-cabac.desktop"]
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "desk1080-cabac.json").read_text())
CONTROL = json.loads(
    (ROOT / "benchmark" / "configs" / "desk1080.json").read_text())
ENGINE_LAYER = "host entropy engine"
# reader -> (layer, moves, source, unit)
READERS = {
    "cabac_engine_mean_ms": (ENGINE_LAYER, "g2g_p50_ms", "program_span", "ms"),
    "cabac_binarize_ms": ("device programs", "g2g_p50_ms", "device_trace",
                          "ms"),
    "cabac_search_ms": ("device programs", "g2g_p50_ms", "device_trace", "ms"),
    "cabac_other_device_ms": ("device programs", "g2g_p50_ms", "device_trace",
                              "ms"),
    "cabac_record_kib_per_frame": (ENGINE_LAYER, "g2g_p50_ms",
                                   "program_counter", "KiB"),
    "cabac_fallback_pct": (ENGINE_LAYER, "g2g_p95_ms", "program_counter", "%"),
}


def reader(name):
    return bench_run.load_by_file("layer_metrics", name)


def test_the_configuration_is_desk1080_with_the_entropy_coder_changed():
    assert CONFIG["name"] == "desk1080-cabac" and CONFIG["reduced"] == []
    assert CONFIG["chips"] == 1 and CONFIG["geometry"] == CONTROL["geometry"]
    changed = {k: v for k, v in CONFIG["env"].items()
               if CONTROL["env"].get(k) != v}
    assert changed == {"ENCODER_ENTROPY": "cabac",
                       "ENCODER_CABAC_BINARIZE": "device"}
    assert set(CONTROL["env"]) <= set(CONFIG["env"])
    entry = {c["name"]: c for c in MANIFEST["configs"]}["desk1080-cabac"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["source"] != CONTROL["source"]
    said = " ".join(CONFIG["guarantees"])
    assert "Main-profile" in said and "entropy_coding_mode_flag 1" in said
    assert "fallback is counted" in said


@pytest.mark.parametrize("cell", CELLS)
def test_the_cell_resolves_and_owes_the_unlisted_readers_and_its_six(cell):
    entry = {w["name"]: w for w in MANIFEST["workloads"]}[cell]
    assert entry["config"] == "desk1080-cabac" and entry["chips"] == 1
    assert entry["traffic"] == cell.split(".")[1]
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         cell, "--seed", str(2**31 + 28), "--seconds", "1", "--resolve-only"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    found = json.loads(r.stdout.strip().splitlines()[-1])
    assert found["env"] == CONFIG["env"]
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    # a set: a metric that a later PR appends for these cells (PR 33's
    # locked_take_pct waited a PR for this) moves no position here
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if cell in m.get("workloads", ())}
    assert set(READERS) | {"locked_take_pct"} <= listed
    assert set(found["per_layer"]) == set(unlisted) | listed
    # the CAVLC programs' stage readers are not this cell's to report
    assert not {"slots_ms", "pack_ms", "deblock_ms"} & set(found["per_layer"])


@pytest.mark.parametrize("name", sorted(READERS))
def test_manifest_lists_the_reader_for_the_two_cells(name):
    m = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    layer, moves, source, unit = READERS[name]
    assert (m["layer"], m["moves"], m["source"], m["unit"], m["better"]) == (
        layer, moves, source, unit, "lower")
    # the 4K cell (PR 32) owes the readers of counters and spans; the three
    # stage readers give nothing there (88.2% of its trace under a scope,
    # layer_metrics/_stages.py asks for 90%), and a metric that is owed and
    # not given refuses the traced run
    assert m["workloads"][:2] == CELLS
    assert ("desk2160-cabac.fulldamage" in m["workloads"]) is (
        source != "device_trace")


def hand_run(**stages):
    return {"stages": dict({"frames": 2, "scoped_share": 0.95}, **stages)}


PROGRAMS = {
    "jit_encode_p_frame": {"device_s": 0.0150, "runs": 2, "scopes": {
        "dngd.me_subpel": 0.0060, "dngd.me_int": 0.0030, "dngd.mc": 0.0020,
        "dngd.tq": 0.0010, "(no scope)": 0.0030}},
    "jit_binarize_p": {"device_s": 0.0100, "runs": 2, "scopes": {
        "dngd.binarize": 0.0100}},
    "jit__pack_keys": {"device_s": 0.0010, "runs": 1, "scopes": {
        "dngd.level_pack": 0.0010}},
    "jit_deblock_frame": {"device_s": 0.0004, "runs": 2, "scopes": {
        "dngd.deblock_edges": 0.0003, "(no scope)": 0.0001}},
}


def test_device_readers_and_the_loop_filter_sum_to_the_programs_time():
    run = hand_run(programs=PROGRAMS)
    got = {n: reader(n).read(run) for n in (
        "cabac_binarize_ms", "cabac_search_ms", "cabac_other_device_ms")}
    assert got == pytest.approx({"cabac_binarize_ms": 5.0,
                                 "cabac_search_ms": 4.5,
                                 "cabac_other_device_ms": 3.5})
    deblock = 1e3 * PROGRAMS["jit_deblock_frame"]["device_s"] / 2
    total = 1e3 * sum(p["device_s"] for p in PROGRAMS.values()) / 2
    assert sum(got.values()) + deblock == pytest.approx(total)


@pytest.mark.parametrize("name", ["cabac_binarize_ms", "cabac_search_ms",
                                  "cabac_other_device_ms"])
def test_device_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """Untraced, a trace without scopes (a stale compile cache, or the
    parent's binarize programs), and a program without the stage."""
    read = reader(name).read
    assert read({"stages": None}) is None
    assert read(hand_run(programs=PROGRAMS, scoped_share=0.6)) is None
    cavlc_only = {"jit_deblock_frame": PROGRAMS["jit_deblock_frame"]}
    assert read(hand_run(programs=cavlc_only)) is None


def counters(**families):
    return {"counters_start": {k: 0.0 for k in families},
            "counters_end": {k: float(v) for k, v in families.items()}}


def test_counter_and_span_readers_on_a_hand_made_run():
    run = counters(dngd_stage_engine_ms_sum=900.0,
                   dngd_stage_engine_ms_count=300,
                   dngd_encoder_cabac_record_bytes_total=300 * 512 * 1024,
                   dngd_encoder_cabac_fallback_total=3,
                   dngd_encoder_frames_total=300)
    assert reader("cabac_engine_mean_ms").read(run) == pytest.approx(3.0)
    assert reader("cabac_record_kib_per_frame").read(run) == pytest.approx(512)
    assert reader("cabac_fallback_pct").read(run) == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["cabac_engine_mean_ms",
                                  "cabac_record_kib_per_frame",
                                  "cabac_fallback_pct"])
def test_counter_reader_gives_nothing_against_the_parents_program(name):
    """The parent has no ``engine`` span and neither counter: nothing, and
    no exception (the line then leaves the metric out)."""
    run = counters(dngd_encoder_frames_total=300,
                   dngd_stage_assemble_ms_sum=1.0,
                   dngd_stage_assemble_ms_count=300)
    assert reader(name).read(run) is None


@pytest.mark.parametrize("family", [
    "dngd_stage_engine_ms_sum", "dngd_stage_engine_ms_count",
    "dngd_encoder_cabac_record_bytes_total",
    "dngd_encoder_cabac_fallback_total"])
def test_the_program_renders_the_families_from_import_on(family):
    """So a reader finds 0 and not nothing in a window without a sample (no
    fallback is the sound case)."""
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac  # noqa
    from docker_nvidia_glx_desktop_tpu.models import h264  # noqa: F401
    from docker_nvidia_glx_desktop_tpu.obs.metrics import REGISTRY

    assert family in bench_run.parse_metrics(REGISTRY.render())


def bits(rbsp: bytes):
    for byte in rbsp:
        for i in range(7, -1, -1):
            yield (byte >> i) & 1


def ue(it) -> int:
    zeros = 0
    while next(it) == 0:
        zeros += 1
    return (1 << zeros) - 1 + sum(next(it) << (zeros - 1 - i)
                                  for i in range(zeros))


def test_the_served_stream_is_main_profile_with_cabac_on():
    """From the bytes the deployment's encoder sends ahead of every IDR."""
    from docker_nvidia_glx_desktop_tpu.models import make_encoder
    from docker_nvidia_glx_desktop_tpu.utils.config import from_env
    from docker_nvidia_glx_desktop_tpu.web.mp4 import split_annexb

    cfg = from_env(dict(CONFIG["env"], SIZEW="128", SIZEH="96", PASSWD="pw"))
    enc, name = make_encoder(cfg, 128, 96)
    assert name == "h264_cabac"
    nals = {n[0] & 0x1F: n for n in split_annexb(enc.headers())}
    assert nals[7][1] == 77                             # profile_idc: Main
    pps = bits(nals[8][1:])
    assert (ue(pps), ue(pps)) == (0, 0)                 # pps id, sps id
    assert next(pps) == 1                               # entropy_coding_mode
    control, _ = make_encoder(from_env(dict(
        CONTROL["env"], SIZEW="128", SIZEH="96", PASSWD="pw")), 128, 96)
    nals = {n[0] & 0x1F: n for n in split_annexb(control.headers())}
    pps = bits(nals[8][1:])
    assert nals[7][1] == 66 and (ue(pps), ue(pps), next(pps)) == (0, 0, 0)


SCOPES = {"binarize_p": "dngd.binarize", "binarize_intra": "dngd.binarize",
          "pack_levels": "dngd.level_pack", "bs_inputs": "dngd.deblock_bs",
          "intra": "dngd.intra", "p": "dngd.me_subpel"}


@pytest.fixture(scope="module")
def lowered():
    """The CABAC path's programs at a geometry no other test compiles (see
    tests/test_stage_spans.py), lowered with debug info."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import _cabac_bs_inputs
    from docker_nvidia_glx_desktop_tpu.ops import (
        cabac_binarize, h264_device, h264_inter, level_pack)

    w, h = 112, 80
    nr, nc = h // 16, w // 16
    y = np.zeros((h, w), np.uint8)
    c = np.zeros((h // 2, w // 2), np.uint8)
    z = lambda *s: np.zeros((nr, nc) + s, np.int32)  # noqa: E731
    mv = np.zeros((nr, nc, 2), np.int8)
    p_levels = {"luma": z(16, 16), "cb_dc": z(4), "cb_ac": z(4, 15),
                "cr_dc": z(4), "cr_ac": z(4, 15)}
    progs = {
        "binarize_p": cabac_binarize.binarize_p.lower(
            mv, *(p_levels[k] for k, _, _ in level_pack.P_KEYS)),
        "binarize_intra": cabac_binarize.binarize_intra.lower(
            z(16), z(16, 15), z(4), z(4, 15), z(4), z(4, 15), z(),
            np.zeros((nr, nc), bool), z(16), z(16, 16)),
        "pack_levels": level_pack._pack_keys.lower(p_levels,
                                                   level_pack.P_KEYS),
        "bs_inputs": _cabac_bs_inputs.lower(z(16, 16), mv),
        "intra": h264_device.encode_intra_frame_yuv_dynqp.lower(
            y, c, c, np.int32(30)),
        "p": h264_inter.encode_p_frame_dynqp.lower(
            y, c, c, y, c, c, np.int32(30)),
    }
    return {k: low.as_text(debug_info=True) for k, low in progs.items()}


@pytest.mark.parametrize("program", sorted(SCOPES))
def test_the_programs_carry_the_scopes_the_readers_read(lowered, program):
    assert re.search(rf'["/]{re.escape(SCOPES[program])}/', lowered[program])


def test_rehearsal_of_a_cabac_cell_prints_the_contract_line():
    """``run.py --rehearse`` at 128x96 with ``--trace 1``: the line holds
    every per-layer metric the cell owes that is no device number, the
    stream decodes to the encoder's own reference pictures, nothing
    compiles in the window (the rate controller moves ``qp`` through it),
    and no frame fell back."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELLS[0], "--seed", str(2**31 + 28), "--seconds", "2", "--rehearse",
         "--geometry", "128x96", "--trace", "1"],
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    assert line["correct"] is False                 # a CPU run never counts
    assert line["rehearsal"]["correct_before_override"] is True, r.stdout
    assert all(c["value"] == 0 for c in line["compared"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    owed = {m["name"] for m in MANIFEST["per_layer"]
            if m["source"] != "device_trace"
            and ("workloads" not in m or CELLS[0] in m["workloads"])}
    assert set(line["metrics"]) == owed
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["cabac_fallback_pct"] == 0 and got["overflow_fallback_pct"] == 0
    assert got["cabac_engine_mean_ms"] > 0 and got["assemble_mean_ms"] > 0
    assert got["cabac_engine_mean_ms"] < got["assemble_mean_ms"]
    # a record stream of 48 macroblocks: its header and some records
    assert 0.1 < got["cabac_record_kib_per_frame"] < 184


def test_the_reference_check_by_hand_runs_on_the_cpu():
    """benchmark/cabac_reference.py (PERF.md PR 28) at 128x96: the served
    bytes are the Python reference coder's, the decoder's luma the
    encoder's reference pictures."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "cabac_reference.py"),
         "--workload", CELLS[1], "--seed", str(2**31 + 28), "--frames", "3",
         "--rehearse", "--geometry", "128x96"],
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["bytes_identical"] == line["pictures_decoded"] == 3
    assert line["luma_maxdiff"] == 0 and line["codec"] == "h264_cabac"
