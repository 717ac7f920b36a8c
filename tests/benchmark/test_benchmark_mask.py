"""The deployment ``desk1600-mask`` (PR 40): its configuration is
``desk1600``'s with ``DNGD_DAMAGE_MASK`` on, its cell resolves with the
unlisted readers and its seven, the seven on hand-made runs (scopes there,
scopes absent, counters missing), loading them refuses a program whose row
step is specialized on ``qp``, a traced rehearsal of the cell at 320x240 gives
every host-side reader a value and ends with all five compared numbers 0, and
the by-hand reference check passes there and fails when a damaged row is left
out of the plan under it."""

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
CELL = "desk1600-mask.desktop"
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "desk1600-mask.json").read_text())
CONTROL = json.loads((ROOT / "benchmark" / "configs"
                      / "desk1600.json").read_text())
MASK, FRONT = "damage mask", "session loop and encoder front"
# reader -> (layer, source, unit, better, moves)
READERS = {
    "mask_grid_mean_ms": (FRONT, "program_span", "ms", "lower",
                          "delivered_fps"),
    "mask_rows_damaged_pct": (MASK, "program_counter", "%", "lower",
                              "delivered_fps"),
    "mask_rows_coded_pct": (MASK, "program_counter", "%", "lower",
                            "delivered_fps"),
    "mask_row_program_pct": (MASK, "program_counter", "%", "higher",
                             "g2g_p50_ms"),
    "mask_gather_ms": (MASK, "device_trace", "ms", "lower", "g2g_p50_ms"),
    "mask_scatter_ms": (MASK, "device_trace", "ms", "lower", "g2g_p50_ms"),
    "mask_move_hbm_pct": (MASK, "device_trace", "%", "higher", "g2g_p50_ms"),
}
HOST_SIDE = sorted(n for n in READERS if READERS[n][1] != "device_trace")
CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def reader(name):
    return bench_run.load_by_file("layer_metrics", name)


def test_the_configuration_is_desk1600_with_the_mask_on():
    assert CONFIG["name"] == "desk1600-mask"
    assert CONFIG["reduced"] == [] and CONFIG["chips"] == 1
    assert CONFIG["env"] == dict(CONTROL["env"], DNGD_DAMAGE_MASK="true")
    geo = CONFIG["geometry"]
    assert (geo["width"], geo["height"], geo["refresh"]) == (2560, 1600, 60)
    assert geo["macroblocks"] == 160 * 100 == 16000 and geo["rows"] == 100
    entry = {c["name"]: c for c in MANIFEST["configs"]}[CONFIG["name"]]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["source"] != CONTROL["source"] and entry["reduced"] == []
    assert entry is MANIFEST["configs"][-1]          # appended
    # the control's six guarantees word for word, and the mask's two
    assert CONFIG["guarantees"][:6] == CONTROL["guarantees"]
    rows, compiles = CONFIG["guarantees"][6:]
    assert "summed absolute difference over 512" in rows
    assert "any other row is one all-skip slice" in rows
    assert compiles == ("nothing compiles while frames are served, at any "
                        "damage")
    assert any("SURVEY.md section 2.4's reading" in a
               for a in CONFIG["assumed"])
    assert CONFIG["assumed"][-2:] == CONTROL["assumed"]


def test_the_row_ladder_and_the_threshold_are_the_ones_the_file_states():
    from docker_nvidia_glx_desktop_tpu.obs import content
    from docker_nvidia_glx_desktop_tpu.ops import damage_mask

    geo = CONFIG["geometry"]
    assert damage_mask.bucket_ladder(geo["rows"]) == geo["row_buckets"]
    assert "DNGD_CONTENT_DAMAGE_THR" not in CONFIG["env"]
    assert content.damage_thr_sad() == 512


def test_the_cell_resolves_with_the_unlisted_readers_and_its_seven():
    entry = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "desk1600-mask", "desktop", 1)
    assert entry is MANIFEST["workloads"][-1] and len(entry["why"]) <= 200
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 40), "--seconds", "1", "--resolve-only"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    found = json.loads(r.stdout.strip().splitlines()[-1])
    assert found["env"] == CONFIG["env"] and found["chips"] == 1
    assert found["generator"] == "desktop"
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert found["per_layer"] == unlisted + list(READERS)
    for owed in ("colour_mean_ms", "dispatch_mean_ms", "pull_mean_ms",
                 "assemble_mean_ms", "pull_extra_pct", "device_ms_per_frame",
                 "device_idle_pct"):
        assert owed in found["per_layer"]
    # no accepted list was touched: the cell is in none of them
    assert [m["name"] for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", ())] == list(READERS)


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_manifest_lists_the_reader_for_the_cell_alone(name):
    m = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    assert (m["layer"], m["source"], m["unit"], m["better"],
            m["moves"]) == READERS[name]
    assert m["workloads"] == [CELL]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}


def test_the_readers_hold_the_program_to_a_traced_qp():
    from benchmark.layer_metrics import _mask
    from docker_nvidia_glx_desktop_tpu.ops import damage_mask

    _mask.require_traced_row_step()
    assert "qp" not in damage_mask.ROW_STEP_DYNQP_STATIC


@pytest.mark.parametrize("static", [
    ("qp", "tune", "p_intra", "deblock"), None])
def test_a_program_with_a_static_qp_is_refused(static, monkeypatch):
    """``qp`` among the row step's static arguments, or a program that does
    not say (the parent: one row step, ``encode_p_rows``, ``qp`` static)."""
    from benchmark.layer_metrics import _mask
    from docker_nvidia_glx_desktop_tpu.ops import damage_mask

    if static is None:
        monkeypatch.delattr(damage_mask, "ROW_STEP_DYNQP_STATIC")
    else:
        monkeypatch.setattr(damage_mask, "ROW_STEP_DYNQP_STATIC", static)
    with pytest.raises(_mask.StaticRowStep, match="cannot run a damage-mask"):
        _mask.require_traced_row_step()


def test_a_row_step_of_before_pr_40_ends_the_cell_before_the_chip(tmp_path):
    """The cell through run.py on a program whose ``ops/damage_mask`` is the
    parent's in what matters here (no word on its row step's static
    arguments): exit code 1 within seconds, no result line, JAX's devices
    never asked for."""
    pkg = tmp_path / "docker_nvidia_glx_desktop_tpu" / "ops"
    pkg.mkdir(parents=True)
    (pkg.parent / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "damage_mask.py").write_text(
        "def encode_p_rows(*a, qp, **k):\n    raise NotImplementedError\n")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", CELL, "--seed", "97804840", "--seconds", "20",
         "--trace", "1"], capture_output=True, text=True, timeout=60,
        env=CHILD_ENV)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "StaticRowStep" in r.stderr and "specialized on qp" in r.stderr
    assert "device:" not in r.stdout and '"metrics"' not in r.stdout
    # the control lists none of the mask's readers and resolves there
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "desk1600.desktop", "--seed", "1", "--seconds", "1",
         "--resolve-only"], capture_output=True, text=True, timeout=60,
        env=CHILD_ENV)
    assert r.returncode == 0, r.stdout + r.stderr
    assert not set(READERS) & set(json.loads(
        r.stdout.strip().splitlines()[-1])["per_layer"])


# the chip's view of a traced span of ten frames: an IDR, a dense P frame
# with its loop filter, six frames of the row program at a bucket of 4 and
# two at 8, the statistics' program beside every one
PROGRAMS = {
    "jit_encode_p_rows_b4": {"device_s": 0.0180, "runs": 6, "scopes": {
        "dngd.mask_gather": 0.0030, "dngd.mask_scatter": 0.0006,
        "dngd.me_int": 0.0040, "dngd.slots": 0.0050, "(no scope)": 0.0010}},
    "jit_encode_p_rows_b8": {"device_s": 0.0080, "runs": 2, "scopes": {
        "dngd.mask_gather": 0.0010, "dngd.mask_scatter": 0.0004,
        "dngd.me_int": 0.0030, "(no scope)": 0.0006}},
    "jit_encode_p_cavlc_frame": {"device_s": 0.0170, "runs": 1, "scopes": {
        "dngd.me_int": 0.0050, "dngd.ingest": 0.0020}},
    "jit_encode_intra_cavlc_frame_yuv": {"device_s": 0.0100, "runs": 1,
                                         "scopes": {"dngd.intra": 0.0090}},
    "jit_frame_stats": {"device_s": 0.0150, "runs": 10, "scopes": {
        "dngd.frame_stats": 0.0150}},
}
# a window of 1,000 planned P frames of 100 rows: 900 of the row program
# (18,000 rows gathered for 5,400 damaged), 100 dense
ROWS = {"dngd_mask_rows_total": 100_000,
        "dngd_mask_rows_damaged_total": 5_400 + 100 * 100,
        "dngd_mask_rows_coded_total": 18_000 + 100 * 100,
        "dngd_mask_rows_gathered_total": 18_000,
        "dngd_mask_frames_total": 1_000}


def hand_run(programs=PROGRAMS, frames=10, share=0.95, **families):
    return {"stages": {"frames": frames, "scoped_share": share,
                       "programs": programs},
            "counters_start": {k: 7.0 for k in families},
            "counters_end": {k: 7.0 + v for k, v in families.items()},
            "device_kind": "TPU v5 lite", "width": 2560}


def test_the_counter_and_span_readers_on_a_hand_made_run():
    run = hand_run(dngd_stage_damage_grid_ms_sum=2100.0,
                   dngd_stage_damage_grid_ms_count=1000, **ROWS)
    got = {n: reader(n).read(run) for n in HOST_SIDE}
    assert got == pytest.approx({
        "mask_grid_mean_ms": 2.1, "mask_rows_damaged_pct": 15.4,
        "mask_rows_coded_pct": 28.0, "mask_row_program_pct": 90.0})
    assert (got["mask_rows_damaged_pct"] <= got["mask_rows_coded_pct"]
            <= 100.0)


def test_the_device_readers_on_a_hand_made_run():
    from benchmark.layer_metrics import _mask

    run = hand_run(**ROWS)
    assert reader("mask_gather_ms").read(run) == pytest.approx(0.4)
    assert reader("mask_scatter_ms").read(run) == pytest.approx(0.1)
    # 6 x 4 + 2 x 8 rows; a row: (16+26) x (2560+26) of luma and twice
    # (8+26) x (1280+26) of chroma in, 16 x 2560 + 2 x 8 x 1280 out
    assert _mask.traced_rows(run) == 40
    assert _mask.row_move_bytes(2560) == 42 * 2586 + 2 * 34 * 1306 + 61440
    want = 40 * _mask.row_move_bytes(2560) / 819e9 / 0.0050
    assert reader("mask_move_hbm_pct").read(run) == pytest.approx(100 * want)
    assert 0 < 100 * want < 100
    # the whole picture in one frame of one microsecond a row would still
    # read under 100: the count is the least a move can be
    assert 100 * _mask.row_move_bytes(2560) / 819e9 / 1e-6 < 100


@pytest.mark.parametrize("name", sorted(
    n for n in READERS if READERS[n][1] == "device_trace"))
def test_a_device_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """Untraced; a span in which no frame went through the row program (an
    IDR and dense frames, or the parent's one program without the mask's
    scopes); a device that ``peaks.json`` does not hold."""
    read = reader(name).read
    assert read(hand_run(None, **ROWS) | {"stages": None}) is None
    dense = {k: v for k, v in PROGRAMS.items() if "rows" not in k}
    assert read(hand_run(dense, frames=2, **ROWS)) is None
    parent = {"jit_encode_p_rows": {"device_s": 0.01, "runs": 4, "scopes": {
        "dngd.me_int": 0.004, "(no scope)": 0.004}}}
    assert read(hand_run(parent, frames=4)) is None
    if name == "mask_move_hbm_pct":
        assert read(hand_run(**ROWS) | {"device_kind": "cpu"}) is None
    if name == "mask_scatter_ms":
        # the row program ran and spent nothing there: 0, not nothing
        only_in = {"jit_encode_p_rows_b1": {"device_s": 0.01, "runs": 4,
                   "scopes": {"dngd.mask_gather": 0.002}}}
        assert read(hand_run(only_in, frames=4)) == 0.0


@pytest.mark.parametrize("name", HOST_SIDE)
def test_a_host_side_reader_gives_nothing_to_the_parent(name):
    """The parent's families (no ``damage_grid`` stage, no ``dngd_mask_``
    counter), and a window without a planned frame."""
    read = reader(name).read
    parent = hand_run(dngd_encoder_frames_total=1200,
                      dngd_stage_dispatch_ms_sum=1.0,
                      dngd_stage_dispatch_ms_count=1200)
    assert read(parent) is None
    idle = hand_run(**dict.fromkeys(ROWS, 0),
                    dngd_stage_damage_grid_ms_sum=0.0,
                    dngd_stage_damage_grid_ms_count=0)
    assert read(idle) is None


@pytest.mark.parametrize("family", [
    "dngd_stage_damage_grid_ms_sum", "dngd_stage_damage_grid_ms_count",
    "dngd_mask_rows_total", "dngd_mask_rows_damaged_total",
    "dngd_mask_rows_coded_total", "dngd_mask_rows_gathered_total",
    "dngd_mask_frames_total"])
def test_the_program_renders_the_families_from_import_on(family):
    from docker_nvidia_glx_desktop_tpu.models import h264  # noqa: F401

    assert family in bench_run.program_counters()


@pytest.fixture(scope="module")
def rehearsal():
    """One TRACED rehearsal of the cell at 320x240 (15 macroblock rows: the
    row program's buckets are 1, 2, 4 and 8)."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 400), "--seconds", "4", "--trace", "1",
         "--rehearse", "--geometry", "320x240"],
        capture_output=True, text=True, timeout=900, env=CHILD_ENV)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout, json.loads(r.stdout.strip().splitlines()[-1])


def test_a_rehearsal_of_the_cell_ends_with_all_five_numbers_0(rehearsal):
    _, line = rehearsal
    assert line["correct"] is False                  # a CPU run never is
    assert line["rehearsal"]["correct_before_override"] is True
    assert line["rehearsal"]["compared"] == {
        "undecoded_fragments": 0, "frame_order_faults": 0,
        "p_run_over_gop": 0, "compiles_in_window": 0,
        "closed_loop_luma_maxdiff": 0}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_the_rehearsal_gives_every_host_side_reader_a_value(rehearsal):
    _, line = rehearsal
    got = line["metrics"]
    for name in HOST_SIDE + ["colour_mean_ms", "dispatch_mean_ms",
                             "pull_mean_ms", "assemble_mean_ms",
                             "pull_extra_pct", "submit_mean_ms",
                             "collect_mean_ms"]:
        assert name in got, name
    assert 0 < got["mask_grid_mean_ms"]["value"] < 50
    assert (0 < got["mask_rows_damaged_pct"]["value"]
            <= got["mask_rows_coded_pct"]["value"] <= 100)
    # typing: most P frames are the row program's
    assert got["mask_row_program_pct"]["value"] > 50
    # a CPU run carries no device number
    assert not {"mask_gather_ms", "mask_scatter_ms",
                "mask_move_hbm_pct"} & set(got)


def reference(*extra):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "mask_reference.py"),
         "--workload", CELL, "--seed", str(2**31 + 401), "--rehearse",
         "--geometry", "320x240", "--frames", "6", *extra],
        capture_output=True, text=True, timeout=900, env=CHILD_ENV)


def test_the_reference_check_passes_at_320x240():
    r = reference()
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["frames"] == 7 == line["pictures_decoded"]
    assert line["p_frames"] == 6 == line["frames_exact"]
    assert line["rows_differing"] == 0 == line["rows_not_skipped"]
    assert 6 <= line["rows_that_must_be_coded"] < 6 * 15
    assert line["luma_maxdiff"] == 0 and line["threshold"] == 512
    assert len(set(line["qps"])) > 1                 # the controller walked


def test_the_reference_check_fails_when_a_damaged_row_is_left_out():
    r = reference("--fault", "stale_row")
    assert r.returncode == 1, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["frames_exact"] < line["p_frames"] == 6
    assert line["rows_differing"] > 0
    # the stream itself stays one a decoder follows: the fault is a row the
    # encoder did not code, which only the dense encoder's bytes show
    assert line["luma_maxdiff"] == 0


def test_the_plain_reader_tells_a_skip_row_from_a_coded_one():
    """The reader of (b) on slices made by the program's own writer: an
    all-skip slice of the right row parses, one of another row, one short of
    a macroblock and a coded slice do not."""
    from benchmark import mask_reference as ref
    from docker_nvidia_glx_desktop_tpu.bitstream import h264 as syn
    from docker_nvidia_glx_desktop_tpu.ops import damage_mask

    headers = (syn.nal_unit(syn.NAL_SPS, syn.sps_rbsp(320, 240))
               + syn.nal_unit(syn.NAL_PPS, syn.pps_rbsp()))
    sp = ref.stream_parameters(headers)
    assert sp["mb_w"] == 20 and not sp["cabac"]
    assert sp["frame_num_bits"] == 4 and sp["deblock_control"]
    skip = lambda row, n=20, **kw: ref.nal_units(  # noqa: E731
        damage_mask.skip_slice_nal(row * 20, n, kw.get("frame_num", 3),
                                   kw.get("qp_delta", -2), 2))[0]
    assert ref.all_skip_row(skip(4), sp, 4)
    assert ref.all_skip_row(skip(0, frame_num=15, qp_delta=9), sp, 0)
    assert not ref.all_skip_row(skip(4), sp, 5)
    assert not ref.all_skip_row(skip(4, n=19), sp, 4)
    assert not ref.all_skip_row(skip(4) + b"\x80", sp, 4)
    assert ref.slices_by_row(b"\x00\x00\x00\x01" + skip(7)
                             + b"\x00\x00\x01" + skip(2), 20).keys() == {7, 2}
