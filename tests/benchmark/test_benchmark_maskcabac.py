"""The deployment ``desk1600-cabac-mask`` (PR 43): its configuration is
``desk1600-mask``'s under the CABAC stream (``desk1080-cabac``'s two knobs),
its one cell resolves with the unlisted readers and its eleven, the eleven on
hand-made runs (scopes there, scopes absent, counters missing), loading them
refuses a program whose row step is specialized on ``qp`` AND a program that
drops the mask under CABAC, a traced rehearsal of the cell at 128x96 gives
every host-side reader a value and ends with all five compared numbers 0, and
the by-hand reference check (its own CABAC decoder among it) passes there and
fails when a damaged row is left out of the plan under it."""

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
CELL = "desk1600-cabac-mask.desktop"
CONFIGS = ROOT / "benchmark" / "configs"
CONFIG = json.loads((CONFIGS / "desk1600-cabac-mask.json").read_text())
MASK = json.loads((CONFIGS / "desk1600-mask.json").read_text())
CABAC = json.loads((CONFIGS / "desk1080-cabac.json").read_text())
DMG, FRONT = "damage mask", "session loop and encoder front"
ENGINE, DEVICE = "host entropy engine", "device programs"
# reader -> (layer, source, unit, better, moves)
READERS = {
    "maskcabac_rows_damaged_pct": (DMG, "program_counter", "%", "lower",
                                   "delivered_fps"),
    "maskcabac_rows_coded_pct": (DMG, "program_counter", "%", "lower",
                                 "delivered_fps"),
    "maskcabac_row_program_pct": (DMG, "program_counter", "%", "higher",
                                  "g2g_p50_ms"),
    "maskcabac_grid_mean_ms": (FRONT, "program_span", "ms", "lower",
                               "g2g_p50_ms"),
    "maskcabac_engine_mean_ms": (ENGINE, "program_span", "ms", "lower",
                                 "g2g_p50_ms"),
    "maskcabac_skip_slices_mean_ms": (ENGINE, "program_span", "ms", "lower",
                                      "g2g_p50_ms"),
    "maskcabac_record_kib_per_frame": (ENGINE, "program_counter", "KiB",
                                       "lower", "g2g_p50_ms"),
    "maskcabac_binarize_ms": (DEVICE, "device_trace", "ms", "lower",
                              "g2g_p50_ms"),
    "maskcabac_search_ms": (DEVICE, "device_trace", "ms", "lower",
                            "g2g_p50_ms"),
    "maskcabac_move_hbm_pct": (DMG, "device_trace", "%", "higher",
                               "g2g_p50_ms"),
    "maskcabac_fallback_pct": (ENGINE, "program_counter", "%", "lower",
                               "g2g_p95_ms"),
}
HOST_SIDE = sorted(n for n in READERS if READERS[n][1] != "device_trace")
ON_DEVICE = sorted(set(READERS) - set(HOST_SIDE))
CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def reader(name):
    return bench_run.load_by_file("layer_metrics", name)


def last_line(r) -> dict:
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_configuration_is_the_mask_deployment_under_the_cabac_stream():
    assert CONFIG["name"] == "desk1600-cabac-mask"
    assert CONFIG["reduced"] == [] and CONFIG["chips"] == 1
    assert CONFIG["env"] == dict(MASK["env"], ENCODER_ENTROPY="cabac",
                                 ENCODER_CABAC_BINARIZE="device")
    assert CONFIG["geometry"] == MASK["geometry"]
    assert CONFIG["geometry"]["row_buckets"] == [1, 2, 4, 8, 16, 32, 64]
    entries = [c["name"] for c in MANIFEST["configs"]]
    entry = MANIFEST["configs"][entries.index(CONFIG["name"])]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and set(entry) == {
        "name", "source", "file", "reduced", "why"}
    assert entry["source"] not in (MASK["source"], CABAC["source"])
    # appended: behind every entry that was there (no position pinned)
    assert entries.index(CONFIG["name"]) > entries.index("desk1600-mask")
    # the CABAC deployment's seven guarantees and the mask's two, word for
    # word, none weakened, and the one this deployment adds
    assert CONFIG["guarantees"][:7] == CABAC["guarantees"]
    assert CONFIG["guarantees"][7:9] == MASK["guarantees"][6:]
    (not_dropped,) = CONFIG["guarantees"][9:]
    assert "the mask is not dropped" in not_dropped
    assert "maskcabac_row_program_pct reads 100" in not_dropped
    said = " ".join(CONFIG["assumed"])
    assert "commented out" in said and "DEGRADE_ENABLE=false" in said
    assert "ENCODER_CABAC_BINARIZE=device" in said
    assert "DNGD_CONTENT_DAMAGE_THR at its default" in said


def test_the_program_serves_what_the_file_states():
    from docker_nvidia_glx_desktop_tpu.obs import content
    from docker_nvidia_glx_desktop_tpu.ops import damage_mask

    geo = CONFIG["geometry"]
    assert damage_mask.bucket_ladder(geo["rows"]) == geo["row_buckets"]
    assert "DNGD_CONTENT_DAMAGE_THR" not in CONFIG["env"]
    assert content.damage_thr_sad() == 512
    assert "cabac" in damage_mask.MASKED_ENTROPY


def test_the_cell_resolves_with_the_unlisted_readers_and_its_eleven():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    entry = MANIFEST["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "desk1600-cabac-mask", "desktop", 1)
    assert len(entry["why"]) <= 200
    assert cells.index(CELL) > cells.index("desk1600-mask.desktop")
    assert sum(w["config"] == "desk1600-cabac-mask"
               for w in MANIFEST["workloads"]) == 1         # its one cell
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 43), "--seconds", "1", "--resolve-only"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    found = last_line(r)
    assert found["env"] == CONFIG["env"] and found["chips"] == 1
    assert found["generator"] == "desktop"
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    # as a SET: a reader a later PR appends for this cell moves nothing here
    assert set(found["per_layer"]) >= unlisted | set(READERS)
    assert {m["name"] for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", ())} >= set(READERS)
    for owed in ("colour_mean_ms", "dispatch_mean_ms", "pull_mean_ms",
                 "assemble_mean_ms", "pull_extra_pct", "device_ms_per_frame",
                 "device_idle_pct"):
        assert owed in found["per_layer"]
    # no accepted list was touched: the mask cell's and the dense CABAC
    # cells' readers do not list this cell
    for m in MANIFEST["per_layer"]:
        if m["name"].startswith(("mask_", "cabac_")):
            assert CELL not in m["workloads"], m["name"]


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_manifest_lists_the_reader_for_the_cell(name):
    m = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    assert (m["layer"], m["source"], m["unit"], m["better"],
            m["moves"]) == READERS[name]
    assert CELL in m["workloads"]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert (ROOT / "benchmark" / "layer_metrics" / f"{name}.py").is_file()


def test_the_layers_are_ones_the_manifest_already_names():
    before = {m["layer"] for m in MANIFEST["per_layer"]
              if not m["name"].startswith("maskcabac_")}
    assert {v[0] for v in READERS.values()} <= before


# -- the load-time refusals ---------------------------------------------------

def test_the_readers_hold_the_program_to_a_traced_qp_and_a_kept_mask():
    from benchmark.layer_metrics import _mask, _maskcabac
    from docker_nvidia_glx_desktop_tpu.ops import damage_mask

    _mask.require_traced_row_step()
    _maskcabac.require_masked_cabac()
    assert "qp" not in damage_mask.ROW_STEP_DYNQP_STATIC


@pytest.mark.parametrize("masked", [("device",), None, "no_row_step"])
def test_a_program_that_drops_the_mask_under_cabac_is_refused(
        masked, monkeypatch):
    """A program whose mask names the CAVLC path alone, one that does not
    say (the parent), one that says ``cabac`` and has no row step for it."""
    from benchmark.layer_metrics import _maskcabac
    from docker_nvidia_glx_desktop_tpu.ops import damage_mask

    if masked is None:
        monkeypatch.delattr(damage_mask, "MASKED_ENTROPY")
    elif masked == "no_row_step":
        monkeypatch.delattr(damage_mask, "row_step_cabac")
    else:
        monkeypatch.setattr(damage_mask, "MASKED_ENTROPY", masked)
    with pytest.raises(_maskcabac.MaskDropped,
                       match="cannot run a masked CABAC cell"):
        _maskcabac.require_masked_cabac()


def program_copy(tmp_path, damage_mask_source: str):
    """The benchmark's files beside a program that is ``damage_mask_source``
    and nothing else."""
    pkg = tmp_path / "docker_nvidia_glx_desktop_tpu" / "ops"
    pkg.mkdir(parents=True)
    (pkg.parent / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "damage_mask.py").write_text(damage_mask_source)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


@pytest.mark.parametrize("program,refusal,said", [
    # before PR 40: one row step, qp static, and no word on either
    ("def encode_p_rows(*a, qp, **k):\n    raise NotImplementedError\n",
     "StaticRowStep", "specialized on qp"),
    # the parent of PR 43: the row step traces qp, the mask is CAVLC's alone
    ("ROW_STEP_DYNQP_STATIC = ('tune', 'p_intra', 'deblock')\n"
     "def row_step(bucket):\n    raise NotImplementedError\n",
     "MaskDropped", "drops DNGD_DAMAGE_MASK without a word")],
    ids=["before_pr_40", "the_parent_of_pr_43"])
def test_such_a_program_ends_the_cell_before_the_chip(
        program, refusal, said, tmp_path):
    """The cell through run.py: exit code 1 within seconds, no result line,
    JAX's devices never asked for; the controls resolve there."""
    root = program_copy(tmp_path, program)
    r = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"),
         "--workload", CELL, "--seed", "97804843", "--seconds", "20",
         "--trace", "1"], capture_output=True, text=True, timeout=60,
        env=CHILD_ENV)
    assert r.returncode == 1, r.stdout + r.stderr
    assert refusal in r.stderr and said in r.stderr
    assert "device:" not in r.stdout and '"metrics"' not in r.stdout
    for control in ("desk1080-cabac.desktop", "desk1600.desktop"):
        r = subprocess.run(
            [sys.executable, str(root / "benchmark" / "run.py"),
             "--workload", control, "--seed", "1", "--seconds", "1",
             "--resolve-only"], capture_output=True, text=True, timeout=60,
            env=CHILD_ENV)
        assert r.returncode == 0, r.stdout + r.stderr
        assert not set(READERS) & set(last_line(r)["per_layer"])


# -- the readers on hand-made runs --------------------------------------------

# the chip's view of a traced span of ten frames: an IDR (its programs and
# its binarize), a dense P frame with its loop filter and binarize, six
# frames of the row program at a bucket of 4 and two at 8 with their
# binarize at the band's shape, the statistics' program beside every one
PROGRAMS = {
    "jit_encode_p_rows_cabac_b4": {"device_s": 0.0150, "runs": 6, "scopes": {
        "dngd.mask_gather": 0.0030, "dngd.mask_scatter": 0.0006,
        "dngd.me_int": 0.0040, "dngd.me_subpel": 0.0030,
        "(no scope)": 0.0010}},
    "jit_encode_p_rows_cabac_b8": {"device_s": 0.0080, "runs": 2, "scopes": {
        "dngd.mask_gather": 0.0010, "dngd.mask_scatter": 0.0004,
        "dngd.me_int": 0.0020, "dngd.me_subpel": 0.0010,
        "(no scope)": 0.0006}},
    "jit_binarize_p": {"device_s": 0.0110, "runs": 9, "scopes": {
        "dngd.binarize": 0.0100}},
    "jit_binarize_intra": {"device_s": 0.0030, "runs": 1, "scopes": {
        "dngd.binarize": 0.0030}},
    "jit_encode_p_frame": {"device_s": 0.0150, "runs": 1, "scopes": {
        "dngd.me_int": 0.0050, "dngd.me_subpel": 0.0040,
        "dngd.ingest": 0.0020}},
    "jit_encode_intra_frame_yuv": {"device_s": 0.0100, "runs": 1,
                                   "scopes": {"dngd.intra": 0.0090}},
    "jit_deblock_frame": {"device_s": 0.0040, "runs": 2, "scopes": {
        "dngd.deblock_v": 0.0030}},
    "jit_frame_stats": {"device_s": 0.0150, "runs": 10, "scopes": {
        "dngd.frame_stats": 0.0150}},
}
# a window of 1,000 planned P frames of 100 rows, every one the row
# program's (18,000 rows gathered for 5,400 damaged), and 20 IDRs
COUNTERS = {"dngd_mask_rows_total": 100_000,
            "dngd_mask_rows_damaged_total": 5_400,
            "dngd_mask_rows_coded_total": 18_000,
            "dngd_mask_rows_gathered_total": 18_000,
            "dngd_mask_frames_total": 1_000,
            "dngd_encoder_frames_total": 1_020,
            "dngd_encoder_cabac_record_bytes_total": 1_020 * 200 * 1024,
            "dngd_encoder_cabac_fallback_total": 0,
            "dngd_stage_damage_grid_ms_sum": 2100.0,
            "dngd_stage_damage_grid_ms_count": 1000,
            "dngd_stage_engine_ms_sum": 714.0,
            "dngd_stage_engine_ms_count": 1020,
            "dngd_stage_skip_slices_ms_sum": 20.0,
            "dngd_stage_skip_slices_ms_count": 1000}


def hand_run(programs=PROGRAMS, frames=10, **families):
    return {"stages": {"frames": frames, "scoped_share": 0.5,
                       "programs": programs},
            "counters_start": {k: 7.0 for k in families},
            "counters_end": {k: 7.0 + v for k, v in families.items()},
            "device_kind": "TPU v5 lite", "width": 2560}


def test_the_counter_and_span_readers_on_a_hand_made_run():
    run = hand_run(**COUNTERS)
    got = {n: reader(n).read(run) for n in HOST_SIDE}
    assert got == pytest.approx({
        "maskcabac_rows_damaged_pct": 5.4, "maskcabac_rows_coded_pct": 18.0,
        "maskcabac_row_program_pct": 100.0, "maskcabac_grid_mean_ms": 2.1,
        "maskcabac_engine_mean_ms": 0.7,
        "maskcabac_skip_slices_mean_ms": 0.02,
        "maskcabac_record_kib_per_frame": 200.0,
        "maskcabac_fallback_pct": 0.0})
    fell = dict(COUNTERS, dngd_encoder_cabac_fallback_total=51)
    assert reader("maskcabac_fallback_pct").read(
        hand_run(**fell)) == pytest.approx(5.0)
    # a window with dense frames: 100 of the 1,000 reached the ladder's top
    dense = dict(COUNTERS, dngd_mask_rows_coded_total=18_000 + 100 * 100)
    assert reader("maskcabac_row_program_pct").read(
        hand_run(**dense)) == pytest.approx(90.0)


def test_the_device_readers_on_a_hand_made_run():
    """Whatever share is scoped (here half: no nine-tenths rule), over
    every program of the span: the binarizer is no frame's program."""
    from benchmark.layer_metrics import _mask, _maskcabac

    run = hand_run(**COUNTERS)
    assert reader("maskcabac_binarize_ms").read(run) == pytest.approx(1.3)
    assert reader("maskcabac_search_ms").read(run) == pytest.approx(1.9)
    assert _maskcabac.traced_rows(run) == 6 * 4 + 2 * 8
    assert _mask.traced_rows(run) is None       # the CAVLC mask's names
    want = 40 * _mask.row_move_bytes(2560) / 819e9 / 0.0050
    assert reader("maskcabac_move_hbm_pct").read(run) == pytest.approx(
        100 * want)
    assert 0 < 100 * want < 100


@pytest.mark.parametrize("name", ON_DEVICE)
def test_a_device_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """Untraced; a span without the scope (the parent's dense programs from
    a cache that carries no scopes); for the move, a span in which no frame
    went through a row program, and a device ``peaks.json`` does not hold."""
    read = reader(name).read
    assert read(hand_run(None, **COUNTERS) | {"stages": None}) is None
    bare = {k: dict(v, scopes={"(no scope)": v["device_s"]})
            for k, v in PROGRAMS.items()}
    assert read(hand_run(bare, **COUNTERS)) is None
    assert read(hand_run(PROGRAMS, frames=0, **COUNTERS)) is None
    if name == "maskcabac_move_hbm_pct":
        dense = {k: v for k, v in PROGRAMS.items() if "rows" not in k}
        assert read(hand_run(dense, frames=2, **COUNTERS)) is None
        cavlc = {"jit_encode_p_rows_b4": PROGRAMS[
            "jit_encode_p_rows_cabac_b4"]}
        assert read(hand_run(cavlc, frames=6, **COUNTERS)) is None
        assert read(hand_run(**COUNTERS) | {"device_kind": "cpu"}) is None


@pytest.mark.parametrize("name", HOST_SIDE)
def test_a_host_side_reader_gives_nothing_without_its_counter(name):
    """A program without the families (no ``skip_slices`` stage, no
    ``dngd_mask_`` counter, no CABAC counter), and a window without a
    frame: nothing, and nothing raised."""
    read = reader(name).read
    other = hand_run(dngd_stage_dispatch_ms_sum=1.0,
                     dngd_stage_dispatch_ms_count=1200)
    assert read(other) is None
    idle = hand_run(**dict.fromkeys(COUNTERS, 0))
    assert read(idle) is None


@pytest.mark.parametrize("family", sorted(COUNTERS) + [
    "dngd_encoder_cabac_skip_slices_total"])
def test_the_program_renders_the_families_from_import_on(family):
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac  # noqa: F401
    from docker_nvidia_glx_desktop_tpu.models import h264  # noqa: F401
    from docker_nvidia_glx_desktop_tpu.web import session  # noqa: F401

    assert family in bench_run.program_counters()


# -- the cell and its reference check, rehearsed on the CPU -------------------

@pytest.fixture(scope="module")
def rehearsal():
    """One TRACED rehearsal of the cell at 128x96 (six macroblock rows: the
    row programs' buckets are 1, 2 and 4)."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 430), "--seconds", "4", "--trace", "1",
         "--rehearse", "--geometry", "128x96"],
        capture_output=True, text=True, timeout=900, env=CHILD_ENV)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout, last_line(r)


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
    assert 0 < got["maskcabac_grid_mean_ms"]["value"] < 50
    assert (0 < got["maskcabac_rows_damaged_pct"]["value"]
            <= got["maskcabac_rows_coded_pct"]["value"] <= 100)
    # the mask is not dropped: P frames went through row programs
    assert got["maskcabac_row_program_pct"]["value"] > 25
    assert got["maskcabac_skip_slices_mean_ms"]["value"] < 5
    assert got["maskcabac_record_kib_per_frame"]["value"] > 0
    assert got["maskcabac_fallback_pct"]["value"] == 0
    # a CPU run carries no device number
    assert not set(ON_DEVICE) & set(got)


def reference(*extra):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "maskcabac_reference.py"),
         "--workload", CELL, "--seed", str(2**31 + 431), "--rehearse",
         "--geometry", "128x96", "--frames", "8", *extra],
        capture_output=True, text=True, timeout=900, env=CHILD_ENV)


def test_the_reference_check_passes_at_128x96():
    r = reference()
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = last_line(r)
    assert line["frames"] == 9 == line["pictures_decoded"]
    assert line["p_frames"] == 8 == line["frames_exact"]
    assert line["row_program_frames"] > 0
    assert (line["plans_wrong"] == line["rows_not_skipped"]
            == line["units_differing"] == line["rows_differing"] == 0)
    assert line["rows_planned"] >= line["rows_that_must_be_coded"] >= 8
    # every unplanned row went through the file's own decoder
    assert line["rows_decoded_as_all_skip"] == 8 * 6 - line["rows_planned"]
    assert line["rows_decoded_as_all_skip"] > 0
    assert line["luma_maxdiff"] == 0 and line["threshold"] == 512
    assert len(line["qps"]) > 1                      # the controller walked


def test_the_reference_check_fails_when_a_damaged_row_is_left_out():
    r = reference("--fault", "stale_row")
    assert r.returncode == 1, r.stdout[-3000:] + r.stderr[-3000:]
    line = last_line(r)
    assert line["frames_exact"] < line["p_frames"] == 8
    assert line["plans_wrong"] > 0
    # the stream itself stays one a decoder follows: the fault is a row the
    # encoder did not code, which only the plain difference shows
    assert line["luma_maxdiff"] == 0


# -- the reference's own decoder ----------------------------------------------

@pytest.fixture(scope="module")
def skip_reader():
    from benchmark import mask_reference as plain
    from benchmark import maskcabac_reference as ref
    from docker_nvidia_glx_desktop_tpu.bitstream import cabac_tables
    from docker_nvidia_glx_desktop_tpu.bitstream import h264 as syn

    headers = (syn.nal_unit(syn.NAL_SPS, syn.sps_rbsp(128, 96,
                                                      profile="main"))
               + syn.nal_unit(syn.NAL_PPS, syn.pps_rbsp(init_qp=26,
                                                        cabac=True)))
    sp = plain.stream_parameters(headers)
    assert sp["mb_w"] == 8 and sp["cabac"]
    assert ref.pic_init_qp(headers) == 26
    return ref, plain, sp, cabac_tables.engine_tables()


def skip_slices(qp: int, frame_num: int, nr=6, nc=8) -> list:
    """The Python coder's all-skip picture, a NAL unit a row."""
    import numpy as np

    from benchmark import mask_reference as plain
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac

    zeros = {"mv": np.zeros((nr, nc, 2), np.int32),
             "luma": np.zeros((nr, nc, 16, 16), np.int32),
             "cb_dc": np.zeros((nr, nc, 4), np.int32),
             "cb_ac": np.zeros((nr, nc, 4, 15), np.int32),
             "cr_dc": np.zeros((nr, nc, 4), np.int32),
             "cr_ac": np.zeros((nr, nc, 4, 15), np.int32)}
    return zeros, plain.nal_units(h264_cabac.encode_p_picture(
        zeros, qp=qp, frame_num=frame_num, qp_delta=qp - 26,
        deblocking_idc=2, use_native=False))


@pytest.mark.parametrize("qp", [0, 13, 26, 37, 51])
def test_the_references_decoder_reads_an_all_skip_row(skip_reader, qp):
    ref, plain, sp, tables = skip_reader
    _, nals = skip_slices(qp, frame_num=qp % 16)
    for row, nal in enumerate(nals):
        assert ref.all_skip_cabac_row(nal, sp, row, 26, tables) == (None, qp)
    # ... of another row, and with a byte behind it, it is not
    assert ref.all_skip_cabac_row(nals[2], sp, 3, 26, tables)[0] is not None
    assert ref.all_skip_cabac_row(nals[2] + b"\x80", sp, 2, 26,
                                  tables)[0] == "bytes behind the last " \
                                                "macroblock"


def test_the_references_decoder_tells_a_coded_row_from_a_skipped_one(
        skip_reader):
    """One macroblock of a row with a vector, or with a level: the slice
    decodes as not skipped at that macroblock (or runs out of its syntax),
    never as an all-skip row."""
    import numpy as np

    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac

    ref, plain, sp, tables = skip_reader
    zeros, _ = skip_slices(30, 1)
    for mb, change in ((0, "mv"), (5, "mv"), (7, "luma")):
        lv = {k: v.copy() for k, v in zeros.items()}
        if change == "mv":
            lv["mv"][3, mb] = (4, -8)
        else:
            lv["luma"][3, mb, 2, 0] = 3
        nals = plain.nal_units(h264_cabac.encode_p_picture(
            lv, qp=30, frame_num=1, qp_delta=4, deblocking_idc=2,
            use_native=False))
        fault, _ = ref.all_skip_cabac_row(nals[3], sp, 3, 26, tables)
        assert fault is not None and (
            fault == f"macroblock {mb} is not skipped"
            or "end_of_slice" in fault or "syntax" in fault), fault
        assert ref.all_skip_cabac_row(nals[2], sp, 2, 26, tables) == (
            None, 30)
    assert np.asarray(tables[0]).shape == (64, 4)
