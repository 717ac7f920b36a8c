"""The deployment ``desk2160-cabac-mesh4`` (PR 36): its configuration is the
one-chip 4K deployment's with the shards asked for, its cell resolves with
the unlisted readers and its eight, the eight on hand-made runs (a scope
there, a scope absent, counters missing), a rehearsal of the cell at 128x128
over four host devices ends with all five compared numbers 0, and the by-hand
reference check passes there and fails when two shards' rows are swapped
under it."""

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
CELL = "desk2160-cabac-mesh4.fulldamage"
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "desk2160-cabac-mesh4.json").read_text())
CONTROL = json.loads((ROOT / "benchmark" / "configs"
                      / "desk2160-cabac.json").read_text())
MESH, ENGINE = "mesh programs", "host entropy engine"
# reader -> (layer, source, unit, better)
READERS = {
    "mesh_halo_ms": (MESH, "device_trace", "ms", "lower"),
    "mesh_gather_ms": (MESH, "device_trace", "ms", "lower"),
    "mesh_search_ms": (MESH, "device_trace", "ms", "lower"),
    "mesh_binarize_ms": (MESH, "device_trace", "ms", "lower"),
    "mesh_unscoped_ms": (MESH, "device_trace", "ms", "lower"),
    "mesh_stitch_mean_ms": (ENGINE, "program_span", "ms", "lower"),
    "mesh_collective_mib_per_frame": (MESH, "program_counter", "MiB",
                                      "lower"),
    "mesh_collective_ici_pct": (MESH, "device_trace", "%", "higher"),
}
# four host devices for the children, whatever the test process was given
CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
    os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in os.environ.get(
        "XLA_FLAGS", "")
    else "--xla_force_host_platform_device_count=8"))


def reader(name):
    return bench_run.load_by_file("layer_metrics", name)


def test_the_configuration_is_the_one_chip_4k_deployment_with_shards():
    assert CONFIG["name"] == "desk2160-cabac-mesh4"
    assert CONFIG["reduced"] == [] and CONFIG["chips"] == 4
    assert CONFIG["env"] == dict(CONTROL["env"], ENCODER_SPATIAL_SHARDS="4")
    geo = CONFIG["geometry"]
    assert (geo["width"], geo["height"], geo["refresh"]) == (3840, 2160, 30)
    assert geo["coded_height"] == 2176 == 16 * geo["shards"] * geo[
        "shard_rows"]
    assert geo["macroblocks"] == 240 * 136 == 32640
    assert geo["shard_macroblocks"] == 34 * 240 == 8160
    entry = {c["name"]: c for c in MANIFEST["configs"]}[CONFIG["name"]]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["source"] != CONTROL["source"] and entry["reduced"] == []
    # the control's guarantees word for word, and three of the mesh
    assert CONFIG["guarantees"][:len(CONTROL["guarantees"])] == CONTROL[
        "guarantees"]
    said = " ".join(CONFIG["guarantees"][len(CONTROL["guarantees"]):])
    assert "one-chip encoder of the same coded picture" in said
    assert "padding rows never reach the client" in said
    assert "gathered reference is the decoder's picture bit for bit" in said
    assert any(a.startswith("ENCODER_SPATIAL_SHARDS=4 written out, not auto")
               for a in CONFIG["assumed"])


def test_the_coded_picture_is_the_one_the_configuration_states():
    from docker_nvidia_glx_desktop_tpu.bitstream.h264 import level_idc_for
    from docker_nvidia_glx_desktop_tpu.parallel import batch

    geo = CONFIG["geometry"]
    assert batch.feasible_spatial_shards(geo["height"], 4, 4) == geo["shards"]
    assert batch.coded_height(geo["height"], 4) == geo["coded_height"]
    # the level follows the CODED size: 32,640 macroblocks and 979,200 a
    # second are inside level 5.1's 36,864 and 983,040
    assert level_idc_for(geo["width"], geo["coded_height"], 30) == 51


def test_the_readers_hold_the_program_to_the_stated_plan():
    """Loading the mesh readers' helper checks every stated plan against the
    program's planner: this program passes, and one that plans the parent's
    picture (three shards of 2160 lines) does not."""
    from benchmark.layer_metrics import _mesh

    geo = CONFIG["geometry"]
    assert list(_mesh.stated_plans()) == [
        ("desk2160-cabac-mesh4", 2160, 4, 4, geo)]
    _mesh.require_stated_plans()


@pytest.mark.parametrize("stated", [
    {"shards": 3, "coded_height": 2160},      # what the parent plans
    {"shards": 4, "coded_height": 2160},      # the shards without the rows
    {"shards": 4, "coded_height": 2192},
])
def test_a_program_that_plans_another_picture_is_refused(stated):
    from benchmark.layer_metrics import _mesh

    with pytest.raises(_mesh.PlanMismatch, match="cannot run it"):
        _mesh.require_stated_plans([("made-up", 2160, 4, 4, stated)])


def test_a_planner_of_before_pr_36_ends_the_cell_before_the_chip(tmp_path):
    """The cell through run.py on a program whose planner is the parent's
    (native 4K on four chips: three shards, no ``coded_height``): exit code
    1 within seconds, no result line, JAX's devices never asked for."""
    pkg = tmp_path / "docker_nvidia_glx_desktop_tpu" / "parallel"
    pkg.mkdir(parents=True)
    (pkg.parent / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "batch.py").write_text(
        "def feasible_spatial_shards(height, want, n_devices):\n"
        "    rows = -(-height // 16)\n"
        "    return max(n for n in range(1, n_devices + 1) if rows % n == 0)\n")
    # run.py puts its own checkout first on sys.path: a copy of the benchmark
    # beside the made-up program
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", CELL, "--seed", "97804831", "--seconds", "20",
         "--trace", "1"], capture_output=True, text=True, timeout=60,
        env=CHILD_ENV)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "PlanMismatch" in r.stderr and "plans 3 of 2160 lines" in r.stderr
    assert "device:" not in r.stdout and '"metrics"' not in r.stdout
    # an accepted cell lists none of the mesh readers and resolves there
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "desk2160-cabac.fulldamage", "--seed", "1",
         "--seconds", "1", "--resolve-only"], capture_output=True, text=True,
        timeout=60, env=CHILD_ENV)
    assert r.returncode == 0, r.stdout + r.stderr


def test_the_cell_resolves_with_the_unlisted_readers_and_its_eight():
    entry = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "desk2160-cabac-mesh4", "fulldamage", 4)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 36), "--seconds", "1", "--resolve-only"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    found = json.loads(r.stdout.strip().splitlines()[-1])
    assert found["env"] == CONFIG["env"] and found["chips"] == 4
    unlisted = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(READERS) <= listed
    assert set(found["per_layer"]) == set(unlisted) | listed
    for owed in ("dispatch_mean_ms", "pull_mean_ms", "assemble_mean_ms",
                 "pull_extra_pct", "device_ms_per_frame", "device_idle_pct"):
        assert owed in found["per_layer"]


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_manifest_lists_the_reader_for_the_cell_alone(name):
    m = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    layer, source, unit, better = READERS[name]
    assert (m["layer"], m["source"], m["unit"], m["better"], m["moves"]) == (
        layer, source, unit, better, "g2g_p50_ms")
    assert m["workloads"] == [CELL]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}


# chip 0's view of a traced span: two frames of the mesh's P program, the
# statistics' program beside them (its unscoped time is not the shard's)
PROGRAMS = {
    "jit_encode_p_mesh": {"device_s": 0.0200, "runs": 2, "scopes": {
        "dngd.me_subpel": 0.0060, "dngd.me_int": 0.0030,
        "dngd.binarize": 0.0050, "dngd.halo": 0.0004, "dngd.mc": 0.0020,
        "dngd.deblock_edges": 0.0006, "(no scope)": 0.0030}},
    "jit_frame_stats": {"device_s": 0.0020, "runs": 2, "scopes": {
        "dngd.frame_stats": 0.0015, "(no scope)": 0.0005}},
}
BYTES = {"dngd_mesh_halo_bytes_total": 2 * 199680,
         "dngd_mesh_gather_bytes_total": 0,
         "dngd_encoder_frames_total": 2}


def hand_run(programs=PROGRAMS, frames=2, share=0.85, **families):
    return {"stages": {"frames": frames, "scoped_share": share,
                       "programs": programs},
            "counters_start": {k: 0.0 for k in families},
            "counters_end": {k: float(v) for k, v in families.items()},
            "device_kind": "TPU v5 lite"}


def test_the_device_readers_on_a_hand_made_run_under_90_percent_scoped():
    run = hand_run(**BYTES)
    got = {n: reader(n).read(run) for n in READERS
           if READERS[n][1] == "device_trace"}
    # received a frame: 199,680 B at 200 GB/s are 0.9984 us, of 200 us
    assert got == pytest.approx({
        "mesh_halo_ms": 0.2, "mesh_gather_ms": 0.0, "mesh_search_ms": 4.5,
        "mesh_binarize_ms": 2.5, "mesh_unscoped_ms": 1.5,
        "mesh_collective_ici_pct": 100 * (199680 / 200e9) / 0.2e-3})
    assert 0 < got["mesh_collective_ici_pct"] < 100


def test_a_gather_in_the_programs_is_read_and_counted():
    programs = {"jit_encode_p_mesh": dict(PROGRAMS["jit_encode_p_mesh"],
                scopes=dict(PROGRAMS["jit_encode_p_mesh"]["scopes"],
                            **{"dngd.gather": 0.0080}))}
    run = hand_run(programs, **dict(
        BYTES, dngd_mesh_gather_bytes_total=2 * 3 * 36452520))
    assert reader("mesh_gather_ms").read(run) == pytest.approx(4.0)
    assert reader("mesh_collective_mib_per_frame").read(run) == \
        pytest.approx((199680 + 3 * 36452520) / 2 ** 20)
    assert reader("mesh_collective_ici_pct").read(run) == pytest.approx(
        100 * ((199680 + 3 * 36452520) / 200e9) / 4.2e-3)


@pytest.mark.parametrize("name", sorted(
    n for n in READERS if READERS[n][1] == "device_trace"))
def test_a_device_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """Untraced; the parent's programs (``jit_shard_fn``: no frame is
    counted); one-chip programs without the mesh's scopes; a device that
    ``peaks.json`` does not hold."""
    read = reader(name).read
    assert read(hand_run(None, **BYTES) | {"stages": None}) is None
    parent = {"jit_shard_fn": PROGRAMS["jit_encode_p_mesh"]}
    assert read(hand_run(parent, frames=0, **BYTES)) is None
    if name in ("mesh_halo_ms", "mesh_gather_ms", "mesh_collective_ici_pct"):
        one_chip = {"jit_encode_p_frame": {"device_s": 0.01, "runs": 2,
                    "scopes": {"dngd.me_int": 0.004, "(no scope)": 0.001}}}
        assert read(hand_run(one_chip, **BYTES)) is None
    if name == "mesh_collective_ici_pct":
        assert read(hand_run(**BYTES) | {"device_kind": "cpu"}) is None
        assert read(hand_run()) is None              # counters missing


def test_the_span_and_counter_readers_and_what_they_give_the_parent():
    run = hand_run(dngd_stage_stitch_ms_sum=150.0,
                   dngd_stage_stitch_ms_count=300, **BYTES)
    assert reader("mesh_stitch_mean_ms").read(run) == pytest.approx(0.5)
    assert reader("mesh_collective_mib_per_frame").read(run) == \
        pytest.approx(199680 / 2 ** 20)
    parent = hand_run(dngd_encoder_frames_total=300,
                      dngd_stage_assemble_ms_sum=1.0,
                      dngd_stage_assemble_ms_count=300)
    assert reader("mesh_stitch_mean_ms").read(parent) is None
    assert reader("mesh_collective_mib_per_frame").read(parent) is None
    no_frames = hand_run(**dict(BYTES, dngd_encoder_frames_total=0))
    assert reader("mesh_collective_mib_per_frame").read(no_frames) is None


@pytest.mark.parametrize("family", [
    "dngd_stage_stitch_ms_sum", "dngd_stage_stitch_ms_count",
    "dngd_mesh_halo_bytes_total", "dngd_mesh_gather_bytes_total",
    "dngd_mesh_shards"])
def test_the_program_renders_the_families_from_import_on(family):
    from docker_nvidia_glx_desktop_tpu.models import h264  # noqa: F401

    assert family in bench_run.program_counters()


@pytest.fixture(scope="module")
def rehearsal():
    """One untraced rehearsal of the cell at 128x128 (8 macroblock rows,
    four shards of 2 on four host devices)."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 360), "--seconds", "2", "--trace", "0",
         "--rehearse", "--geometry", "128x128"],
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
    assert {"delivered_fps", "g2g_p50_ms", "g2g_p95_ms", "psnr_p50_db",
            "setup_s"} == set(line["metrics"])
    assert line["device"]["count"] >= 4


def reference(*extra):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "mesh_reference.py"),
         "--workload", CELL, "--seed", str(2**31 + 361), "--rehearse",
         "--geometry", "128x128", *extra],
        capture_output=True, text=True, timeout=900, env=CHILD_ENV)


def test_the_reference_check_passes_at_128x128():
    r = reference()
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["shards"] == 4 and line["frames"] == 8
    assert line["bytes_identical"] == 8 == line["pictures_decoded"]
    assert line["luma_maxdiff"] == 0
    assert line["coded"] == [128, 128] == line["decoded_size"]
    assert len(set(line["qps"])) > 1                 # the controller walked


def test_the_reference_check_fails_when_two_shards_rows_are_swapped():
    r = reference("--fault", "swap_rows", "--frames", "4")
    assert r.returncode == 1, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["bytes_identical"] < line["frames"] == 4
    assert line["luma_maxdiff"] > 0
