"""The reader PR 39 brings: ``early_collect_pct``, the share of the window's
frames that the session loop collected between the halves of the next
frame's submit, from the program's counters; nothing from a program without
the counter (the parent of the PR that added it), 0.0 from a cell whose
frames are never finished by then."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench  # noqa: E402

from test_benchmark_locked_takes import FRAMES, recorded  # noqa: E402

EARLY = "dngd_session_early_collects_total"


@pytest.mark.parametrize("early,frames,pct", [
    (1105.0, 1189.0, 100.0 * 1105 / 1189),     # a display-paced 1080p window
    (0.0, 990.0, 0.0),                         # 1600p: never finished by then
    (215.0, 436.0, 100.0 * 215 / 436)])        # the mesh: every other turn
def test_reader_reads_a_recorded_counter_pair(early, frames, pct):
    read = bench.load_by_file("layer_metrics", "early_collect_pct").read
    run = recorded(frames, **{EARLY: early})
    assert read(run) == pytest.approx(pct, rel=1e-12)
    assert isinstance(read(run), float)


@pytest.mark.parametrize("missing", ["counters_start", "counters_end"])
def test_a_program_without_the_counter_gives_nothing(missing):
    read = bench.load_by_file("layer_metrics", "early_collect_pct").read
    run = recorded(**{EARLY: 900.0})
    del run[missing][EARLY]
    assert read(run) is None


def test_a_window_without_a_frame_gives_nothing():
    read = bench.load_by_file("layer_metrics", "early_collect_pct").read
    assert read(recorded(0.0, **{EARLY: 0.0})) is None


def test_the_counter_stands_in_metrics_before_the_first_turn():
    from docker_nvidia_glx_desktop_tpu.web import session  # noqa: F401
    seen = bench.program_counters()
    assert EARLY in seen and FRAMES in seen


def manifest():
    return bench.load_json(ROOT / "BENCHMARK.json")


def entry():
    (mine,) = [m for m in manifest()["per_layer"]
               if m["name"] == "early_collect_pct"]
    return mine


def test_the_manifest_entry_is_appended_and_lists_every_cell():
    m = manifest()
    assert m["per_layer"][52] == entry() == {
        "name": "early_collect_pct", "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "session loop and encoder front", "moves": "g2g_p50_ms",
        "workloads": [
            "desk1080.desktop", "desk1600.fulldamage", "desk1080.fulldamage",
            "desk1080-cabac.fulldamage", "desk1080-cabac.desktop",
            "desk2160-cabac.fulldamage", "desk1600.desktop",
            "desk2160-cabac-mesh4.fulldamage"]}
    assert m["per_layer"][51]["name"] == "idle_between_spans_pct"
    assert sorted(entry()["workloads"]) == sorted(
        w["name"] for w in m["workloads"][:8])


@pytest.mark.parametrize("cell", [
    "desk1080.desktop", "desk1600.fulldamage", "desk1080.fulldamage",
    "desk1080-cabac.fulldamage", "desk1080-cabac.desktop",
    "desk2160-cabac.fulldamage", "desk1600.desktop",
    "desk2160-cabac-mesh4.fulldamage"])
def test_the_listed_cells_owe_the_metric(cell):
    spec = bench.resolve_cell(cell)
    owed = bench.metrics_for(cell, spec["manifest"]["per_layer"])
    assert entry() in owed
