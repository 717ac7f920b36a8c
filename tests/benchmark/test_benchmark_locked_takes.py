"""The reader PR 33 brings: ``locked_take_pct``, the share of the window's
frames that the session loop took within a look's step of the display's swap,
from the program's counters; nothing from a program without them (the parent
of the PR that added them), 0.0 from a cell whose turns never have time left."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench  # noqa: E402

FRAMES = "dngd_encoder_frames_total"
LOCKED = "dngd_session_locked_takes_total"
LOOKS = "dngd_session_take_looks_total"


def recorded(frames=1189.0, **moved):
    """Two readings of ``/metrics`` a window apart."""
    start = {FRAMES: 7310.0, **{f: 6502.0 for f in moved}}
    end = {FRAMES: 7310.0 + frames,
           **{f: 6502.0 + v for f, v in moved.items()}}
    return {"counters_start": start, "counters_end": end}


@pytest.mark.parametrize("locked,frames,pct", [
    (1071.0, 1189.0, 100.0 * 1071 / 1189),     # a display-paced 1080p window
    (0.0, 990.0, 0.0),                         # 1600p: no turn has time left
    (1189.0, 1189.0, 100.0)])
def test_reader_reads_a_recorded_counter_pair(locked, frames, pct):
    read = bench.load_by_file("layer_metrics", "locked_take_pct").read
    run = recorded(frames, **{LOCKED: locked, LOOKS: 3.1 * frames})
    assert read(run) == pytest.approx(pct, rel=1e-12)
    assert isinstance(read(run), float)


@pytest.mark.parametrize("missing", ["counters_start", "counters_end"])
def test_a_program_without_the_counter_gives_nothing(missing):
    read = bench.load_by_file("layer_metrics", "locked_take_pct").read
    run = recorded(**{LOCKED: 900.0})
    del run[missing][LOCKED]
    assert read(run) is None


def test_a_window_without_a_frame_gives_nothing():
    read = bench.load_by_file("layer_metrics", "locked_take_pct").read
    assert read(recorded(0.0, **{LOCKED: 0.0})) is None


def test_both_counters_stand_in_metrics_before_the_first_take():
    from docker_nvidia_glx_desktop_tpu.web import session  # noqa: F401
    seen = bench.program_counters()
    assert LOCKED in seen and LOOKS in seen and FRAMES in seen


OWED_BY = ["desk1080.desktop", "desk1600.fulldamage", "desk1080.fulldamage",
           "desk2160-cabac.fulldamage", "desk1080-cabac.fulldamage",
           "desk1080-cabac.desktop", "desk1600.desktop"]


def entry():
    (mine,) = [m for m in bench.load_json(ROOT / "BENCHMARK.json")["per_layer"]
               if m["name"] == "locked_take_pct"]
    return mine


def test_the_manifest_entry():
    mine = dict(entry())
    cells = mine.pop("workloads")
    assert mine == {
        "name": "locked_take_pct", "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "session loop and encoder front", "moves": "g2g_p50_ms"}
    # every cell whose program has the counter: all seven (the two
    # desk1080-cabac cells and desk1600.desktop joined in PR 34)
    assert cells[:len(OWED_BY)] == OWED_BY


@pytest.mark.parametrize("cell", OWED_BY)
def test_the_listed_cells_owe_the_metric(cell):
    spec = bench.resolve_cell(cell)
    owed = bench.metrics_for(cell, spec["manifest"]["per_layer"])
    assert entry() in owed
