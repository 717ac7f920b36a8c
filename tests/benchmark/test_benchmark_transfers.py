"""The two readers ``desk2160-cabac`` brings (PR 32): bytes over the link a
frame, from the program's counters over the window; nothing from a program
without them (the parent of the PR that added them)."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench  # noqa: E402

FRAMES = "dngd_encoder_frames_total"


def recorded(**moved):
    """A window of 600 frames in which each family moved by ``moved``."""
    start = {FRAMES: 240.0, **{f: 1e6 for f in moved}}
    end = {FRAMES: 840.0, **{f: 1e6 + v for f, v in moved.items()}}
    return {"counters_start": start, "counters_end": end}


@pytest.mark.parametrize("name,family,mib", [
    ("h2d_mib_per_frame", "dngd_encoder_h2d_bytes_total", 11.953125),
    ("d2h_mib_per_frame", "dngd_encoder_d2h_bytes_total", 2.5)])
def test_reader_reads_a_recorded_counter_pair(name, family, mib):
    read = bench.load_by_file("layer_metrics", name).read
    run = recorded(**{family: mib * 2 ** 20 * 600})
    assert read(run) == pytest.approx(mib, rel=1e-12)
    # the parent's program has no such counter; a window without a frame
    assert read(recorded()) is None
    run["counters_end"][FRAMES] = run["counters_start"][FRAMES]
    assert read(run) is None


def test_the_new_metrics_are_the_4k_cells_alone():
    spec = bench.resolve_cell("desk2160-cabac.fulldamage")
    mine = {m["name"]: m for m in spec["manifest"]["per_layer"]
            if m["name"] in ("h2d_mib_per_frame", "d2h_mib_per_frame")}
    assert len(mine) == 2
    for m in mine.values():
        assert m["workloads"] == ["desk2160-cabac.fulldamage"]
        assert (m["source"], m["moves"]) == ("program_counter", "g2g_p50_ms")
    env = spec["config"]["env"]
    assert (env["SIZEW"], env["SIZEH"], env["REFRESH"]) == (
        "3840", "2160", "30")
    assert spec["config"]["geometry"]["macroblocks"] == 240 * 136
