"""The seven readers PR 38 brings, of what the session thread's turn says of
itself: how long a finished frame waited for its collect, the turn's own
length, its slack, the statistics' pull and the loop's tail (the program's
histograms over the window), and the device's idle time under the
end-of-turn wait and under no span (``stage_reduce``'s idle gaps over the
traced span).  Nothing from the parent of the PR that added the families."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench  # noqa: E402

FRONT = "session loop and encoder front"
FRAMES = "dngd_encoder_frames_total"
CELLS = ["desk1080.desktop", "desk1600.fulldamage", "desk1080.fulldamage",
         "desk1080-cabac.fulldamage", "desk1080-cabac.desktop",
         "desk2160-cabac.fulldamage", "desk1600.desktop",
         "desk2160-cabac-mesh4.fulldamage"]
# reader -> (the histogram it takes the mean of, unit, better, layer, moves,
# source)
MEANS = {
    "ready_wait_mean_ms": ("dngd_session_ready_wait_ms", "ms", "lower",
                           FRONT, "g2g_p50_ms", "program_span"),
    "turn_mean_ms": ("dngd_session_turn_ms", "ms", "lower", FRONT,
                     "delivered_fps", "program_span"),
    "stats_mean_ms": ("dngd_stage_stats_ms", "ms", "lower", FRONT,
                      "g2g_p50_ms", "program_span"),
    "publish_mean_ms": ("dngd_stage_publish_ms", "ms", "lower", FRONT,
                        "delivered_fps", "program_span"),
}
OTHERS = {
    "await_ms_per_frame": ("dngd_stage_await_ms", "ms", "higher", FRONT,
                           "delivered_fps", "program_span"),
    "idle_under_await_pct": (None, "%", "higher", "device",
                             "delivered_fps", "device_trace"),
    "idle_between_spans_pct": (None, "%", "lower", "device",
                               "delivered_fps", "device_trace"),
}
SEVEN = {**MEANS, **OTHERS}


def read(name, run):
    return bench.load_by_file("layer_metrics", name).read(run)


def window(frames=1189.0, **histograms):
    """Two readings of ``/metrics`` a window apart: ``name=(sum, count)`` of
    the window, on top of what set-up had left in each family."""
    start, end = {FRAMES: 7310.0}, {FRAMES: 7310.0 + frames}
    for fam, (total, n) in histograms.items():
        start.update({fam + "_sum": 812.5, fam + "_count": 130.0})
        end.update({fam + "_sum": 812.5 + total, fam + "_count": 130.0 + n})
    return {"counters_start": start, "counters_end": end}


@pytest.mark.parametrize("name", sorted(MEANS))
def test_a_mean_is_the_windows_sum_over_the_windows_count(name):
    fam = MEANS[name][0]
    assert read(name, window(**{fam: (11890.0, 1189.0)})) == 10.0
    # every sample 0.0 (the thread waited for the device all along) is a
    # reading; no sample at all is none
    assert read(name, window(**{fam: (0.0, 990.0)})) == 0.0
    assert read(name, window(**{fam: (0.0, 0.0)})) is None


@pytest.mark.parametrize("name", sorted(MEANS) + ["await_ms_per_frame"])
@pytest.mark.parametrize("missing", ["counters_start", "counters_end"])
def test_an_older_program_gives_nothing(name, missing):
    fam = SEVEN[name][0]
    run = window(**{fam: (5000.0, 1000.0)})
    for part in ("_sum", "_count"):
        del run[missing][fam + part]
    assert read(name, run) is None


def test_the_slack_is_the_waits_sum_over_the_frames():
    """Over the FRAMES: a cell in which one turn in three has time left
    reads a third of that turn's wait, and a cell whose turns never wait
    (the family is there, nothing was added to it) reads 0.0."""
    fam = "dngd_stage_await_ms"
    assert read("await_ms_per_frame",
                window(1200.0, **{fam: (2400.0, 400.0)})) == 2.0
    assert read("await_ms_per_frame", window(990.0, **{fam: (0.0, 0.0)})) \
        == 0.0
    assert read("await_ms_per_frame", window(0.0, **{fam: (0.0, 0.0)})) \
        is None


def traced(gaps, window_s=0.4, await_family=True):
    run = window(**({"dngd_stage_await_ms": (5000.0, 1000.0)}
                    if await_family else {}))
    run["trace"] = {"busy_s": 0.24, "window_s": window_s, "frames": 24}
    run["stages"] = {"frames": 24, "scoped_share": 0.97, "programs": {},
                     "host_spans": 400, "idle_gaps": gaps}
    return run


GAPS = [["dngd.await", 0.1], ["dngd.colour", 0.04], ["between spans", 0.002],
        ["dngd.publish", 0.001]]


def test_idle_shares_are_a_labels_seconds_over_the_traced_span():
    assert read("idle_under_await_pct", traced(GAPS)) == pytest.approx(25.0)
    assert read("idle_between_spans_pct", traced(GAPS)) == pytest.approx(0.5)


@pytest.mark.parametrize("name", ["idle_under_await_pct",
                                  "idle_between_spans_pct"])
def test_idle_shares_read_zero_without_such_a_gap_and_nothing_without_more(
        name):
    assert read(name, traced([["dngd.pull", 0.02]])) == 0.0
    assert read(name, traced([])) == 0.0
    # the parent: its turn has no span at its end, so its gaps there read
    # ``between spans`` for want of one: another quantity
    assert read(name, traced(GAPS, await_family=False)) is None
    for part in ("trace", "stages"):                # an untraced run
        run = traced(GAPS)
        run[part] = None
        assert read(name, run) is None


def test_the_labels_are_stage_reduces_own():
    """``between spans`` is a literal in ``benchmark/stage_reduce.py``: a
    gap that no span covers still gets that label, and the span's name is
    the stage's."""
    from benchmark import stage_reduce
    from benchmark.layer_metrics import _idle
    from docker_nvidia_glx_desktop_tpu.obs import trace as obst

    ms = 10 ** 9                                    # picoseconds
    planes = {
        "/device:TPU:0": {stage_reduce.MODULES_LINE: [
            ("jit_encode_p_frame(1)", 0, 2 * ms, ""),
            ("jit_encode_p_frame(1)", 5 * ms, 7 * ms, ""),
            ("jit_encode_p_frame(1)", 16 * ms, 18 * ms, "")]},
        "/host:CPU": {"thread": [("dngd.await", 8 * ms, 15 * ms, "")]}}
    red = stage_reduce.reduce_planes(planes)
    assert dict(map(tuple, red["idle_gaps"])) == {
        _idle.BETWEEN_SPANS: 0.003, _idle.AWAIT: 0.009}
    assert _idle.AWAIT == stage_reduce.SCOPE_PREFIX + "await"
    assert "await" in obst.TURN_STAGES
    assert _idle.TURN_IS_SPANS == "dngd_stage_await_ms_count"


@pytest.mark.parametrize("name", list(SEVEN))
def test_the_manifest_entry(name):
    _, unit, better, layer, moves, source = SEVEN[name]
    (mine,) = [m for m in bench.load_json(ROOT / "BENCHMARK.json")["per_layer"]
               if m["name"] == name]
    assert mine == {"name": name, "unit": unit, "better": better,
                    "source": source, "layer": layer, "moves": moves,
                    "workloads": CELLS}


def test_the_seven_are_appended_after_what_was_there():
    manifest = bench.load_json(ROOT / "BENCHMARK.json")
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("ready_wait_mean_ms")
    assert names[at:at + 7] == [
        "ready_wait_mean_ms", "turn_mean_ms", "await_ms_per_frame",
        "stats_mean_ms", "publish_mean_ms", "idle_under_await_pct",
        "idle_between_spans_pct"] and set(names[at:at + 7]) == set(SEVEN)
    assert names[at - 1] == "mesh_collective_ici_pct" and at == 45
    # the eight accepted cells, written out: a later cell joins by a
    # ``benchmark`` PR, as with ``locked_take_pct``
    assert CELLS == [w["name"] for w in manifest["workloads"]][:8]


@pytest.mark.parametrize("name", [n for n in SEVEN if SEVEN[n][0]])
def test_the_program_renders_every_family_a_reader_reads(name):
    """From import on, so a reader finds 0 and not nothing before the first
    sample: the text /metrics gives, through run.py's own parser."""
    import docker_nvidia_glx_desktop_tpu.models.h264  # noqa: F401
    import docker_nvidia_glx_desktop_tpu.web.session  # noqa: F401
    from docker_nvidia_glx_desktop_tpu.obs.metrics import REGISTRY

    counters = bench.parse_metrics(REGISTRY.render())
    fam = SEVEN[name][0]
    assert fam + "_sum" in counters and fam + "_count" in counters
    assert FRAMES in counters


def test_every_cell_owes_the_seven():
    manifest = bench.load_json(ROOT / "BENCHMARK.json")
    for cell in CELLS:
        owed = {m["name"] for m in bench.metrics_for(
            cell, manifest["per_layer"])}
        assert set(SEVEN) <= owed
