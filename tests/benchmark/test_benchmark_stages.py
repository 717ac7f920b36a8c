"""The readers of the program's stage spans and set-up counters (PR 25),
``stage_reduce``: the reduction from a trace to device time by named stage,
on a hand-made trace and on a piece of a recorded one, and the readers of
its stages (PR 27)."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench_run  # noqa: E402
from benchmark import stage_reduce, trace_reduce  # noqa: E402

TRACE = (ROOT / "benchmark" / "testdata"
         / "v5e_desk1080_stages_21ms.xplane.pb")
FRONT = "session loop and encoder front"

# reader -> (families it reads, layer, moves, source)
READERS = {
    "capture_mean_ms": (["dngd_stage_capture_ms"], FRONT, "delivered_fps",
                        "program_span"),
    "colour_mean_ms": (["dngd_stage_colour_ms"], FRONT, "delivered_fps",
                       "program_span"),
    "dispatch_mean_ms": (["dngd_stage_dispatch_ms"], FRONT, "delivered_fps",
                         "program_span"),
    "pull_mean_ms": (["dngd_stage_pull_ms"], FRONT, "g2g_p50_ms",
                     "program_span"),
    "assemble_mean_ms": (["dngd_stage_assemble_ms"], FRONT, "g2g_p50_ms",
                         "program_span"),
    "ws_send_mean_ms": (["dngd_ws_publish_to_send_ms"], "entry / HTTP / ws",
                        "g2g_p50_ms", "program_span"),
}
COUNTER_READERS = {
    "pull_extra_pct": (["dngd_encoder_pull_extra_total",
                        "dngd_encoder_frames_total"], FRONT, "g2g_p95_ms",
                       "program_counter"),
    "program_load_s": (["dngd_jax_cache_load_seconds_total"], "XLA compile",
                       "setup_s", "program_counter"),
    "program_build_s": (["dngd_jax_trace_lower_seconds_total",
                         "dngd_jax_backend_compile_seconds_total"],
                        "XLA compile", "setup_s", "program_counter"),
}
ALL_READERS = {**READERS, **COUNTER_READERS}
# reader -> ms a frame on the recorded trace (its loop filter is the parent
# of PR 26's, 5 ms; frame_stats ran twice in its one frame)
STAGE_READERS = {
    "me_subpel_ms": 3.4365, "me_int_ms": 1.3469, "slots_ms": 3.0029,
    "pack_ms": 2.8764, "deblock_ms": 5.1583, "frame_stats_ms": 0.6299,
    "other_stages_ms": 1.2528, "unscoped_ms": 0.2558,
}


def reader(name):
    return bench_run.load_by_file("layer_metrics", name)


@pytest.mark.parametrize("name", sorted(ALL_READERS))
def test_reader_returns_nothing_against_an_older_program(name):
    """A program without the span or counter (the parent commit) prints
    fewer metrics; the reader does not raise."""
    old = {"dngd_encoder_frames_total": 5.0,
           "dngd_encoder_submit_ms_sum": 1.0,
           "dngd_encoder_submit_ms_count": 1.0}
    run = {"counters_start": dict(old),
           "counters_end": dict(old, dngd_encoder_frames_total=905.0)}
    assert reader(name).read(run) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_span_reader_is_the_mean_over_the_window(name):
    fam = READERS[name][0][0]
    run = {"counters_start": {fam + "_sum": 100.0, fam + "_count": 40.0},
           "counters_end": {fam + "_sum": 100.0 + 900 * 1.25,
                            fam + "_count": 940.0}}
    assert reader(name).read(run) == pytest.approx(1.25)
    run["counters_end"][fam + "_count"] = 40.0      # no sample: no mean
    assert reader(name).read(run) is None


@pytest.mark.parametrize("extra,want", [(0.0, 0.0), (9.0, 1.0)])
def test_pull_extra_pct_is_a_share_of_the_windows_frames(extra, want):
    """0.0, not nothing, when frames were served and no pull fell short."""
    run = {"counters_start": {"dngd_encoder_pull_extra_total": 3.0,
                              "dngd_encoder_frames_total": 100.0},
           "counters_end": {"dngd_encoder_pull_extra_total": 3.0 + extra,
                            "dngd_encoder_frames_total": 1000.0}}
    got = reader("pull_extra_pct").read(run)
    assert got is not None and got == pytest.approx(want)


def test_setup_readers_read_the_counters_at_the_windows_start():
    run = {"counters_start": {
               "dngd_jax_cache_load_seconds_total": 27.5,
               "dngd_jax_trace_lower_seconds_total": 6.0,
               "dngd_jax_backend_compile_seconds_total": 4.5},
           "counters_end": {
               "dngd_jax_cache_load_seconds_total": 99.0,
               "dngd_jax_trace_lower_seconds_total": 99.0,
               "dngd_jax_backend_compile_seconds_total": 99.0}}
    assert reader("program_load_s").read(run) == 27.5
    assert reader("program_build_s").read(run) == 10.5


def test_manifest_lists_the_nine_after_what_was_there():
    """By name, wherever a later PR's entries put them in the lists."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (_, layer, moves, source) in ALL_READERS.items():
        m = by_name[name]
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            layer, moves, source, "lower")
        assert "workloads" not in m             # read in every cell
    cell = {w["name"]: w for w in manifest["workloads"]}["desk1080.fulldamage"]
    assert cell == {"name": "desk1080.fulldamage", "config": "desk1080",
                    "traffic": "fulldamage", "chips": 1,
                    "why": cell["why"]} and len(cell["why"]) <= 200


HOST_READERS = {"capture_age_p50_ms": "capture_age_ms",
                "taken_to_glass_p50_ms": "taken_to_glass_ms"}


@pytest.mark.parametrize("name", sorted(STAGE_READERS) + sorted(HOST_READERS))
def test_manifest_lists_the_stage_readers(name):
    """The halves of ``g2g_p50_ms`` are the harness's own and read in every
    cell; a stage reader lists the cells whose programs carry its scopes (a
    later configuration on other programs, CABAC's say, must not be held to
    them: a traced run that lacks a metric it owes is refused)."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    m = {m["name"]: m for m in manifest["per_layer"]}[name]
    want = ((FRONT, "host_clock") if name in HOST_READERS
            else ("device programs", "device_trace"))
    assert (m["layer"], m["source"], m["moves"], m["better"], m["unit"]) == (
        *want, "g2g_p50_ms", "lower", "ms")
    if name in HOST_READERS:
        assert "workloads" not in m
    else:
        assert set(m["workloads"]) >= {
            "desk1080.desktop", "desk1080.fulldamage", "desk1600.fulldamage"}


@pytest.mark.parametrize("name", sorted(ALL_READERS))
def test_the_program_renders_every_family_a_reader_reads(name):
    """From import on (the families are registered then), so a reader finds
    0 and not nothing before the first sample: the text /metrics gives,
    through run.py's own parser."""
    import docker_nvidia_glx_desktop_tpu.models.h264  # noqa: F401
    import docker_nvidia_glx_desktop_tpu.web.session  # noqa: F401
    from docker_nvidia_glx_desktop_tpu.obs import procstats
    from docker_nvidia_glx_desktop_tpu.obs.metrics import REGISTRY

    procstats.register_jax_cache_listener()
    counters = bench_run.parse_metrics(REGISTRY.render())
    for fam in ALL_READERS[name][0]:
        if name in READERS:
            assert fam + "_sum" in counters and fam + "_count" in counters
        else:
            assert fam in counters


# -- stage_reduce -------------------------------------------------------------

def hand_made_trace(tmp_path):
    """Two frames of a program with a loop in it, on the schema
    stage_reduce declares: times in picoseconds, names as the v5e gives
    them, the scope in the ``tf_op`` stat of the event METADATA."""
    space = stage_reduce._schema()()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[7].name = "tf_op"
    dev.stat_metadata[8].name = "hlo_category"

    def meta(plane, key, name, op_name=None):
        md = plane.event_metadata[key]
        md.id, md.name = key, name
        if op_name is not None:
            md.stats.add(metadata_id=8, str_value="fusion")
            md.stats.add(metadata_id=7, str_value=op_name)

    meta(dev, 1, "jit_encode_p_cavlc_frame(123)")
    meta(dev, 2, "jit_deblock_frame(456)")
    meta(dev, 10, "%fusion.1 = s32[8]{0} fusion(...)",
         "jit(encode_p_cavlc_frame)/dngd.me_int/while/body/add:")
    meta(dev, 11, "%while.640 = (s32[]) while(...)")       # no op_name
    meta(dev, 12, "%fusion.2 = s32[8]{0} fusion(...)",
         "jit(deblock_frame)/dngd.deblock_edges/while/body/dngd.deblock_v/"
         "select_n:")
    meta(dev, 13, "%copy.3 = u8[8]{0} copy(...)", "jit(deblock_frame)/copy:")
    modules = dev.lines.add(name="XLA Modules", timestamp_ns=1000)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    ms = 10**9                                            # picoseconds
    for f in range(2):
        t = f * 20 * ms
        modules.events.add(metadata_id=1, offset_ps=t, duration_ps=10 * ms)
        modules.events.add(metadata_id=2, offset_ps=t + 11 * ms,
                           duration_ps=5 * ms)
        ops.events.add(metadata_id=10, offset_ps=t + ms, duration_ps=8 * ms)
        # the loop holds two of its body's operations: 4 - 1 - 1.5 its own
        ops.events.add(metadata_id=11, offset_ps=t + 11 * ms,
                       duration_ps=4 * ms)
        ops.events.add(metadata_id=12, offset_ps=t + 11 * ms,
                       duration_ps=ms)
        ops.events.add(metadata_id=12, offset_ps=t + 13 * ms,
                       duration_ps=3 * ms // 2)
        ops.events.add(metadata_id=13, offset_ps=t + 15 * ms,
                       duration_ps=ms // 2)
    host = space.planes.add(name="/host:CPU")
    meta(host, 1, "dngd.encode_collect")
    meta(host, 2, "dngd.pull")
    meta(host, 3, "bench.encode_collect")
    meta(host, 4, "dngd.dispatch")
    thread = host.lines.add(name="python3", timestamp_ns=1000)
    # the gap 10-11 ms lies in pull, which lies in encode_collect; the gap
    # 16-20 ms is covered to under a half by dispatch; the gap 30-31 ms by
    # encode_collect alone
    for key, start, dur in ((3, 9 * ms, 3 * ms), (1, 9 * ms, 3 * ms),
                            (2, 19 * ms // 2, 2 * ms),
                            (4, 37 * ms // 2, 2 * ms),
                            (1, 59 * ms // 2, 2 * ms)):
        thread.events.add(metadata_id=key, offset_ps=start, duration_ps=dur)
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_stage_reduce_on_a_hand_made_trace(tmp_path):
    red = stage_reduce.reduce(hand_made_trace(tmp_path))
    assert red["frames"] == 2 and red["host_spans"] == 4
    p = red["programs"]["jit_encode_p_cavlc_frame"]
    assert p["runs"] == 2 and p["device_s"] == pytest.approx(0.020)
    assert p["scopes"] == pytest.approx({"dngd.me_int": 0.016})
    d = red["programs"]["jit_deblock_frame"]
    assert d["device_s"] == pytest.approx(0.010)
    assert d["scopes"] == pytest.approx({
        "dngd.deblock_v": 0.005,               # the innermost scope
        stage_reduce.NO_SCOPE: 0.003 + 0.001})  # the loop's own + the copy
    assert red["scoped_share"] == pytest.approx(21 / 25)
    assert dict(red["idle_gaps"]) == pytest.approx({
        "dngd.pull": 0.001, "between spans": 0.004,
        "dngd.encode_collect": 0.001})
    text = stage_reduce.table(red)
    assert "dngd.deblock_v" in text and "84.0%" in text


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/dngd.deblock_edges/while/body/dngd.deblock_h/add:",
     "dngd.deblock_h"),
    ("jit(f)/dngd.me_int/while/body/closed_call/sub:", "dngd.me_int"),
    ("jit(f)/jit(_pad)/reshape:", stage_reduce.NO_SCOPE),
    ("", stage_reduce.NO_SCOPE)])
def test_scope_of_is_the_innermost_scope(op_name, scope):
    assert stage_reduce.scope_of(op_name) == scope


def test_stage_reduce_on_a_recorded_trace():
    """21 ms of a traced ``desk1080.desktop`` run of this program on a v5e
    (my chip run, PR 25; empty compile cache), one frame whole: operations
    under a quarter of a microsecond, every stat but ``tf_op`` and every
    host event but the ``dngd.*`` and ``bench.*`` spans were cut away to
    keep the file small, so the loop's own time reads larger than it was."""
    red = stage_reduce.reduce(str(TRACE))
    assert red["frames"] == 1
    progs = red["programs"]
    assert list(progs)[:3] == ["jit_encode_p_cavlc_frame",
                               "jit_deblock_frame", "jit_frame_stats"]
    p = progs["jit_encode_p_cavlc_frame"]
    assert 0.0120 < p["device_s"] < 0.0125
    assert set(p["scopes"]) >= {"dngd.me_int", "dngd.me_subpel", "dngd.mc",
                                "dngd.tq", "dngd.recon", "dngd.slots",
                                "dngd.pack", "dngd.ingest"}
    assert list(p["scopes"])[:3] == ["dngd.me_subpel", "dngd.slots",
                                     "dngd.pack"]
    d = progs["jit_deblock_frame"]["scopes"]
    assert d["dngd.deblock_v"] > 0.0015 and d["dngd.deblock_h"] > 0.0015
    assert list(progs["jit_frame_stats"]["scopes"])[0] == "dngd.frame_stats"
    assert red["scoped_share"] > 0.9
    assert red["host_spans"] >= 4
    assert all(name.startswith("dngd.") or name == "between spans"
               for name, _ in red["idle_gaps"])


def test_the_accepted_reduction_still_reads_the_same_trace():
    """``trace_reduce`` keeps the device's totals and gives them as the
    reduction PR 24 was accepted with gave them (its numbers, from the parent
    of PR 27 over the same file): the window's edges follow the program's
    ``dngd.`` spans where they followed run.py's ``bench.`` wrappers."""
    red = trace_reduce.reduce(str(TRACE))
    assert red == {"busy_s": 0.018042657, "window_s": 0.018086263,
                   "frames": 1, "devices": 1}
    assert "dngd." in trace_reduce.SPAN_PREFIXES


# -- the stage readers (PR 27) --------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return stage_reduce.reduce(str(TRACE))


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_on_the_recorded_trace(name, recorded):
    got = reader(name).read({"stages": recorded})
    assert got == pytest.approx(STAGE_READERS[name], abs=1e-4)


def test_the_stage_readers_sum_to_the_device_time_of_a_frame(recorded):
    """Every program's operations are under one reader and no reader counts
    another's: the sum is ``device_ms_per_frame`` less the programs' time
    between their operations."""
    run = {"stages": recorded, "trace": trace_reduce.reduce(str(TRACE))}
    total = sum(reader(name).read(run) for name in STAGE_READERS)
    whole = reader("device_ms_per_frame").read(run)
    assert whole - 0.1 < total <= whole


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_gives_nothing_under_a_stale_cache(name, recorded):
    """Programs served by a compile cache that a tree without the scopes
    filled read ``(no scope)``: no number, not a wrong one.  Nor without a
    trace, nor from a trace without a frame."""
    stale = dict(recorded, scoped_share=0.89)
    assert reader(name).read({"stages": stale}) is None
    assert reader(name).read({"stages": None}) is None
    assert reader(name).read({"stages": dict(recorded, frames=0)}) is None
    none = {"frames": 2, "scoped_share": 1.0, "programs": {
        "jit_other": {"device_s": 0.002, "runs": 2, "scopes": {}}}}
    assert reader(name).read({"stages": none}) is None      # never 0


def test_stage_readers_over_programs_and_the_loop_filter_apart(tmp_path):
    """``slots`` over two programs; the loop filter whole, and its scopes in
    no other reader."""
    red = stage_reduce.reduce(hand_made_trace(tmp_path))
    red["scoped_share"] = 0.95
    red["programs"]["jit_encode_intra_cavlc_frame_yuv"] = {
        "device_s": 0.004, "runs": 1,
        "scopes": {"dngd.slots": 0.001, "dngd.intra": 0.002,
                   stage_reduce.NO_SCOPE: 0.0005}}
    red["programs"]["jit_encode_p_cavlc_frame"]["scopes"]["dngd.slots"] = 0.003
    run = {"stages": red}
    assert reader("slots_ms").read(run) == pytest.approx(2.0)       # 4 ms / 2
    assert reader("me_int_ms").read(run) == pytest.approx(8.0)
    assert reader("deblock_ms").read(run) == pytest.approx(5.0)
    assert reader("other_stages_ms").read(run) == pytest.approx(1.0)
    assert reader("unscoped_ms").read(run) == pytest.approx(0.25)
    assert reader("me_subpel_ms").read(run) is None


@pytest.mark.parametrize("name", sorted(HOST_READERS))
def test_the_two_halves_of_g2g_are_medians_over_the_window(name):
    """``capture_age_p50_ms`` (taken minus due) and ``taken_to_glass_p50_ms``
    (arrival minus taken), each from its list in ``run``."""
    key = HOST_READERS[name]
    assert reader(name).read({key: [3.0, 1.0, 16.0, 2.0]}) == 2.5
    assert reader(name).read({key: []}) is None
    assert reader(name).read({}) is None


def test_breakdown_lists_programs_and_stages_by_name(recorded):
    ops = stage_reduce.device_ops(recorded)
    assert ops[0][0] == "jit_encode_p_cavlc_frame"
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    names = [n for n, _ in ops]
    assert "jit_encode_p_cavlc_frame/dngd.me_subpel" in names[:4]
    assert "jit_frame_stats" in names
    # a stage that is its whole program is listed once, as the program
    assert "jit_frame_stats/dngd.frame_stats" not in names
    assert not any("fusion" in n or "%" in n for n in names)
