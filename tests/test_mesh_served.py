"""The spatial mesh as the deployment ``desk2160-cabac-mesh4`` serves it
(PR 36), on 8 virtual CPU devices: four shards, CABAC with the binarization
on the device, the loop filter on, the qp walking.  Every access unit is the
bytes a ONE-chip encoder of the same coded picture emits (``row_align``),
the decoder's pictures are the gathered reference, one compiled program a
kind serves every qp, the programs are named as the benchmark's reductions
count frames, and the path's spans and counters are there.

Tier 1 (``tests/test_spatial.py`` is in conftest's slow set: nothing there is
counted): one module, so one worker compiles the two mesh programs once.
"""

import numpy as np
import pytest

import conftest  # noqa: F401  (forces the 8-device CPU backend)

from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
from docker_nvidia_glx_desktop_tpu.obs.metrics import REGISTRY
from docker_nvidia_glx_desktop_tpu.parallel import batch

W = 64
NX = 4
# IDR first, then five more rungs of the rate ladder, an IDR among them
QPS = [26, 32, 20, 38, 44, 26, 30]
GOP = 4


def _frames(n, h, seed):
    r = np.random.default_rng(seed)
    base = r.integers(0, 256, size=(h, W, 3)).astype(np.uint8)
    return [np.ascontiguousarray(np.roll(base, 2 * i, axis=1))
            for i in range(n)]


def _pair(h):
    """(one chip, mesh) encoders of the same coded picture."""
    kw = dict(entropy="cabac", host_color=True, gop=GOP,
              deblock=True)
    mesh = H264Encoder(W, h, spatial_shards=NX, **kw)
    one = H264Encoder(W, h, row_align=mesh.row_align, **kw)
    assert mesh._spatial_nx == NX and one._spatial_nx == 1
    return one, mesh


def _counters():
    out = {}
    for line in REGISTRY.render().splitlines():
        if line and not line.startswith("#") and "_bucket{" not in line:
            name, _, val = line.rpartition(" ")
            out[name] = float(val)
    return out


@pytest.fixture(scope="module")
def walked(tmp_path_factory):
    """The qp walk at 64x128 (8 rows) and at 64x112 (7 rows, coded as 8
    and cropped), once for the module: per height the access units of
    both encoders, the mesh encoder's reference after every frame, what
    the decoder made of the mesh's stream, the size of the step cache
    after the first P frame and at the end, and the counters around the
    walk."""
    import cv2

    out = {}
    for h in (128, 112):
        one, mesh = _pair(h)
        before = _counters()
        units, refs, steps, ready = [], [], [], []
        for rgb, qp in zip(_frames(len(QPS), h, seed=h), QPS):
            one._forced_qp = mesh._forced_qp = qp
            a = one.encode(rgb)
            token = mesh.encode_submit(rgb)
            ready.append(mesh.token_ready(token))
            b = mesh.encode_collect(token)
            ready.append(mesh.token_ready(token))
            units.append((a, b))
            refs.append(np.array(
                mesh.export_state()["ref"][0][:h, :W]))
            steps.append(len(mesh._sp_steps))
        after = _counters()
        path = str(tmp_path_factory.mktemp("mesh") / f"{h}.h264")
        with open(path, "wb") as f:
            f.write(mesh.headers() + b"".join(b.data for _, b in units))
        cap = cv2.VideoCapture(path)
        cap.set(cv2.CAP_PROP_CONVERT_RGB, 0)
        decoded = []
        while True:
            ok, img = cap.read()
            if not ok:
                break
            decoded.append(np.asarray(img).reshape(-1)[:W * h]
                           .reshape(h, W))
        cap.release()
        shape = (int(cap.get(cv2.CAP_PROP_FRAME_WIDTH)),
                 int(cap.get(cv2.CAP_PROP_FRAME_HEIGHT)))
        out[h] = dict(one=one, mesh=mesh, units=units, refs=refs,
                      steps=steps, decoded=decoded, ready=ready,
                      before=before,
                      after=after, path=path, shape=shape)
    return out


@pytest.mark.parametrize("h", [128, 112])
def test_the_coded_picture_follows_the_mesh(walked, h):
    mesh, one = walked[h]["mesh"], walked[h]["one"]
    assert mesh.pad_h == one.pad_h == batch.coded_height(h, NX) == 128
    assert mesh.row_align == NX and mesh._sp_rows_local() == 2
    assert (mesh.width, mesh.height) == (W, h)       # what hello says
    assert mesh.headers() == one.headers()           # the SPS crops both
    # without shards nothing changes: the parent's rows
    plain = H264Encoder(W, h, entropy="cabac", gop=GOP)
    assert plain.row_align == 1 and plain.pad_h == -(-h // 16) * 16


@pytest.mark.parametrize("h", [128, 112])
def test_every_access_unit_is_the_one_chip_encoders(walked, h):
    units = walked[h]["units"]
    assert [b.keyframe for _, b in units] == [
        i % GOP == 0 for i in range(len(QPS))]
    for i, (a, b) in enumerate(units):
        assert a.keyframe == b.keyframe, f"frame {i}"
        assert a.data == b.data, f"frame {i} (qp {QPS[i]}) diverges"


@pytest.mark.parametrize("h", [128, 112])
def test_the_decoder_shows_the_display_and_the_gathered_reference(walked, h):
    got = walked[h]
    assert len(got["decoded"]) == len(QPS)
    for i, (luma, ref) in enumerate(zip(got["decoded"], got["refs"])):
        assert luma.shape == (h, W)
        assert np.array_equal(luma, ref), f"picture {i}"


@pytest.mark.parametrize("h", [128, 112])
def test_one_program_a_kind_whatever_the_qp(walked, h):
    steps = walked[h]["steps"]
    # the intra step at the IDR, the P step at the first P frame, and no
    # other after it, five more qps and a second IDR later
    assert steps[0] == 1 and steps[1] == 2 and steps[-1] == 2
    assert set(walked[h]["mesh"]._sp_steps) == {("intra", None), ("p", None)}


def test_the_programs_are_named_as_a_frame_is_counted(walked):
    from benchmark.stage_reduce import FRAME_PROGRAM_PREFIX

    mesh = walked[128]["mesh"]
    planes = (np.zeros((128, W), np.uint8), np.zeros((64, W // 2), np.uint8),
              np.zeros((64, W // 2), np.uint8))
    qp = np.int32(26)
    for kind, args in (("intra", planes + (qp,)),
                       ("p", planes + planes + (qp,))):
        jitted = mesh._sp_steps[(kind, None)].__wrapped__
        text = jitted.lower(*args).as_text()
        name = text.split("module @", 1)[1].split()[0]
        assert name.startswith(FRAME_PROGRAM_PREFIX), name
        assert name == f"jit_encode_{kind}_mesh"
        # what the new readers look for, and no gather on this path
        assert ("dngd.halo" in jitted.lower(*args).as_text(
            debug_info=True)) == (kind == "p")
        assert "all_gather" not in text and "all-gather" not in text


@pytest.mark.parametrize("h", [128, 112])
def test_spans_and_counters_of_the_mesh_path(walked, h):
    from benchmark.layer_metrics import _mesh

    got = walked[h]
    d = {k: got["after"].get(k, 0.0) - got["before"].get(k, 0.0)
         for k in got["after"]}
    n, n_p = len(QPS), sum(1 for _, b in got["units"] if not b.keyframe)
    for stage in ("colour", "dispatch", "pull", "stitch", "engine"):
        # one sample a frame from the mesh encoder (the one-chip encoder
        # beside it gives one each too, but no stitch)
        want = n if stage == "stitch" else 2 * n
        assert d[f"dngd_stage_{stage}_ms_count"] == want, stage
    # the encoder's assembly is the first part of a split stage: the
    # session's muxer closes it (tests/test_stage_spans.py; the rehearsal
    # of the cell in tests/benchmark reads assemble_mean_ms), so no
    # sample from an encoder alone, and no second pull on this walk
    assert d["dngd_stage_assemble_ms_count"] == 0
    assert d["dngd_stage_pull_extra_ms_count"] \
        == d["dngd_encoder_pull_extra_total"] == 0
    # the halo: what the arithmetic says one chip receives a P frame
    assert d["dngd_mesh_halo_bytes_total"] == n_p * _mesh.halo_bytes(W, NX)
    assert _mesh.halo_bytes(W, NX) == batch.spatial_halo_bytes(W, NX) \
        == 2 * 13 * (W + W)
    assert _mesh.halo_bytes(3840, 4) == 199680 and _mesh.halo_bytes(W, 2) \
        == 13 * 2 * W
    assert d["dngd_mesh_gather_bytes_total"] == 0
    assert got["after"]["dngd_mesh_shards"] == NX
    # the CABAC path's counters, counted here as on one chip
    assert d["dngd_encoder_cabac_record_bytes_total"] > 0
    assert d["dngd_encoder_h2d_bytes_total"] >= 2 * n * (128 * W * 3 // 2)
    assert d["dngd_encoder_d2h_bytes_total"] > 0
    assert d.get('dngd_encoder_cabac_fallback_total{kind="dense"}', 0) == 0


@pytest.mark.parametrize("h", [128, 112])
def test_a_mesh_token_says_whether_every_shard_is_finished(walked, h):
    """``token_ready``: ``is_ready()`` of the stacked prefix
    ``PrefixPull.pull`` pulls first, one array over every shard; a bool before
    the collect, True after it, and the access units (above) are the
    one-chip encoder's all the same.  One ``stats`` span a collected
    frame."""
    ready = walked[h]["ready"]
    assert len(ready) == 2 * len(QPS)
    assert all(r in (True, False) for r in ready[0::2])
    assert all(r is True for r in ready[1::2])
    d = walked[h]
    assert d["after"]["dngd_stage_stats_ms_count"] \
        - d["before"]["dngd_stage_stats_ms_count"] == len(QPS)


def test_the_pull_ladder_is_warmed_for_the_stacked_buffers():
    """``warm_pulls`` compiles every slice the mesh path's two pulls can
    meet, on this encoder's own mesh and programs; a walk of the guess over
    the whole ladder then compiles nothing."""
    from docker_nvidia_glx_desktop_tpu.analysis.retrace import (
        RetraceTripwire, compile_events_supported)

    if not compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    _, mesh = _pair(128)
    assert mesh.warm_pulls() > 0
    rgb = _frames(2, 128, seed=5)
    mesh.encode(rgb[0])
    mesh.encode(rgb[1])                    # the session's own first frames
    with RetraceTripwire(label="pulls after warm_pulls") as tw:
        for kind in ("intra", "p"):
            pull = mesh._sp_cabac_pull(kind)
            for words in (1, pull.BUCKET + 1, 5 * pull.BUCKET):
                pull.hist.clear()
                pull.guess = pull.rung(words)
                mesh._force_idr = kind == "intra"
                mesh.encode(rgb[1])
    tw.assert_quiet()
