"""Content & quality telemetry plane (ISSUE 17), fast tier: device
kernels vs their numpy oracles, stats-vector decoding, the ContentPlane
state machine (gauges, events, SLO quality verdicts, teardown), the
/debug/content endpoint, the budget/capacity annotations, and the
selkies client-QoE ingest.  The GOP-deep bitstream byte-identity runs
live in test_content_identity (slow tier)."""

import asyncio
import json

import numpy as np
import pytest
from aiohttp import ClientSession

from docker_nvidia_glx_desktop_tpu.obs import content as obsc
from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.ops import content_stats as cs
from docker_nvidia_glx_desktop_tpu.utils.config import from_env
from docker_nvidia_glx_desktop_tpu.web.server import bound_port, serve

from conftest import make_test_frame


def run(coro):
    return asyncio.new_event_loop().run_until_complete(
        asyncio.wait_for(coro, 30))


def _luma(w, h, seed):
    rgb = make_test_frame(h, w, seed)
    # any 8-bit plane works as a luma stand-in for the stats kernels
    return np.asarray(rgb[..., 0], np.uint8)


class TestKernelsVsOracle:
    """frame_stats (device) must match frame_stats_np slot for slot."""

    def test_full_inputs_match_oracle(self, rng):
        w, h = 64, 48
        y = _luma(w, h, 1)
        prev = _luma(w, h, 2)
        recon = np.clip(y.astype(np.int32)
                        + rng.integers(-4, 5, y.shape), 0, 255
                        ).astype(np.uint8)
        r, c = h // 16, w // 16
        mv = rng.integers(-8, 9, (r, c, 2)).astype(np.int32)
        mv[0, 0] = 0
        resid = (rng.integers(-2, 3, (r, c, 16, 16)).astype(np.int32),)
        resid[0][0, 0] = 0           # MB(0,0): zero MV + uncoded = skip
        mb_intra = np.zeros((r, c), bool)
        mb_intra[1, 1] = True
        thr = 512
        vec_d, grid_d = cs.frame_stats(y, prev, recon, mv,
                                       tuple(resid), mb_intra, thr)
        vec_o, grid_o = cs.frame_stats_np(y, prev, recon, mv, resid,
                                          mb_intra, thr)
        vec_d = np.asarray(vec_d, np.float64)
        np.testing.assert_array_equal(np.asarray(grid_d), grid_o)
        # integer-exact slots
        for idx in (cs.IDX_DAMAGE, cs.IDX_SKIP, cs.IDX_INTER,
                    cs.IDX_INTRA, cs.IDX_MBS):
            assert vec_d[idx] == vec_o[idx], idx
        # PSNR within 0.01 dB of the float64 oracle (the ISSUE bar)
        npix = h * w
        p_d = cs.psnr_from_sse(float(vec_d[cs.IDX_SSE]), npix)
        p_o = cs.psnr_from_sse(float(vec_o[cs.IDX_SSE]), npix)
        assert abs(p_d - p_o) < 0.01
        # float slots within float32 tolerance
        for idx in (cs.IDX_MV_MEAN, cs.IDX_MV_P95,
                    cs.IDX_ACT_P50, cs.IDX_ACT_P95):
            np.testing.assert_allclose(vec_d[idx], vec_o[idx],
                                       rtol=1e-5, atol=1e-3)
        # the skip/intra plants actually landed
        assert vec_o[cs.IDX_SKIP] >= 1
        assert vec_o[cs.IDX_INTRA] == 1

    def test_optional_inputs_sentinel(self):
        y = _luma(32, 32, 3)
        vec, grid = cs.frame_stats(y, None, None, None, (), None, 512)
        vec = np.asarray(vec)
        for idx in (cs.IDX_SSE, cs.IDX_DAMAGE, cs.IDX_SKIP,
                    cs.IDX_MV_MEAN):
            assert vec[idx] == -1.0
        assert vec[cs.IDX_MBS] == 4
        assert np.asarray(grid).sum() == 0

    def test_chunk_stats_matches_per_frame_oracle(self, rng):
        w, h, k = 48, 32, 3
        ys = np.stack([_luma(w, h, 10 + i) for i in range(k)])
        prev = _luma(w, h, 9)
        recon_last = np.clip(ys[-1].astype(np.int32) + 3, 0, 255
                             ).astype(np.uint8)
        r, c = h // 16, w // 16
        mvs = rng.integers(-6, 7, (k, r, c, 2)).astype(np.int32)
        resid = (rng.integers(-1, 2, (k, r, c, 256)).astype(np.int32),)
        vecs, grids = cs.chunk_stats(ys, prev, recon_last, mvs,
                                     tuple(resid), 512)
        vecs = np.asarray(vecs, np.float64)
        grids = np.asarray(grids)
        chain = [prev] + list(ys[:-1])
        for i in range(k):
            vo, go = cs.frame_stats_np(
                ys[i], chain[i], recon_last if i == k - 1 else None,
                mvs[i], (resid[0][i],), None, 512)
            np.testing.assert_array_equal(grids[i], go)
            assert vecs[i, cs.IDX_DAMAGE] == vo[cs.IDX_DAMAGE]
            assert vecs[i, cs.IDX_SKIP] == vo[cs.IDX_SKIP]
            if i < k - 1:
                assert vecs[i, cs.IDX_SSE] == -1.0   # PSNR last slot only
            else:
                npix = h * w
                assert abs(cs.psnr_from_sse(vecs[i, cs.IDX_SSE], npix)
                           - cs.psnr_from_sse(vo[cs.IDX_SSE], npix)
                           ) < 0.01

    def test_mb_activity_oracle_matches_device(self):
        from docker_nvidia_glx_desktop_tpu.ops.aq import mb_activity

        y = _luma(64, 32, 5)
        np.testing.assert_array_equal(
            np.asarray(mb_activity(y), np.int64), cs.mb_activity_np(y))

    @pytest.mark.parametrize("frames", ["random", "extreme"])
    @pytest.mark.parametrize("w,h", [(1920, 1088), (2560, 1600),
                                     (3840, 2176)])
    def test_served_sizes_match_oracle(self, w, h, frames):
        """The served call (three ``uint8`` planes, the ``int8`` vectors,
        the five ``int16`` level tensors, no intra map) at the served
        sizes: the per-macroblock sums go rows first, then columns (PR 44),
        and stay the oracle's one-step sums to the bit, also where every
        sum is at its maximum (SSE 256 x 255^2 a macroblock)."""
        rng = np.random.default_rng(w)
        r, c = h // 16, w // 16
        if frames == "random":
            y, prev, recon = (rng.integers(0, 256, (h, w), dtype=np.uint8)
                              for _ in range(3))
            prev[: h // 2] = y[: h // 2]        # half the grid undamaged
        else:
            y = np.full((h, w), 255, np.uint8)
            prev = recon = np.zeros((h, w), np.uint8)
        mv = rng.integers(-8, 9, (r, c, 2)).astype(np.int8)
        mv[rng.random((r, c)) < 0.5] = 0
        resid = tuple(
            (rng.integers(-2, 3, (r, c) + sh)
             * (rng.random((r, c) + (1,) * len(sh)) < 0.3)).astype(np.int16)
            for sh in ((16, 16), (4,), (4, 15), (4,), (4, 15)))
        vec_d, grid_d = cs.frame_stats(y, prev, recon, mv, resid, None, 512)
        vec_o, grid_o = cs.frame_stats_np(y, prev, recon, mv, resid,
                                          None, 512)
        vec_d = np.asarray(vec_d, np.float64)
        np.testing.assert_array_equal(np.asarray(grid_d), grid_o)
        for idx in (cs.IDX_DAMAGE, cs.IDX_SKIP, cs.IDX_INTER,
                    cs.IDX_INTRA, cs.IDX_MBS):
            assert vec_d[idx] == vec_o[idx], idx
        assert 0 < vec_o[cs.IDX_SKIP] < r * c
        assert vec_o[cs.IDX_DAMAGE] == (r * c if frames == "extreme"
                                        else r * c // 2)
        if frames == "extreme":
            assert vec_o[cs.IDX_SSE] == 255.0 ** 2 * h * w
        p_d = cs.psnr_from_sse(float(vec_d[cs.IDX_SSE]), h * w)
        p_o = cs.psnr_from_sse(float(vec_o[cs.IDX_SSE]), h * w)
        assert abs(p_d - p_o) < 0.01
        for idx in (cs.IDX_MV_MEAN, cs.IDX_MV_P95,
                    cs.IDX_ACT_P50, cs.IDX_ACT_P95):
            np.testing.assert_allclose(vec_d[idx], vec_o[idx],
                                       rtol=1e-5, atol=1e-3)

    def test_activity_and_aq_match_the_one_step_sums(self, monkeypatch):
        """``mb_activity`` and ``aq_offsets`` against themselves over the
        per-macroblock sums taken in one step over (R, 16, C, 16)."""
        from docker_nvidia_glx_desktop_tpu.ops import aq

        def one_step(plane):
            h, w = plane.shape
            return plane.reshape(h // 16, 16, w // 16, 16).sum((1, 3))

        # noise of another strength in every macroblock: flat to busy
        rng = np.random.default_rng(44)
        amp = rng.choice([0, 1, 4, 16, 64], (100, 160)).repeat(
            16, 0).repeat(16, 1)
        y = np.clip(128 + amp * rng.standard_normal((1600, 2560)),
                    0, 255).astype(np.uint8)
        act, offs = np.asarray(aq.mb_activity(y)), np.asarray(aq.aq_offsets(y))
        monkeypatch.setattr(aq, "_mb_sum", one_step)
        np.testing.assert_array_equal(act, np.asarray(aq.mb_activity(y)))
        np.testing.assert_array_equal(offs, np.asarray(aq.aq_offsets(y)))
        assert len(np.unique(offs)) >= 4


class TestVecDecode:
    def test_psnr_from_sse(self):
        assert cs.psnr_from_sse(-1.0, 100) is None
        assert cs.psnr_from_sse(0.0, 100) == 99.0
        # SSE == npix -> MSE 1 -> 10*log10(255^2)
        assert abs(cs.psnr_from_sse(100.0, 100)
                   - 10 * np.log10(255.0 ** 2)) < 1e-9

    def test_vec_to_stats_sentinels(self):
        vec = np.full(cs.VEC_LEN, -1.0)
        vec[cs.IDX_MBS] = 4
        vec[cs.IDX_ACT_P50] = 1.0
        vec[cs.IDX_ACT_P95] = 2.0
        st = cs.vec_to_stats(vec, np.zeros((2, 2), np.uint8), 1024)
        assert st["psnr_db"] is None
        assert st["damage_fraction"] is None
        assert st["mode"] is None
        assert st["mbs"] == 4

    def test_vec_to_stats_mode_fractions(self):
        vec = np.full(cs.VEC_LEN, -1.0)
        vec[cs.IDX_MBS] = 4
        vec[cs.IDX_SKIP], vec[cs.IDX_INTER], vec[cs.IDX_INTRA] = 2, 1, 1
        vec[cs.IDX_DAMAGE] = 1
        vec[cs.IDX_ACT_P50] = vec[cs.IDX_ACT_P95] = 0.0
        st = cs.vec_to_stats(vec, np.zeros((2, 2), np.uint8), 1024)
        assert st["mode"] == {"skip": 0.5, "inter": 0.25, "intra": 0.25}
        assert st["damage_fraction"] == 0.25

    def test_downsample_grid(self):
        g = np.ones((36, 64), np.uint8)
        d = cs.downsample_grid(g)
        assert d.shape == (18, 32)
        np.testing.assert_allclose(d, 1.0)
        # small grids pass through untouched
        assert cs.downsample_grid(np.zeros((4, 4))).shape == (4, 4)


class TestKnobs:
    def test_psnr_floor_parsing(self, monkeypatch):
        monkeypatch.delenv("DNGD_CONTENT_PSNR_FLOOR", raising=False)
        assert obsc.psnr_floor("off") == 30.0
        assert obsc.psnr_floor("hq") == 33.0
        monkeypatch.setenv("DNGD_CONTENT_PSNR_FLOOR", "25")
        assert obsc.psnr_floor("off") == 25.0
        assert obsc.psnr_floor("hq") == 25.0
        monkeypatch.setenv("DNGD_CONTENT_PSNR_FLOOR", "off:28,hq:35")
        assert obsc.psnr_floor("off") == 28.0
        assert obsc.psnr_floor("hq") == 35.0
        assert obsc.psnr_floor("hq_noaq") == 32.0   # default survives

    def test_damage_thr_and_sample(self, monkeypatch):
        monkeypatch.delenv("DNGD_CONTENT_DAMAGE_THR", raising=False)
        assert obsc.damage_thr_sad() == 512
        monkeypatch.setenv("DNGD_CONTENT_DAMAGE_THR", "1.0")
        assert obsc.damage_thr_sad() == 256
        monkeypatch.setenv("DNGD_CONTENT_SAMPLE", "4")
        assert obsc.sample_every() == 4
        monkeypatch.setenv("DNGD_CONTENT_SAMPLE", "junk")
        assert obsc.sample_every() == 1


def _stats(psnr=40.0, damage=0.02, tier="off", **kw):
    d = {"psnr_db": psnr, "damage_fraction": damage, "tier": tier,
         "mode": {"skip": 0.9, "inter": 0.08, "intra": 0.02},
         "mv_mean_qpel": 0.5, "mv_p95_qpel": 2.0,
         "act_p50": 10.0, "act_p95": 40.0, "mbs": 4,
         "damage_grid": np.zeros((2, 2), np.uint8),
         "frame_type": "p", "au_bytes": 100}
    d.update(kw)
    return d


class TestContentPlane:
    def test_record_exports_gauges_and_drop_removes(self):
        p = obsc.ContentPlane()
        # exercise via the module-global gauges with a unique session
        sess = "cp-test-1"
        obsc.PLANE.record(sess, _stats())
        text = obsm.REGISTRY.render()
        assert f'dngd_content_psnr_db{{session="{sess}"}} 40' in text
        assert 'dngd_content_damage_fraction{session="cp-test-1"}' in text
        assert ('dngd_content_mode_fraction{mode="skip",'
                'session="cp-test-1"} 0.9' in text
                or 'dngd_content_mode_fraction{session="cp-test-1",'
                   'mode="skip"} 0.9' in text)
        assert 'dngd_content_bits_total' in text
        obsc.PLANE.drop(sess)
        text = obsm.REGISTRY.render()
        assert f'session="{sess}"' not in text
        assert sess not in obsc.PLANE.quality_state()
        del p

    def test_quality_state_verdicts(self, monkeypatch):
        monkeypatch.delenv("DNGD_CONTENT_PSNR_FLOOR", raising=False)
        p = obsc.ContentPlane()
        for _ in range(5):
            p.record("good", _stats(psnr=41.0))
            p.record("bad", _stats(psnr=20.0))
        q = p.quality_state()
        assert q["good"]["verdict"] == "ok"
        assert q["bad"]["verdict"] == "breach"
        assert q["bad"]["floor_db"] == 30.0
        p.record("mute", _stats(psnr=None))
        assert p.quality_state()["mute"]["verdict"] == "no-data"

    def test_breach_and_spike_events(self, monkeypatch):
        from docker_nvidia_glx_desktop_tpu.obs import events as obse

        monkeypatch.delenv("DNGD_CONTENT_PSNR_FLOOR", raising=False)
        monkeypatch.delenv("DNGD_CONTENT_SPIKE", raising=False)
        p = obsc.ContentPlane()
        # calm history, then a spike + a floor breach on one frame
        for _ in range(35):
            p.record("ev", _stats(psnr=40.0, damage=0.01))
        p.record("ev", _stats(psnr=10.0, damage=0.95))
        kinds = [e["kind"] for e in obse.EVENTS.recent(64)
                 if e.get("session") == "ev"]
        assert "psnr_floor_breach" in kinds
        assert "damage_spike" in kinds
        # debounced: an immediate second breach emits nothing new
        n = kinds.count("psnr_floor_breach")
        p.record("ev", _stats(psnr=10.0, damage=0.95))
        kinds2 = [e["kind"] for e in obse.EVENTS.recent(64)
                  if e.get("session") == "ev"]
        assert kinds2.count("psnr_floor_breach") == n

    def test_spike_requires_calm_history(self, monkeypatch):
        monkeypatch.delenv("DNGD_CONTENT_SPIKE", raising=False)
        p = obsc.ContentPlane()
        # a busy session sitting at high damage is NOT spiking
        for _ in range(35):
            p.record("busy", _stats(damage=0.9))
        assert p._s["busy"]["spikes"] == 0

    def test_snapshot_and_render(self):
        p = obsc.ContentPlane()
        grid = np.zeros((4, 4), np.uint8)
        grid[1, 1] = 1
        p.record("snap", _stats(damage_grid=grid))
        snap = p.snapshot()
        s = snap["sessions"]["snap"]
        assert s["last"]["psnr_db"] == 40.0
        assert s["last"]["damage_grid_shape"] == [4, 4]
        assert s["rolling"]["n"] == 1
        brief = p.snapshot(brief=True)
        assert "damage_grid" not in (
            brief["sessions"]["snap"]["last"] or {})
        text = obsc.render_content_text(p)
        assert "session snap" in text

    def test_mean_damage_fraction(self):
        p = obsc.ContentPlane()
        assert p.mean_damage_fraction() is None
        p.record("a", _stats(damage=0.1))
        p.record("b", _stats(damage=0.3))
        assert abs(p.mean_damage_fraction() - 0.2) < 1e-9


class TestBudgetAndCapacityAnnotations:
    def test_ledger_content_stage(self):
        from docker_nvidia_glx_desktop_tpu.obs.budget import BudgetLedger

        led = BudgetLedger()
        led.record_content(0.25)
        stages = led.snapshot()["stages"]
        assert "content-damage-pct" in stages
        assert abs(stages["content-damage-pct"]["p50"] - 25.0) < 1e-6

    def test_capacity_snapshot_observed_damage(self):
        from docker_nvidia_glx_desktop_tpu.fleet.capacity import (
            CapacityModel)

        snap = CapacityModel().snapshot(1, 320, 240, 30)
        assert "observed_damage_fraction" in snap
        obsc.PLANE.record("cap-test", _stats(damage=0.5))
        try:
            got = CapacityModel().snapshot(1, 320, 240, 30)
            assert got["observed_damage_fraction"] is not None
        finally:
            obsc.PLANE.drop("cap-test")

    def test_slo_quality_plane(self, monkeypatch):
        from docker_nvidia_glx_desktop_tpu.obs import slo as obss

        monkeypatch.delenv("DNGD_CONTENT_PSNR_FLOOR", raising=False)
        for _ in range(3):
            obsc.PLANE.record("slo-test", _stats(psnr=12.0))
        try:
            v = obss.PLANE.verdicts()
            assert v["quality"]["slo-test"]["verdict"] == "breach"
            text = obsm.REGISTRY.render()
            assert "dngd_slo_quality_breaching" in text
        finally:
            obsc.PLANE.drop("slo-test")


class TestContentEndpoint:
    def test_debug_content_json_and_text(self):
        async def scenario():
            cfg = from_env({"ENABLE_BASIC_AUTH": "true",
                            "BASIC_AUTH_PASSWORD": "pw",
                            "LISTEN_ADDR": "127.0.0.1",
                            "LISTEN_PORT": "0"})
            runner = await serve(cfg)
            obsc.PLANE.record("ep-test", _stats())
            try:
                port = bound_port(runner)
                async with ClientSession() as http:
                    # auth-exempt, like the other telemetry routes
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/content"
                            "?format=json") as r:
                        assert r.status == 200
                        doc = await r.json()
                        assert doc["enabled"] is True
                        assert "ep-test" in doc["sessions"]
                        assert doc["quality"]["ep-test"]["verdict"]
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/content"
                            ) as r:
                        assert r.status == 200
                        body = await r.text()
                        assert "session ep-test" in body
            finally:
                obsc.PLANE.drop("ep-test")
                await runner.cleanup()

        run(scenario())

    def test_debug_slo_includes_quality(self):
        async def scenario():
            cfg = from_env({"ENABLE_BASIC_AUTH": "false",
                            "LISTEN_ADDR": "127.0.0.1",
                            "LISTEN_PORT": "0"})
            runner = await serve(cfg)
            obsc.PLANE.record("slo-ep", _stats())
            try:
                port = bound_port(runner)
                async with ClientSession() as http:
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/slo"
                            "?format=json") as r:
                        assert r.status == 200
                        doc = await r.json()
                        assert "slo-ep" in doc["quality"]
            finally:
                obsc.PLANE.drop("slo-ep")
                await runner.cleanup()

        run(scenario())

    def test_metric_families_registered_at_server_import(self):
        """The PR 13 lesson: a scrape BEFORE any session must already
        show the content families (web/server imports obs/content)."""
        import docker_nvidia_glx_desktop_tpu.web.server  # noqa: F401

        text = obsm.REGISTRY.render()
        for fam in ("dngd_content_psnr_db",
                    "dngd_content_damage_fraction",
                    "dngd_content_mode_fraction",
                    "dngd_content_bits_total",
                    "dngd_client_qoe"):
            assert f"# HELP {fam}" in text, fam


class TestClientQoe:
    def test_ingest_sets_gauges(self):
        from docker_nvidia_glx_desktop_tpu.web import selkies_shim as shim

        msg = {"type": "stats", "stats": {
            "renderedFps": 58.5, "decodeTime": 4.2,
            "jitterBufferDelay": 12.0}}
        assert shim.ingest_client_qoe("qoe-peer", msg) is True
        text = obsm.REGISTRY.render()
        assert ('dngd_client_qoe' in text
                and 'qoe-peer' in text)
        assert '58.5' in text
        shim.drop_client_qoe("qoe-peer")
        assert 'qoe-peer' not in obsm.REGISTRY.render()

    def test_non_qoe_messages_ignored(self):
        from docker_nvidia_glx_desktop_tpu.web import selkies_shim as shim

        assert shim.ingest_client_qoe("x", {"type": "ping"}) is False
        assert shim.ingest_client_qoe("x", "not-a-dict") is False
        assert shim.ingest_client_qoe("x", {"fps": True}) is False
        assert 'peer="x"' not in obsm.REGISTRY.render()

    def test_flat_and_nested_field_aliases(self):
        from docker_nvidia_glx_desktop_tpu.web import selkies_shim as shim

        assert shim.ingest_client_qoe(
            "qoe-alias", {"frames_per_second": 30,
                          "video": {"jitter_buffer_ms": 8}}) is True
        text = obsm.REGISTRY.render()
        assert 'stat="fps"' in text
        assert 'stat="jitter_buffer_ms"' in text
        shim.drop_client_qoe("qoe-alias")


class TestFlightIntegration:
    def test_breach_event_triggers_dump_with_content_block(self,
                                                           monkeypatch):
        from docker_nvidia_glx_desktop_tpu.obs import flight as obsf

        monkeypatch.delenv("DNGD_CONTENT_PSNR_FLOOR", raising=False)
        obsf.FLIGHT.clear()
        obsc.PLANE.record("fl-test", _stats(psnr=5.0))
        try:
            dump = obsf.FLIGHT.find_dump("psnr_floor_breach")
            assert dump is not None
            assert "content" in dump
            assert "fl-test" in dump["content"]["sessions"]
        finally:
            obsc.PLANE.drop("fl-test")
            obsf.FLIGHT.clear()
