"""Config surface + codec factory: env parity chains and the
fail-loudly contract for unimplemented codecs (VERDICT round-1 weak #8)."""

import os

import pytest

from docker_nvidia_glx_desktop_tpu.models import make_encoder
from docker_nvidia_glx_desktop_tpu.utils.config import from_env


class TestCodecFactory:
    def test_default_is_h264_with_knobs(self):
        cfg = from_env({"ENCODER_QP": "30", "ENCODER_GOP": "15",
                        "ENCODER_BITRATE_KBPS": "2000", "REFRESH": "30"})
        enc, name = make_encoder(cfg, 128, 96)
        assert name == "h264_cavlc"
        assert enc.qp == 30
        assert enc.gop == 15
        assert enc._rate is not None
        assert enc._rate.target_bits == pytest.approx(2000 * 1000 / 30)

    def test_make_encoder_is_the_one_factory_and_builds_what_is_served(self):
        """No second factory beside ``make_encoder`` (a bench once timed
        one that built an all-intra encoder without loop filter or rate
        control), and the defaults are the deployment PERF.md section 4
        measures: device CAVLC, the loop filter on, GOP 60, CBR."""
        import inspect

        from docker_nvidia_glx_desktop_tpu import models

        factories = [n for n, f in vars(models).items()
                     if inspect.isfunction(f) and not n.startswith("_")]
        assert factories == ["make_encoder"]
        enc, name = make_encoder(from_env({}), 64, 48)
        assert name == "h264_cavlc"
        assert enc.entropy == "device"
        assert enc.deblock and enc.gop == 60 and enc._rate is not None

    @pytest.mark.parametrize("function,gone", [
        ("ops.h264_inter:encode_p_frame", "refine"),
        ("parallel.batch:h264_spatial_step", "halo"),
    ])
    def test_no_parameter_selects_a_measurement_twin(self, function, gone):
        """The alternate-line search and the exchanged halo are THE
        paths: no argument builds another program beside the served one."""
        import importlib
        import inspect

        module, name = function.split(":")
        f = getattr(importlib.import_module(
            f"docker_nvidia_glx_desktop_tpu.{module}"), name)
        assert gone not in inspect.signature(f).parameters

    def test_legacy_aliases(self):
        for legacy in ("nvh264enc", "x264enc"):
            cfg = from_env({"WEBRTC_ENCODER": legacy})
            _, name = make_encoder(cfg, 64, 48)
            assert name == "h264_cavlc"

    def test_mjpeg(self):
        cfg = from_env({"WEBRTC_ENCODER": "tpumjpegenc"})
        _, name = make_encoder(cfg, 64, 48)
        assert name == "mjpeg"

    def test_vp8_resolves(self):
        """vp8enc/vp9enc alias to tpuvp8enc -> the first-party VP8
        encoder (BASELINE config 2, ref fallback matrix README.md:21,35)."""
        from docker_nvidia_glx_desktop_tpu.native import vpx
        if not vpx.available():
            pytest.skip("libvpx not present (table source)")
        for legacy in ("vp8enc", "vp9enc", "tpuvp8enc"):
            cfg = from_env({"WEBRTC_ENCODER": legacy})
            enc, name = make_encoder(cfg, 64, 48)
            assert name == "vp8"
            assert enc.core.q_index == 26 * 127 // 51

    def test_unknown_codec_rejected(self):
        cfg = from_env({"WEBRTC_ENCODER": "h265enc"})
        with pytest.raises(ValueError, match="h265enc"):
            make_encoder(cfg, 64, 48)

    def test_cqp_mode_disables_rate_control(self):
        cfg = from_env({"ENCODER_BITRATE_KBPS": "0"})
        enc, _ = make_encoder(cfg, 64, 48)
        assert enc._rate is None

    def test_nvidia_vars_ignored_with_warning(self, caplog):
        import logging

        with caplog.at_level(logging.WARNING):
            from_env({"NVIDIA_VISIBLE_DEVICES": "all", "VIDEO_PORT": "DFP"})
        assert sum("no effect on a TPU VM" in r.message
                   for r in caplog.records) == 2

    def test_mesh_spec_parsing(self):
        assert from_env({"TPU_MESH": "2x4"}).mesh_shape == (2, 4)
        assert from_env({"TPU_MESH": "8"}).mesh_shape == (8,)
        assert from_env({"TPU_MESH": "junk"}).mesh_shape == (1,)


class TestCompileCachePlacement:
    """utils/jaxcache: the cache lives where JAX_COMPILATION_CACHE_DIR
    says, exactly as given, and otherwise at ONE fixed directory inside
    the checkout — never /tmp, a pid, a time or the backend's name."""

    @pytest.mark.parametrize("given", [
        None, "/some/dir", "/some/dir-with-suffix-cpu", "relative/dir"])
    def test_cache_dir_placement(self, monkeypatch, given):
        import pathlib

        import jax

        from docker_nvidia_glx_desktop_tpu.utils import jaxcache

        if given is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", given)
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append((k, v)))
        first = jaxcache.setup_compile_cache()
        second = jaxcache.setup_compile_cache()
        assert first == second == jaxcache.cache_dir()
        dirs = [v for k, v in updates if k == "jax_compilation_cache_dir"]
        if given is None:
            root = pathlib.Path(jaxcache.__file__).resolve().parents[2]
            assert pathlib.Path(first) == root / ".jax_cache"
            assert "/tmp" not in first and str(os.getpid()) not in first
            assert set(dirs) == {first}
        else:
            # the operator's directory verbatim; JAX read the variable
            # itself, so the helper sets no directory in code at all
            assert first == given
            assert dirs == []
        # beyond the directory the helper touches only the two
        # what-gets-cached thresholds
        assert {k for k, _ in updates} <= {
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes"}

    def test_private_spellings_are_gone(self, monkeypatch):
        from docker_nvidia_glx_desktop_tpu.utils import jaxcache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_COMPILE_CACHE_DIR", "/old/one")
        monkeypatch.setenv("JAX_TEST_COMPILE_CACHE", "/old/two")
        assert jaxcache.cache_dir() == jaxcache.DEFAULT_CACHE_DIR
