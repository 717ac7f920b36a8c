"""Result suites a CPU run can judge: recoveries, admissions, bits, counts.

Nothing here measures speed.  What a frame costs on the chip is the
benchmark's to say: ``python3 benchmark/run.py --workload <cell> --seed <n>``
(``BENCHMARK.json``; PERF.md section 1 says who pays for what, and
``PERF_LEDGER.jsonl`` keeps every PR's numbers in every cell).  Each suite
below prints ONE JSON line ({"metric", "value", "unit", "vs_baseline", ...})
and exits by its RESULT; the only clock that can end a run is the hang
watchdog (``BENCH_TIMEOUT_S``).

``--serving-budget`` drives the loopback serving path (synthetic X source
-> StreamSession -> muxer -> aiohttp server -> local WebSocket sink,
web/loopback) and passes on COUNTS: zero silent trace loss over the window
and, under ``--quick``, frame journeys closed by the client's acks.  The
emitted ``serving_budget`` block is the one ``/debug/budget`` serves.

``--chaos`` (web/chaos): every registered fault point (resilience/faults)
is injected against the live loopback serving path and must recover —
session alive, stream resumed via IDR — and the degradation ladder
(resilience/degrade) must downshift under an injected sustained breach
and restore afterwards.

``--fleet`` (web/fleetbench): N batched sessions on a simulated v5e-8
(forced host-platform devices) behind the fleet admission scheduler
(fleet/), with a churning client population — every join must be admitted,
queued, or cleanly rejected with ``retry_after_s`` (no silent hangs) while
``mesh_chip_lost`` and ``ws_send_stall`` fire mid-churn.

``--bdrate``: bits and PSNR of tune=off / hq_noaq / hq over a QP ladder on
four synthetic content classes; fails if hq spends more bits than off at
equal quality on any class.

``--quick`` shrinks a suite to CI smoke geometry on the CPU backend.
"""

from __future__ import annotations

import json
import os
import signal
import sys

RESULT = {"metric": "", "value": 0.0, "unit": "", "vs_baseline": 0.0}


def _emit_and_exit(code: int = 0):
    print(json.dumps(RESULT), flush=True)
    os._exit(code)


def _watchdog(signum, frame):
    RESULT["note"] = "watchdog timeout (device unreachable or compile stuck)"
    _emit_and_exit(1)


def _force_cpu_mesh(ndev: int = 0) -> None:
    """Pin the CPU backend BEFORE the first jax import (CI smoke must
    not touch a chip — same rationale as tests/conftest.py) and
    optionally force an ``ndev``-device fake host mesh for multi-chip
    scenarios."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if ndev:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={ndev}"
            ).strip()


def _arm_watchdog(default_s: int) -> int:
    """Arm the SIGALRM hang watchdog at ``BENCH_TIMEOUT_S`` (or the
    entry point's default) and return the armed budget in seconds."""
    signal.signal(signal.SIGALRM, _watchdog)
    budget_s = int(os.environ.get("BENCH_TIMEOUT_S", str(default_s)))
    signal.alarm(budget_s)
    return budget_s


def _backend_name() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:
        return "unknown"


def _stamp_obs() -> None:
    """Stamp RESULT with provenance (backend, versions, topology, env
    knobs, git SHA) and the observability state ``/metrics`` and
    ``/debug/*`` serve (obs/provenance.bench_snapshot), so two artifacts
    are mechanically diffable.  A block a suite has set itself stays.
    Defensive: a missing obs plane must never cost a suite its result."""
    try:
        from docker_nvidia_glx_desktop_tpu.obs.provenance import (
            bench_snapshot)
        for key, block in bench_snapshot().items():
            RESULT.setdefault(key, block)
    except Exception as e:
        RESULT["provenance"] = {"error": f"{type(e).__name__}: {e}"[:200]}


def serving_budget_main(quick: bool = False) -> None:
    """Loopback end-to-end serving run (web/loopback), judged on counts.

    Emits ONE JSON line with the ``serving_budget`` block; value =
    frame journeys closed, vs_baseline = 1.0 when no trace entry was
    lost.  Exits non-zero on any silent trace loss (ring overwrite or
    listener-flush loss over the window) or, under ``--quick``, when no
    journey closed (the loopback sink acks every probe — zero closures
    means the probe/ack path broke).
    """
    import asyncio

    if quick:
        # CI smoke: CPU backend, tiny geometry, no device needed.
        _force_cpu_mesh()
    budget_s = _arm_watchdog(300 if quick else 600)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
    from docker_nvidia_glx_desktop_tpu.web import loopback

    if quick:
        width, height, fps, frames = 128, 96, 30, 12
    else:
        width, height, fps, frames = 1920, 1080, 60, 120
    # dense ack sampling: the closure count needs a population, not the
    # serving default's 1-in-8 trickle
    obsj.sample_every(2)
    cfg = loopback.serving_budget_config(width, height, fps)
    block = asyncio.run(loopback.run_serving_budget(
        cfg, frames=frames, timeout_s=budget_s * 0.8))

    g2g = block.get("glass_to_glass", {})
    closed = int(g2g.get("closed") or 0)
    drops = block.get("trace_dropped_total", 0)
    RESULT.update({
        "metric": f"serving_budget_journeys_closed_{width}x{height}",
        "value": closed,
        "unit": "journeys",
        "vs_baseline": 1.0 if drops == 0 else 0.0,
        "backend": _backend_name(),
        "serving_budget": block,
        "trace_dropped_total": drops,
    })
    _stamp_obs()
    signal.alarm(0)
    _emit_and_exit(0 if drops == 0 and (closed or not quick) else 1)


def chaos_main(quick: bool = False, continuity_only: bool = False,
               skip_continuity: bool = False) -> None:
    """Chaos-mode loopback bench (web/chaos): inject every registered
    fault point against the live serving path and assert bounded
    recovery; drive the degradation ladder down and back up, and run
    the session-continuity scenarios (device_preempt: checkpoint
    restore with SSRC/seq continuity; mesh_chip_lost: N->N-1 elastic
    re-bucket).

    Emits ONE JSON line whose ``chaos`` block carries per-fault
    {fired, recovered, recovery_ms}; value = faults recovered,
    vs_baseline = recovered/total (1.0 = every registered fault
    survived).  Exits non-zero when any recovery failed.
    ``--continuity-only`` restricts the run to the two continuity
    scenarios (the CI continuity-smoke step).
    """
    import asyncio

    if quick:
        # Forced host-platform devices give the mesh-failover scenario
        # a multi-chip mesh to lose a chip from.
        _force_cpu_mesh(4)
    budget_s = _arm_watchdog(420 if quick else 900)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    from docker_nvidia_glx_desktop_tpu.web import chaos

    report = asyncio.run(chaos.run_chaos(
        quick=quick, timeout_s=budget_s * 0.8,
        continuity=not skip_continuity,
        continuity_only=continuity_only))
    scored = dict(report["faults"])
    scored.update({k: v for k, v in report["continuity"].items()
                   if v.get("recovered") is not None})
    total = len(scored)
    recovered = sum(1 for f in scored.values() if f.get("recovered"))
    RESULT.update({
        "metric": ("continuity_faults_recovered" if continuity_only
                   else "chaos_faults_recovered"),
        "value": recovered,
        "unit": "faults",
        "vs_baseline": round(recovered / max(total, 1), 4),
        "backend": _backend_name(),
        "chaos": report,
    })
    signal.alarm(0)
    _emit_and_exit(0 if report.get("all_recovered") else 1)


def fleet_main(quick: bool = False) -> None:
    """Fleet churn bench (web/fleetbench) on a SIMULATED v5e-8.

    Always runs on forced host-platform devices (8, or 4 under --quick)
    so the admission/placement control plane is exercised against a real
    multi-chip mesh without touching shared TPU hardware — the same
    fake-backend strategy the chaos bench and the test suite use.  Emits
    ONE JSON line whose ``fleet`` block carries the churn report; value
    = peak sessions/chip, vs_baseline = 1 - rejection_rate.  Exits
    non-zero when any zero-crash/no-silent-hang invariant failed.
    """
    import asyncio

    _force_cpu_mesh(4 if quick else 8)
    budget_s = _arm_watchdog(420 if quick else 1800)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    from docker_nvidia_glx_desktop_tpu.web import fleetbench

    report = asyncio.run(fleetbench.run_fleet(
        quick=quick, timeout_s=budget_s * 0.8))
    RESULT.update({
        "metric": "fleet_peak_sessions_per_chip",
        "value": report["sessions_per_chip"],
        "unit": "sessions/chip",
        "vs_baseline": round(1.0 - report["rejection_rate"], 4),
        "backend": _backend_name(),
        "fleet": report,
    })
    signal.alarm(0)
    _emit_and_exit(0 if report.get("ok") else 1)


def _bdrate_frames(kind: str, w: int, h: int, n: int):
    """Synthetic content classes for the BD-rate harness (seeded, so
    every run scores the same pixels).

    - ``desktop_text``: window chrome + black-on-white glyph rows that
      scroll two px/frame (the remote-desktop workload: hard edges,
      skip-heavy background).
    - ``natural_gradients``: smooth low-frequency gradients with a slow
      global drift (flat-energy content where coarse quantization bands
      visibly — the AQ map's best case).
    - ``panning_motion``: band-limited texture panning 4 px/frame (ME
      stress: every MB moves, lambda MV costs dominate).
    - ``scrolling``: a static document vertically panned 8 px/frame
      (the scroll-wheel workload the damage mask prices: every MB row
      changes each frame — full damage — but the content is pure
      translation, so ME + skip should carry almost all of it; the
      class pins the mask's worst case in the BD-rate ledger).
    """
    import numpy as np

    r = np.random.default_rng(42)
    if kind == "desktop_text":
        # white page with CONTINUOUS micro-grain (real captures dither;
        # a 3-valued synthetic image resonates with the quant lattice at
        # specific QPs and makes PSNR(qp) non-monotonic), flat margins
        # (the AQ map's negative side needs genuinely flat MBs to act
        # on), and a scrolling text column.
        grain = r.normal(0.0, 2.0, (h, w, 1))
        base = np.clip(246.0 + grain, 0, 255).astype(np.uint8).repeat(3, 2)
        base[: h // 8] = (58, 62, 70)                 # title bar
        base[: h // 8] += r.integers(0, 3, (h // 8, w, 3)).astype(np.uint8)
        glyphs = (r.random((h, w)) < 0.18) & (
            (np.arange(h) % 8 < 5)[:, None])          # text lines
        glyphs[:, : w // 4] = False                   # left margin
        glyphs[:, w - w // 6:] = False                # right margin
        pane = slice(h // 8 + 8, h - 8)
        frames = []
        for i in range(n):
            f = base.copy()
            g = np.roll(glyphs, -2 * i, axis=0)       # scrolling pane
            f[pane][g[pane]] = (16, 16, 20)
            frames.append(f)
        return frames
    if kind == "natural_gradients":
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float64)
        frames = []
        for i in range(n):
            ph = i * 0.35
            g = (110 + 70 * np.sin(xx / w * 3.1 + ph)
                 + 55 * np.cos(yy / h * 2.3 + 0.4 * ph))
            f = np.stack([g, g * 0.92 + 12, g * 0.85 + 25], axis=-1)
            frames.append(np.clip(f, 0, 255).astype(np.uint8))
        return frames
    if kind == "panning_motion":
        # band-limited texture: blurred noise, tiled wide enough to pan
        big = r.integers(0, 256, (h, w * 2, 3)).astype(np.float64)
        k = 7
        kern = np.ones(k) / k
        for ax in (0, 1):
            big = np.apply_along_axis(
                lambda v: np.convolve(v, kern, mode="same"), ax, big)
        big = np.clip((big - big.mean()) * 3.0 + 128, 0, 255)
        big = big.astype(np.uint8)
        return [np.ascontiguousarray(big[:, 4 * i:4 * i + w])
                for i in range(n)]
    if kind == "scrolling":
        # a tall "document": white page, ruled text bands, occasional
        # figures (gray boxes) — scrolled vertically 8 px/frame.  Mild
        # grain keeps PSNR(qp) monotonic, same reasoning as
        # desktop_text.
        doc_h = h + 8 * n
        grain = r.normal(0.0, 2.0, (doc_h, w, 1))
        doc = np.clip(248.0 + grain, 0, 255).astype(np.uint8).repeat(3, 2)
        text = (r.random((doc_h, w)) < 0.16) & (
            (np.arange(doc_h) % 10 < 6)[:, None])
        text[:, : w // 6] = False
        text[:, w - w // 8:] = False
        doc[text] = (20, 20, 24)
        for fy in range(0, doc_h - h // 3, max(doc_h // 5, 1)):
            doc[fy:fy + h // 6, w // 3:w - w // 3] = (
                r.integers(96, 160, (1, 1, 3)).astype(np.uint8))
        return [np.ascontiguousarray(doc[8 * i:8 * i + h])
                for i in range(n)]
    raise ValueError(kind)


def _bd_rate_pct(rate_ref, psnr_ref, rate_new, psnr_new) -> float:
    """Bjontegaard rate delta of NEW vs REF, percent (negative = NEW
    spends fewer bits at equal quality).  Cubic log-rate fit over the
    overlapping PSNR interval — the standard BD-rate construction."""
    import numpy as np

    la, lb = np.log10(rate_ref), np.log10(rate_new)
    pa = np.polyfit(psnr_ref, la, 3)
    pb = np.polyfit(psnr_new, lb, 3)
    lo = max(np.min(psnr_ref), np.min(psnr_new))
    hi = min(np.max(psnr_ref), np.max(psnr_new))
    if hi - lo < 1e-6:
        return 0.0
    ia, ib = np.polyint(pa), np.polyint(pb)
    span = lambda p: np.polyval(p, hi) - np.polyval(p, lo)  # noqa: E731
    avg = (span(ib) - span(ia)) / (hi - lo)
    return float((10.0 ** avg - 1.0) * 100.0)



def bdrate_main(quick: bool = False) -> None:
    """BD-rate harness (ISSUE 15 / ROADMAP item 4): prove ENCODER_TUNE.

    Encodes four synthetic content classes over a 4-point QP ladder at
    three tuning tiers — ``off`` (the fixed-heuristic pre-tune encoder),
    ``hq_noaq`` (Lagrangian mode/MV/skip decisions at uniform slice qp),
    ``hq`` (lambda decisions + per-MB adaptive quantization) — and
    reports the Bjontegaard rate delta of each tuned tier against
    ``off``.  Distortion is luma PSNR of the encoder's device
    reconstruction vs the device-converted source plane: one more
    device-side reduction (ops/aq.psnr_planes), no golden decoder in the
    rate loop.  What a tier costs on the chip is not this suite's to say
    (not measured on the chip: no cell serves tune=hq yet).

    Scope note: ``keep_recon`` (the PSNR hook) disables the super-step
    ring, so this harness drives the per-frame path and the measured hq
    tier is AQ + lambda decisions WITHOUT the 1-frame lookahead bias —
    that rides only chunked serving, where its conformance is pinned by
    tests/test_tune.py's chunked-hq decode test.  The BD-rate numbers
    are therefore a floor for the chunked configuration, not a claim
    about the lookahead.

    Exit code: non-zero if tune=hq LOSES to tune=off (positive BD-rate)
    on any content class — the CI bdrate-smoke gate.
    """
    _force_cpu_mesh()
    _arm_watchdog(420 if quick else 1800)

    from docker_nvidia_glx_desktop_tpu.utils.jaxcache import (
        setup_compile_cache)
    setup_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from docker_nvidia_glx_desktop_tpu.models.h264 import (
        H264Encoder, _yuv_stage)
    from docker_nvidia_glx_desktop_tpu.obs import budget as obs_budget
    from docker_nvidia_glx_desktop_tpu.ops import aq

    w, h = (192, 112) if quick else (448, 256)
    n = 9 if quick else 12              # serving GOPs are long (gop=60):
    qps = (26, 30, 34, 38)              # give the I/P split room to pay
    tiers = ("off", "hq_noaq", "hq")
    classes = ("desktop_text", "natural_gradients", "panning_motion",
               "scrolling")

    def run_tier(frames, tier: str, qp: int):
        enc = H264Encoder(w, h, qp=qp, entropy="device",
                          gop=len(frames), keep_recon=True, tune=tier)
        bits = 0
        psnrs = []
        for f in frames:
            src_y = np.asarray(_yuv_stage(jnp.asarray(f), enc.pad_h,
                                          enc.pad_w)[0])
            bits += len(enc.encode(f).data) * 8
            psnrs.append(aq.psnr_planes(enc.last_recon[0], src_y))
        return bits, round(float(np.mean(psnrs)), 3)

    block = {
        "geometry": f"{w}x{h}",
        "frames": n,
        "qps": list(qps),
        "backend": _backend_name(),
        "quick": bool(quick),
        "classes": {},
    }
    gains = []
    for cls in classes:
        frames = _bdrate_frames(cls, w, h, n)
        per_tier = {t: {"rate_bits": [], "psnr_y": []} for t in tiers}
        for qp in qps:
            for t in tiers:
                bits, psnr = run_tier(frames, t, qp)
                per_tier[t]["rate_bits"].append(bits)
                per_tier[t]["psnr_y"].append(psnr)
        crow = {"tiers": per_tier}
        off = per_tier["off"]
        for t in ("hq_noaq", "hq"):
            bd = _bd_rate_pct(off["rate_bits"], off["psnr_y"],
                              per_tier[t]["rate_bits"],
                              per_tier[t]["psnr_y"])
            crow[f"bd_rate_{t}_vs_off_pct"] = round(bd, 2)
        block["classes"][cls] = crow
        gains.append(-crow["bd_rate_hq_vs_off_pct"])
    block["best_gain_pct"] = round(max(gains), 2)
    block["worst_gain_pct"] = round(min(gains), 2)
    # the gate: hq must never LOSE to off; the acceptance headline is
    # >=15% on at least one class
    block["ok"] = bool(min(gains) >= 0.0)
    block["meets_issue15"] = bool(max(gains) >= 15.0)

    obs_budget.record_bdrate(block)
    RESULT.update({
        "metric": "h264_hq_best_bdrate_gain_pct",
        "value": block["best_gain_pct"],
        "unit": "pct_fewer_bits_at_equal_psnr",
        "vs_baseline": round(block["best_gain_pct"] / 15.0, 3),
        "backend": _backend_name(),
        "bdrate": block,
    })
    _stamp_obs()
    signal.alarm(0)
    _emit_and_exit(0 if block["ok"] else 1)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--serving-budget", action="store_true",
                    help="loopback end-to-end serving run: zero silent "
                         "trace loss, journeys closed")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injection suite: every registered "
                         "fault point must recover; degradation ladder "
                         "downshifts and restores")
    ap.add_argument("--continuity-only", action="store_true",
                    help="with --chaos: run only the session-continuity "
                         "scenarios (device_preempt checkpoint restore, "
                         "mesh_chip_lost elastic re-bucket)")
    ap.add_argument("--skip-continuity", action="store_true",
                    help="with --chaos: skip the continuity scenarios "
                         "(the pre-existing chaos-smoke scope)")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet churn suite: admission scheduler + "
                         "queue backpressure + churn-safe placement on "
                         "a simulated v5e-8 (chip loss + ws stalls "
                         "mid-churn)")
    ap.add_argument("--bdrate", action="store_true",
                    help="BD-rate harness: tune=off/hq_noaq/hq over a "
                         "QP ladder on four synthetic content classes; "
                         "fails if hq loses to off on any class")
    ap.add_argument("--quick", action="store_true",
                    help="smoke geometry on the CPU backend (CI)")
    args = ap.parse_args()
    if args.bdrate:
        bdrate_main(quick=args.quick)
    elif args.fleet:
        fleet_main(quick=args.quick)
    elif args.chaos:
        chaos_main(quick=args.quick, continuity_only=args.continuity_only,
                   skip_continuity=args.skip_continuity)
    elif args.serving_budget:
        serving_budget_main(quick=args.quick)
    else:
        ap.print_usage()
        print("bench.py times nothing: name a suite above, or run the "
              "benchmark, `python3 benchmark/run.py --workload <cell> "
              "--seed <n>` (BENCHMARK.json, PERF.md section 1).")
        sys.exit(2)
