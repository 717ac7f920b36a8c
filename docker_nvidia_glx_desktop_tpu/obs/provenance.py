"""Provenance-stamped BENCH snapshots + the stage-p50 tripwire.

BENCH_rNN.json files are only diffable when two rounds are known to
have measured the same thing the same way.  Until now bench.py computed
its own stage blocks through code paths the serving process never
exercised, and a round's environment (backend, jaxlib, topology, env
knobs, commit) lived in the operator's memory.  This module is the one
emitter both ends share:

- :func:`provenance_block` — backend, jax/jaxlib versions, chip
  topology (device kinds/counts, platform version), the observability-
  relevant env knobs, host picture, and the git SHA.  Every BENCH line
  carries it, so "run the same bench anywhere, diff two provenance-
  matched files" is a mechanical check.
- :func:`bench_snapshot` — the full BENCH block snapshotted from the
  SAME live objects ``/metrics`` scrapes: the metrics registry, the
  kernel profiler (obs/profile), the SLO burn plane (obs/slo) and the
  serving-budget ledger.  bench.py embeds this instead of computing
  parallel numbers.
- :func:`stage_p50_tripwire` — the regression verdict: measured stage
  p50s vs a committed baseline, failing any stage over
  ``baseline * (1 + max_pct/100) + guard_ms``.

Run as a module it is the CI tripwire CLI (stdlib-only import chain —
the diff job needs no jax install)::

    python -m docker_nvidia_glx_desktop_tpu.obs.provenance \\
        --tripwire bench_quick.json \\
        --baseline deploy/bench_quick_baseline.json \\
        --max-regression-pct 25
"""

from __future__ import annotations

import json
import os
import platform
import subprocess
import sys
import time
from typing import Dict, Optional

__all__ = ["provenance_block", "bench_snapshot", "stage_p50_tripwire",
           "git_sha", "env_knobs"]

# env prefixes that change what the pipeline measures — stamped so two
# BENCH files diff apples-to-apples (values, not just presence)
ENV_PREFIXES = ("ENCODER_", "DNGD_", "FLEET_", "DEGRADE_", "BENCH_",
                "JAX_", "XLA_", "TPUDESKTOP_")


def git_sha(short: bool = False) -> Optional[str]:
    """HEAD commit of the repo this package lives in; None outside a
    checkout (the shipped container has no .git — the image tag is the
    provenance there)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short" if short else "HEAD"]
            + (["HEAD"] if short else []),
            capture_output=True, text=True, timeout=5, cwd=root)
        sha = out.stdout.strip()
        return sha or None
    except Exception:
        return None


def env_knobs() -> Dict[str, str]:
    return {k: v for k, v in sorted(os.environ.items())
            if k.startswith(ENV_PREFIXES)}


def topology() -> dict:
    """Backend + chip topology from the live jax runtime; degrades to
    {"backend": "unavailable"} where jax is not importable (the
    tripwire CLI, doc builds)."""
    try:
        import jax
    except Exception:
        return {"backend": "unavailable"}
    out = {"backend": jax.default_backend()}
    try:
        devs = jax.devices()
        kinds: Dict[str, int] = {}
        for d in devs:
            kinds[d.device_kind] = kinds.get(d.device_kind, 0) + 1
        out.update({
            "device_count": jax.device_count(),
            "local_device_count": jax.local_device_count(),
            "process_count": jax.process_count(),
            "device_kinds": kinds,
        })
        if devs:
            # driver/runtime version string (PJRT platform version —
            # the TPU runtime or the CPU client build)
            out["platform_version"] = str(
                getattr(devs[0].client, "platform_version", ""))
    except Exception:
        pass
    return out


def provenance_block() -> dict:
    """Everything needed to decide two BENCH files are comparable."""
    versions = {"python": platform.python_version()}
    for mod in ("jax", "jaxlib", "numpy"):
        try:
            versions[mod] = __import__(mod).__version__
        except Exception:
            versions[mod] = None
    return {
        "schema": 1,
        "ts_unix": round(time.time(), 3),
        "git_sha": git_sha(),
        "versions": versions,
        "topology": topology(),
        "host": {
            "cores": os.cpu_count(),
            "platform": platform.platform(),
            "machine": platform.machine(),
        },
        "env": env_knobs(),
    }


def bench_snapshot(include_metrics: bool = True) -> dict:
    """The BENCH block: provenance + the live registry/profiler/SLO/
    budget state — the exact objects ``/metrics`` and ``/debug/*``
    serve, so a BENCH artifact and a scrape can never drift."""
    from . import metrics as obsm
    from . import profile as obsp
    from . import slo as obss
    from .budget import serving_budget_block

    snap = {
        "provenance": provenance_block(),
        "profile": obsp.PROFILER.snapshot(),
        "slo": obss.snapshot(),
        "serving_budget": serving_budget_block(),
    }
    if include_metrics:
        snap["metrics"] = obsm.REGISTRY.snapshot()
    return snap


def stage_p50_tripwire(got: Dict[str, float], baseline: Dict[str, float],
                       max_pct: float = 25.0,
                       guard_ms: float = 2.0) -> dict:
    """Diff measured stage p50s against a committed baseline.

    Only stages present in BOTH dicts are compared (a new stage has no
    baseline yet; a retired one must not fail forever).  A stage
    regresses when ``got > baseline * (1 + max_pct/100) + guard_ms`` —
    the absolute guard forgives shared-runner timer noise on
    sub-millisecond stages.
    """
    regressions = {}
    compared = []
    for stage, want in sorted(baseline.items()):
        have = got.get(stage)
        if have is None:
            continue
        compared.append(stage)
        limit = float(want) * (1.0 + max_pct / 100.0) + guard_ms
        if float(have) > limit:
            regressions[stage] = {
                "baseline_ms": round(float(want), 3),
                "got_ms": round(float(have), 3),
                "limit_ms": round(limit, 3),
                "regression_pct": round(
                    (float(have) / max(float(want), 1e-9) - 1.0)
                    * 100.0, 1),
            }
    return {"ok": not regressions, "max_regression_pct": max_pct,
            "guard_ms": guard_ms, "compared": compared,
            "regressions": regressions}


def _tripwire_cli(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="stage-p50 regression tripwire over a bench.py "
                    "--quick artifact (stdlib-only; no jax needed)")
    ap.add_argument("--tripwire", required=True,
                    help="bench_quick.json artifact (last line = the "
                         "emitted BENCH JSON)")
    ap.add_argument("--baseline", required=True,
                    help="committed baseline "
                         "(deploy/bench_quick_baseline.json)")
    ap.add_argument("--max-regression-pct", type=float, default=25.0)
    ap.add_argument("--guard-ms", type=float, default=2.0)
    args = ap.parse_args(argv)

    with open(args.tripwire) as f:
        doc = json.loads(f.read().strip().splitlines()[-1])
    with open(args.baseline) as f:
        base = json.load(f)
    got = (doc.get("profile") or {}).get("stage_p50_ms_steady") or {}
    if not got:
        got = (doc.get("profile") or {}).get("stage_p50_ms") or {}
    want = base.get("profile_stage_p50_ms") or {}
    if not want:
        print("tripwire: baseline has no profile_stage_p50_ms block; "
              "nothing to gate", file=sys.stderr)
        return 0
    verdict = stage_p50_tripwire(got, want,
                                 max_pct=args.max_regression_pct,
                                 guard_ms=args.guard_ms)
    # provenance must match on the axes that change what the numbers
    # mean — a backend mismatch is an apples-to-oranges diff, not a
    # perf regression
    prov = (doc.get("provenance") or {}).get("topology") or {}
    if base.get("backend") and prov.get("backend") and \
            base["backend"] != prov["backend"]:
        verdict["ok"] = False
        verdict["backend_mismatch"] = {
            "baseline": base["backend"], "got": prov["backend"]}
    print(json.dumps(verdict, indent=2))
    if not verdict["ok"]:
        print(f"tripwire: {len(verdict.get('regressions', {}))} stage "
              f"p50 regression(s) > {args.max_regression_pct}%",
              file=sys.stderr)
        return 1
    print(f"tripwire: {len(verdict['compared'])} stages within "
          f"{args.max_regression_pct}% of baseline", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(_tripwire_cli())
