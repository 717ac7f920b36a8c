"""Provenance: what ran, where, on which commit, under which knobs.

Two artifacts are only diffable when they are known to come from the
same thing run the same way.  This module is the one emitter the server
and bench.py's result suites share:

- :func:`provenance_block` — backend, jax/jaxlib versions, chip
  topology (device kinds/counts, platform version), the observability-
  relevant env knobs, host picture, and the git SHA.
- :func:`bench_snapshot` — provenance plus a snapshot of the SAME live
  objects ``/debug/*`` serves: the kernel profiler (obs/profile), the
  SLO burn plane (obs/slo) and the serving-budget ledger.  bench.py's
  suites embed it in their one JSON line.

No verdict on speed lives here: that is the benchmark's
(``BENCHMARK.json``, ``benchmark/``, ``PERF_LEDGER.jsonl``).
"""

from __future__ import annotations

import os
import platform
import subprocess
import time
from typing import Dict, Optional

__all__ = ["provenance_block", "bench_snapshot", "git_sha", "env_knobs"]

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
    {"backend": "unavailable"} where jax is not importable (doc
    builds)."""
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


def bench_snapshot() -> dict:
    """The BENCH block: provenance + the live profiler/SLO/budget state
    — the exact objects ``/debug/*`` serves, so a BENCH artifact and a
    scrape can never drift."""
    from . import profile as obsp
    from . import slo as obss
    from .budget import serving_budget_block

    return {
        "provenance": provenance_block(),
        "profile": obsp.PROFILER.snapshot(),
        "slo": obss.snapshot(),
        "serving_budget": serving_budget_block(),
    }
