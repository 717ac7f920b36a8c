"""Continuous kernel-step profiler: BENCH rounds as a standing instrument.

Every perf claim since the super-step ring landed was proven by a
bespoke bench campaign and then went dark: the serving process itself
never measured its own kernel steps, so a TPU round (BENCH_r06) means
re-running a one-off script and hand-diffing JSON.  This module makes
the per-stage numbers a LIVE property of the process:

- :class:`KernelProfiler` keeps per-stage timing **histograms** on the
  metrics registry (``dngd_profile_stage_ms``), labelled by
  backend/codec/geometry/tune/shards — fed by lightweight hooks in the
  codec models' ``encode_submit``/``encode_collect`` (the collect path
  materializes the bitstream, i.e. it is block-until-ready fenced on
  the device) and in :mod:`..ops.devloop`.  Super-step ring collects
  are **amortized over the chunk** (``chunk_len``), mirroring the
  frame-journey accounting, so a chunk-dispatch slot's big pull reads
  as K honest per-frame costs, not one outlier.
- **XLA compile capture**: a ``jax.monitoring`` duration listener
  records every ``.../backend_compile_duration`` (and sibling compile
  phases) into ``dngd_xla_compile_ms`` and bumps a compile sequence
  number.  Each stage sample is stamped ``phase="cold"`` when a compile
  fired since that stage's previous sample (or it is the stage's first)
  and ``phase="steady"`` otherwise — cold-jit and steady-state separate
  cleanly on the same histogram family.
- ``/debug/profile`` (obs/http) exports the bounded sample ring as
  Chrome trace-event JSON (open it in Perfetto / ``chrome://tracing``);
  ``?format=json`` returns the structured snapshot BENCH embeds.

Hot-path contract (same as the rest of obs/): :meth:`record` is a dict
lookup + deque append + one histogram bisect — no string formatting
beyond an f-string the caller already paid for, no rendering.  All
export happens at scrape time.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, Optional

from ..utils.env import env_flag
from ..utils.timing import percentile
from . import metrics as obsm

__all__ = ["KernelProfiler", "PROFILER", "set_enabled", "enabled",
           "export_chrome_trace"]

RING_CAPACITY = 4096          # recent raw samples (the /debug/profile ring)
COMPILE_RING = 256            # recent XLA compile events

# only the backend-compile phase counts toward the cold/steady sequence:
# jaxpr tracing re-fires on cache hits and would mark warm frames cold
_COMPILE_SEQ_EVENT = "backend_compile"

_M_SAMPLES = obsm.counter(
    "dngd_profile_samples_total",
    "Kernel-profiler stage samples recorded, by stage", ("stage",))
_M_COMPILE_MS = obsm.histogram(
    "dngd_xla_compile_ms",
    "XLA compile-phase durations (jax.monitoring), by phase event",
    ("event",),
    buckets=(1.0, 10.0, 50.0, 100.0, 500.0, 1000.0, 5000.0, 15000.0,
             60000.0))
_M_COMPILES = obsm.counter(
    "dngd_xla_compiles_total",
    "Backend XLA compiles observed since process start")

_ENABLED = env_flag("DNGD_PROFILE", True)


def set_enabled(flag: bool) -> None:
    """Master switch (overhead A/B benches); recording only — the rings
    and registry families stay readable while disabled."""
    global _ENABLED
    _ENABLED = bool(flag)


def enabled() -> bool:
    return _ENABLED


def _backend_name() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:
        return "unknown"


class KernelProfiler:
    """Per-stage timing histograms + compile capture + sample ring."""

    def __init__(self, capacity: int = RING_CAPACITY):
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._backend: Optional[str] = None
        # histogram children are cached per (stage, phase, label-tuple):
        # the hot path resolves a child once per combination, then holds
        self._children: Dict[tuple, object] = {}
        self._hist = obsm.histogram(
            "dngd_profile_stage_ms",
            "Per-stage kernel/pipeline step time (chunk-amortized), "
            "cold-jit vs steady-state separated by the phase label",
            ("stage", "phase", "backend", "codec", "geometry", "tune",
             "shards"))
        # compile capture: monotone sequence bumped per backend compile;
        # per-(stage,labels) memo of the sequence last seen -> cold flag
        self._compile_seq = 0
        self._last_seq: Dict[tuple, int] = {}
        self._compiles: deque = deque(maxlen=COMPILE_RING)
        self._compile_listener = False
        self._dropped = 0

    # -- backend (resolved once; cheap thereafter) ---------------------

    def backend(self) -> str:
        b = self._backend
        if b is None:
            b = self._backend = _backend_name()
        return b

    # -- ingestion (encode thread) -------------------------------------

    def record(self, stage: str, ms: float, codec: str = "",
               geometry: str = "", tune: str = "off",
               shards: int = 1, chunk_len: int = 1) -> None:
        """One stage sample.  ``chunk_len > 1`` amortizes a super-step
        chunk's span into a per-frame cost (the ring's chunk-dispatch
        slot carries the whole chunk's pull; dividing it — and the
        near-zero staged slots — by K keeps the per-frame histogram
        honest, exactly like the frame journeys' device attribution)."""
        if not _ENABLED:
            return
        k = max(int(chunk_len), 1)
        msf = float(ms) / k
        key = (stage, codec, geometry, tune, str(shards))
        seq = self._compile_seq
        last = self._last_seq.get(key)
        self._last_seq[key] = seq
        phase = "steady" if last == seq else "cold"
        child = self._children.get((key, phase))
        if child is None:
            child = self._hist.labels(stage, phase, self.backend(),
                                      codec, geometry, tune, str(shards))
            self._children[(key, phase)] = child
        child.observe(msf)
        _M_SAMPLES.labels(stage).inc()
        self._ring.append((time.perf_counter(), stage, round(msf, 4),
                           phase, codec, geometry, tune, int(shards)))

    def record_encoder(self, enc, stage: str, ms: float,
                       chunk_len: int = 1) -> None:
        """Model-side hook: label dimensions pulled off the encoder
        (codec / geometry / tune / spatial shards) so the codecs feed
        the profiler with one call and zero per-site wiring."""
        if not _ENABLED:
            return
        try:
            shards = int(getattr(enc, "_spatial_nx", 1))
        except Exception:
            shards = 1
        self.record(
            stage, ms,
            codec=str(getattr(enc, "codec", type(enc).__name__)),
            geometry=f"{getattr(enc, 'width', 0)}x"
                     f"{getattr(enc, 'height', 0)}",
            tune=str(getattr(enc, "tune", "off")),
            shards=shards, chunk_len=chunk_len)

    # -- XLA compile capture -------------------------------------------

    def on_compile_duration(self, event: str, duration_s: float,
                            **kwargs) -> None:
        """jax.monitoring duration listener: any compile-phase duration
        lands on the ``dngd_xla_compile_ms`` histogram; the backend-
        compile phase additionally bumps the cold/steady sequence."""
        if "compile" not in event:
            return
        name = event.rsplit("/", 1)[-1]
        _M_COMPILE_MS.labels(name).observe(float(duration_s) * 1e3)
        self._compiles.append((time.perf_counter(), name,
                               round(float(duration_s) * 1e3, 3)))
        if _COMPILE_SEQ_EVENT in event:
            self._compile_seq += 1
            _M_COMPILES.inc()

    def register_compile_capture(self) -> bool:
        """Idempotently subscribe to jax.monitoring compile durations.
        Runs at this module's import (before the serving encoders' first
        jit when models import the profiler); False when jax (or the
        monitoring API) is unavailable."""
        if self._compile_listener:
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                self.on_compile_duration)
        except Exception:
            return False
        self._compile_listener = True
        return True

    # -- scrape-time views ---------------------------------------------

    def stage_summary(self) -> Dict[str, Dict[str, float]]:
        """{stage: {p50, p90, p99, n, cold_n}} over the sample ring
        (exact percentiles from raw samples — the histograms serve
        Prometheus, this serves BENCH and ``/debug/profile``)."""
        samples = list(self._ring)
        by_stage: Dict[str, list] = {}
        cold: Dict[str, int] = {}
        for (_, stage, ms, phase, *_rest) in samples:
            by_stage.setdefault(stage, []).append(ms)
            if phase == "cold":
                cold[stage] = cold.get(stage, 0) + 1
        out = {}
        for stage, vals in by_stage.items():
            s = sorted(vals)
            out[stage] = {"p50": round(percentile(s, 50), 3),
                          "p90": round(percentile(s, 90), 3),
                          "p99": round(percentile(s, 99), 3),
                          "n": len(s), "cold_n": cold.get(stage, 0)}
        return out

    def stage_p50s(self, steady_only: bool = False
                   ) -> Dict[str, float]:
        """{stage: p50_ms} — the tripwire/baseline view.  With
        ``steady_only`` the cold-jit samples are excluded, so a CI run
        that happened to recompile doesn't fail the latency gate."""
        by_stage: Dict[str, list] = {}
        for (_, stage, ms, phase, *_rest) in list(self._ring):
            if steady_only and phase != "steady":
                continue
            by_stage.setdefault(stage, []).append(ms)
        return {stage: round(percentile(sorted(v), 50), 3)
                for stage, v in by_stage.items() if v}

    def compile_summary(self) -> dict:
        recent = list(self._compiles)
        return {
            "backend_compiles": self._compile_seq,
            "events": len(recent),
            "total_ms": round(sum(ms for _, _, ms in recent), 1),
            "recent": [{"event": ev, "ms": ms}
                       for _, ev, ms in recent[-16:]],
        }

    def snapshot(self) -> dict:
        """The structured block BENCH / the flight recorder embed (and
        ``/debug/profile?format=json`` serves)."""
        return {
            "enabled": _ENABLED,
            "backend": self.backend(),
            "samples": len(self._ring),
            "stages": self.stage_summary(),
            "stage_p50_ms": self.stage_p50s(),
            "stage_p50_ms_steady": self.stage_p50s(steady_only=True),
            "compiles": self.compile_summary(),
        }

    def export_chrome_trace(self) -> dict:
        """Perfetto-openable trace-event JSON: one track per stage
        (complete "X" events, chunk-amortized durations), plus an
        ``xla-compile`` track."""
        samples = list(self._ring)
        compiles = list(self._compiles)
        ts0 = min([t for t, *_ in samples]
                  + [t for t, *_ in compiles], default=0.0)
        events = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "kernel-profiler"}},
        ]
        for (t, stage, ms, phase, codec, geometry, tune,
             shards) in samples:
            events.append({
                "name": stage, "ph": "X", "pid": 1,
                "tid": f"stage:{stage}",
                "ts": round((t - ts0) * 1e6, 1),
                "dur": round(ms * 1e3, 1),
                "cat": phase,
                "args": {"phase": phase, "codec": codec,
                         "geometry": geometry, "tune": tune,
                         "shards": shards},
            })
        for (t, ev, ms) in compiles:
            events.append({
                "name": ev, "ph": "X", "pid": 1, "tid": "xla-compile",
                "ts": round((t - ts0) * 1e6, 1),
                "dur": round(ms * 1e3, 1), "cat": "compile",
                "args": {},
            })
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "backend": self.backend(),
                "compiles": self.compile_summary(),
            },
        }

    def clear(self) -> None:
        """Bench/test isolation: drop samples and the cold/steady memo
        (registry histograms are cumulative by design and stay)."""
        self._ring.clear()
        self._compiles.clear()
        self._last_seq.clear()


PROFILER = KernelProfiler()
# subscribe to compile events at import: the codec models import this
# module before their first jit, so cold compiles are never missed
PROFILER.register_compile_capture()


def export_chrome_trace() -> dict:
    return PROFILER.export_chrome_trace()
