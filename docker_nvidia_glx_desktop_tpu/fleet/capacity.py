"""Per-chip session capacity modeled from MEASURED serving costs.

Admission control is only as honest as its cost model.  Rather than a
hand-tuned "max sessions" constant, the fleet scheduler asks this model,
which reads the serving-budget ledger (obs/budget): the ledger's
link-separated compute p50 is the measured per-frame device cost of the
geometry currently serving, and device work in this codebase scales with
macroblock count (every kernel is a per-MB map/scan — ops/), so the cost
of any OTHER geometry is the measured one scaled by the MB-count ratio.
Capacity per chip is then the frame budget divided by the per-session
cost, derated by a headroom fraction so the admission edge sits below
the SLO cliff, not on it.

Cold start (no frames measured yet) falls back to a prior anchored on
the published BENCH numbers (BENCH_r05: 1080p intra 10.9 ms device-only
per frame at 8160 MBs ≈ 1.34 µs/MB), so the first admission decision of
a fresh pod is conservative rather than arbitrary.  ``FLEET_MAX_SESSIONS``
overrides the whole model for operators who know better.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["CapacityModel", "mb_count", "PRIOR_US_PER_MB"]

# BENCH_r05 anchor: 10.9 ms device intra step at 1080p (120x68 = 8160
# macroblocks) -> 1.34 us per macroblock per frame.
PRIOR_US_PER_MB = 10.9e3 / 8160.0


def mb_count(width: int, height: int) -> int:
    """Macroblock count of the MB-padded geometry (the unit all device
    kernels scale with)."""
    return (-(-height // 16)) * (-(-width // 16))


class CapacityModel:
    """sessions-per-chip from ledger-measured per-stage costs.

    ``headroom`` derates the frame budget (0.85 = plan to 85% of the
    deadline) so queueing noise and IDR spikes don't tip admitted
    sessions over the SLO the moment anything jitters.
    """

    # Device-cost factor of the ENCODER_TUNE tiers relative to off.
    # "hq" plans at the CI-gated ceiling (bdrate-smoke fails a build
    # whose hq step exceeds 1.5x off), not the typically-lower measured
    # ratio: admission must hold under the worst step the gate admits.
    # DNGD_HQ_COST_FACTOR overrides after a calibrating TPU round.
    # (Measured: 1.01-1.04x at 1920x1080 on one v5e chip, 9.11 ms of
    # device a frame against 8.75-9.03 on the same pictures: cells
    # desk1080-hq.* beside desk1080.*, my chip runs, PR 48.  The default
    # stays the gate's ceiling.)
    TUNE_COST_FACTORS = {"off": 1.0, "hq_noaq": 1.15, "hq": 1.5}

    def __init__(self, ledger=None, headroom: float = 0.85,
                 prior_us_per_mb: float = PRIOR_US_PER_MB,
                 max_sessions_override: int = 0,
                 per_chip_override: int = 0,
                 tune: str = "off"):
        import os

        self._ledger = ledger
        self.headroom = float(headroom)
        self.prior_us_per_mb = float(prior_us_per_mb)
        self.max_sessions_override = int(max_sessions_override)
        self.per_chip_override = int(per_chip_override)
        self.tune = tune if tune in self.TUNE_COST_FACTORS else "off"
        env = os.environ.get("DNGD_HQ_COST_FACTOR", "")
        if self.tune == "hq" and env:
            try:
                self.tune_cost_factor = max(float(env), 1.0)
            except ValueError:
                self.tune_cost_factor = self.TUNE_COST_FACTORS[self.tune]
        else:
            self.tune_cost_factor = self.TUNE_COST_FACTORS[self.tune]

    def _led(self):
        if self._ledger is None:
            from ..obs.budget import LEDGER
            self._ledger = LEDGER
        return self._ledger

    # -- cost -----------------------------------------------------------

    def measured_us_per_mb(self, n_chips: int = 1) -> Optional[float]:
        """Per-MB *per-chip* device cost from the ledger's live window,
        or None before any frame was measured.  The batch path records
        ONE compute span per tick covering the whole mesh, so the p50 is
        wall time of ``n_chips`` chips working in parallel: total chip-
        time is p50 x chips, and dividing by the context's total MB
        count (geometry x sessions) yields the same per-chip-per-MB unit
        the single-device prior is anchored in.  Without the chip factor
        capacity would overestimate by ~n_chips the moment measurements
        replace the prior.  (Assumes the window was measured on the
        current chip pool — true except transiently across a rebuild,
        until the rolling window turns over.)"""
        led = self._led()
        ctx = led.context()
        if led.frames <= 0 or ctx is None:
            return None
        w, h, _fps, sessions = ctx
        p50 = led.compute_p50_ms()
        if p50 <= 0.0:
            return None
        mbs = mb_count(w, h) * max(int(sessions), 1)
        return (p50 * 1e3 * max(int(n_chips), 1)) / max(mbs, 1)

    def session_cost_ms(self, width: int, height: int,
                        n_chips: int = 1, damage=None) -> float:
        """Modeled per-frame per-chip device cost (ms) of one session at
        this geometry — measured scale when available, prior otherwise.
        The tuning tier's device-cost factor applies to the PRIOR only:
        a ledger window measured under the active tier already carries
        the tier's real cost (double-charging it would underfill).
        ``damage`` (a [0, 1] rolling damage fraction, usually the
        content plane's :meth:`~..obs.content.ContentPlane.damage_charge`)
        scales the charge by ``ops.damage_mask.damage_factor`` — the
        damage-driven encode's cost really is proportional to changed
        rows, floored so a calm session is never priced at zero.  None
        (no telemetry, or the mask off) charges full cost."""
        us_per_mb = self.measured_us_per_mb(n_chips)
        if us_per_mb is None:
            us_per_mb = self.prior_us_per_mb * self.tune_cost_factor
        cost = mb_count(width, height) * us_per_mb / 1e3
        if damage is not None:
            from ..ops import damage_mask as dmg
            cost *= dmg.damage_factor(damage)
        return cost

    # -- capacity -------------------------------------------------------

    def chips_for_session(self, width: int, height: int, fps: float,
                          n_chips: int = 1, max_chips: int = 8,
                          budget_ms: float = None) -> int:
        """Chips ONE session needs to close its frame budget — the
        spatial-shard counterpart of :meth:`sessions_per_chip`.  A 4K30
        session whose modeled per-chip cost exceeds the headroom-derated
        budget consumes several chips (the frame's MB rows shard across
        them, parallel/batch spatial steps) instead of missing its SLO;
        admission and drain planning must charge it accordingly.
        Returns ``ceil(cost / (headroom * budget))`` as a shard count
        the geometry can split into
        (``parallel.batch.feasible_spatial_shards``: the coded height
        follows the mesh, so native 4K's 135 MB rows are coded as 136
        over 2 or 4 chips and every chip charged works; only a shard
        too short for the motion search's halo is refused), capped at
        ``max_chips``; 1 whenever the session fits one chip (including
        under ``per_chip_override`` — an operator pinning sessions per
        chip has declared the chip sufficient)."""
        if self.per_chip_override > 0:
            return 1
        if budget_ms is None:
            budget_ms = 1000.0 / max(float(fps), 1.0)
        allowed = self.headroom * budget_ms
        cost = self.session_cost_ms(width, height, n_chips)
        need = -int(-cost // max(allowed, 1e-6))
        if need > 1:
            from ..parallel.batch import feasible_spatial_shards
            # nx never exceeds the MB row count — cap the search there,
            # not at a 2^16 sentinel
            need = feasible_spatial_shards(
                height, need,
                min(int(max_chips), max(-(-int(height) // 16), 1)))
        return max(1, min(int(max_chips), need))

    def sessions_per_chip(self, width: int, height: int, fps: float,
                          n_chips: int = 1) -> int:
        """How many sessions of this geometry one chip sustains inside
        the frame budget (>= 1: a chip always serves at least one
        session, degraded if need be — shedding the last session is the
        scheduler's decision, never the model's).  ``per_chip_override``
        (FLEET_SESSIONS_PER_CHIP) pins this while still scaling the
        FLEET total with the live chip count — the knob benches and
        cautious operators use.  ``n_chips`` normalizes the MEASURED
        cost (see :meth:`measured_us_per_mb`)."""
        if self.per_chip_override > 0:
            return self.per_chip_override
        budget_ms = 1000.0 / max(float(fps), 1.0)
        cost = self.session_cost_ms(width, height, n_chips)
        return max(1, int(self.headroom * budget_ms / max(cost, 1e-6)))

    def fleet_capacity(self, n_chips: int, width: int, height: int,
                       fps: float) -> int:
        """Total concurrent sessions the fleet admits.  The operator
        override wins when set; otherwise chips x per-chip model — or,
        when one session of this geometry needs SEVERAL chips (spatial
        sharding), chips // chips-per-session: without that division an
        8-chip fleet would admit 8 four-chip 4K sessions and promise
        4x the silicon it has."""
        if self.max_sessions_override > 0:
            return self.max_sessions_override
        n_chips = max(1, int(n_chips))
        # uncapped need: a 4-chip geometry on a 3-chip pool must model
        # 0 whole groups (floored to 1 below — the serve-degraded
        # posture), not shrink into a "3-chip" session
        need = self.chips_for_session(width, height, fps, n_chips,
                                      max_chips=1 << 16)
        if need > 1:
            return max(1, n_chips // need)
        return n_chips * self.sessions_per_chip(
            width, height, fps, n_chips)

    def snapshot(self, n_chips: int, width: int, height: int,
                 fps: float) -> dict:
        """The model's inputs and verdicts (the /debug/fleet block)."""
        measured = self.measured_us_per_mb(n_chips)
        return {
            "headroom": self.headroom,
            "us_per_mb": round(measured if measured is not None
                               else self.prior_us_per_mb, 4),
            "us_per_mb_source": ("measured" if measured is not None
                                 else "prior"),
            "session_cost_ms": round(
                self.session_cost_ms(width, height, n_chips), 3),
            "frame_budget_ms": round(1000.0 / max(float(fps), 1.0), 3),
            "sessions_per_chip": self.sessions_per_chip(
                width, height, fps, n_chips),
            "chips_per_session": self.chips_for_session(
                width, height, fps, n_chips, max_chips=1 << 16),
            "fleet_capacity": self.fleet_capacity(
                n_chips, width, height, fps),
            "override": self.max_sessions_override or None,
            "per_chip_override": self.per_chip_override or None,
            "chips": int(n_chips),
            "tune": self.tune,
            "tune_cost_factor": self.tune_cost_factor,
            # the fleet-mean rolling damage fraction (obs/content) —
            # since the damage-driven encode landed, placement CHARGES
            # per-session damage-scaled costs (fleet/placement,
            # SessionSpec.damage); the mean is the snapshot's summary
            # of what the fleet is paying for
            "observed_damage_fraction": self._observed_damage(),
            "damage_cost_floor": self._damage_floor(),
        }

    @staticmethod
    def _observed_damage():
        try:
            from ..obs.content import PLANE
            d = PLANE.mean_damage_fraction()
            return None if d is None else round(d, 4)
        except Exception:
            return None

    @staticmethod
    def _damage_floor():
        try:
            from ..ops import damage_mask as dmg
            return round(dmg.cost_floor(), 4)
        except Exception:
            return None
