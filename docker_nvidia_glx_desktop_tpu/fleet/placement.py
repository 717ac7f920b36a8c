"""Seeded, deterministic placement planning: sessions -> buckets -> chips.

Pure arithmetic — no devices, no asyncio — so every invariant is
property-testable (tests/test_fleet.py).  The planner bin-packs sessions
onto MB-padded geometry buckets (XLA compiles one program per padded
shape, web/multisession contract) and allots mesh chips to buckets,
deriving each bucket's (session x spatial) mesh shape through
``parallel.batch.replan_mesh`` — the same rule elastic failover uses, so
a plan is always a shape the batch managers can actually compile.

Invariants the tests pin:

- a plan NEVER exceeds the modeled per-chip capacity of any bucket
  (with damage-scaled charges: no chip's charged load plus its spike
  reserve ever exceeds the headroom-derated frame budget);
- the same (sessions, chips, seed) always yields the identical plan;
- a migration between two plans preserves the session set exactly
  (no drop, no duplicate);
- draining a chip yields a feasible N-1 plan or an EXPLICIT shed list —
  assignments and shed always partition the input set;
- a session whose modeled cost exceeds one chip (spatial sharding,
  ``CapacityModel.chips_for_session``) is placed ATOMICALLY: it claims
  its whole chip group or is shed whole — a drain never leaves a 4-shard
  4K session straddling a cordon with 3 chips.

Damage-scaled charging (damage-driven encode): each session carries its
rolling damage fraction (``SessionSpec.damage``, fed from the content
plane's ``damage_charge``; 1.0 = unknown/full).  A calm session is
charged ``base x damage_factor(damage)`` (ops/damage_mask: floored
linear, so calm is cheaper but never free), which lets a chip hold more
calm sessions than the uniform count model would admit.  Every chip
additionally holds a SPIKE RESERVE — the largest single-session
``base - charged`` gap on that chip — so when any one session bursts to
full-frame damage the chip absorbs it inside the frame budget and the
backpressure ladder (degrade, then shed) engages on MEASURED overload,
never pre-emptively against a co-tenant.

Shed priority is strict: lowest tier first, then newest join first —
a long-lived high-tier session is the last thing this fleet drops.
"""

from __future__ import annotations

import dataclasses
import random
from typing import Dict, List, Optional, Sequence, Tuple

from .capacity import CapacityModel

__all__ = ["SessionSpec", "BucketPlan", "Plan", "plan_placement",
           "migration_moves", "drain_chip", "shed_order"]


@dataclasses.dataclass(frozen=True)
class SessionSpec:
    """One session as the planner sees it.  ``tier`` ranks importance
    (higher = kept longer); ``joined_at`` orders same-tier sessions
    (older = kept longer)."""

    sid: str
    width: int = 1920
    height: int = 1080
    fps: float = 60.0
    tier: int = 0
    joined_at: float = 0.0
    # rolling damage fraction the capacity model charges this session
    # at (obs/content damage_charge); 1.0 = unknown or fully dynamic —
    # the conservative full-cost default
    damage: float = 1.0

    @property
    def bucket(self) -> Tuple[int, int]:
        from ..parallel.batch import geometry_bucket
        return geometry_bucket(self.width, self.height)


@dataclasses.dataclass
class BucketPlan:
    """One geometry bucket's share of the mesh."""

    key: Tuple[int, int]              # (pad_h, pad_w)
    chips: int
    mesh: Tuple[int, int]             # (ns, nx) via replan_mesh
    sessions: Tuple[str, ...]
    per_chip: int                     # modeled capacity used
    # chips ONE session of this bucket consumes (spatial sharding:
    # a 4K session whose modeled cost exceeds its budget spreads its
    # MB rows over several chips and must be CHARGED several — the
    # planner treats such a session atomically: it claims its whole
    # chip group or lands on the shed list, never a partial slice)
    chips_per_session: int = 1
    # per-chip charged load (ms) under damage-scaled costs, parallel
    # to the bucket's chips; empty for multi-chip (sharded) buckets
    chip_load_ms: Tuple[float, ...] = ()
    # per-chip spike reserve (ms): the largest single-session
    # base-minus-charged gap on that chip
    chip_reserve_ms: Tuple[float, ...] = ()


@dataclasses.dataclass
class Plan:
    buckets: Dict[Tuple[int, int], BucketPlan]
    shed: Tuple[str, ...]
    n_chips: int
    seed: int

    def assignment(self) -> Dict[str, Tuple[int, int]]:
        """sid -> bucket key for every placed session."""
        return {sid: b.key for b in self.buckets.values()
                for sid in b.sessions}

    def placed(self) -> Tuple[str, ...]:
        return tuple(sid for b in self.buckets.values()
                     for sid in b.sessions)


def shed_order(sessions: Sequence[SessionSpec]) -> List[SessionSpec]:
    """Victims-first ordering: lowest tier, then newest join, then sid
    (a total order — shedding must be reproducible across replicas)."""
    return sorted(sessions,
                  key=lambda s: (s.tier, -s.joined_at, s.sid))


def _keep_order(sessions: Sequence[SessionSpec],
                rng: random.Random) -> List[SessionSpec]:
    """Placement ordering: the mirror of shed order (highest tier and
    oldest join placed first), with the seeded rng breaking exact ties
    so equal sessions spread deterministically-but-fairly."""
    jitter = {s.sid: rng.random() for s in
              sorted(sessions, key=lambda s: s.sid)}
    return sorted(sessions,
                  key=lambda s: (-s.tier, s.joined_at, jitter[s.sid],
                                 s.sid))


def plan_placement(sessions: Sequence[SessionSpec], n_chips: int,
                   model: Optional[CapacityModel] = None,
                   seed: int = 0,
                   measured_chips: Optional[int] = None) -> Plan:
    """Greedy capacity-aware bin-packing.

    Sessions are placed in keep-priority order; a session whose bucket
    is out of headroom claims a free chip for that bucket (first-fit),
    and when no chip is free it lands on the shed list.  Chips are never
    split across buckets (one compiled step per bucket serves one padded
    geometry — splitting a chip would interleave two XLA programs on it,
    which the batch managers already do across buckets by serializing
    dispatches, but the PLAN stays one-bucket-per-chip so per-chip
    capacity stays meaningful).

    ``measured_chips`` is the pool the ledger's cost window was measured
    on, when it differs from the pool being PLANNED (drain planning:
    measure on N, plan N-1) — the measured-cost normalization must use
    the former or a hypothetical smaller plan understates per-session
    cost by measured/planned."""
    from ..parallel.batch import coded_height, replan_mesh

    model = model if model is not None else CapacityModel()
    rng = random.Random(seed)
    n_chips = max(int(n_chips), 0)
    norm_chips = max(int(measured_chips) if measured_chips is not None
                     else n_chips, 1)
    free = n_chips
    placed: Dict[Tuple[int, int], List[SessionSpec]] = {}
    chips: Dict[Tuple[int, int], int] = {}
    per_chip: Dict[Tuple[int, int], int] = {}
    chips_per: Dict[Tuple[int, int], int] = {}
    base_ms: Dict[Tuple[int, int], float] = {}
    allowed_ms: Dict[Tuple[int, int], float] = {}
    loads: Dict[Tuple[int, int], List[float]] = {}
    reserves: Dict[Tuple[int, int], List[float]] = {}
    shed: List[SessionSpec] = []
    for spec in _keep_order(sessions, rng):
        key = spec.bucket
        if key not in per_chip:
            # norm_chips normalizes the MEASURED cost: the ledger's
            # batch span was taken over the whole parallel mesh (see
            # CapacityModel.measured_us_per_mb) — without it the plan
            # would overfill every chip ~n_chips-fold once measurements
            # replace the prior
            per_chip[key] = model.sessions_per_chip(
                spec.width, spec.height, spec.fps,
                n_chips=norm_chips)
            # a session may cost MORE than one chip (spatial sharding,
            # CapacityModel.chips_for_session): it is placed atomically
            # — a whole chips_per group claimed per session, or shed.
            # The need is UNCAPPED by the pool: a 4-chip session on a
            # 3-chip pool must shed, not shrink into a 3-chip one
            chips_per[key] = model.chips_for_session(
                spec.width, spec.height, spec.fps,
                n_chips=norm_chips, max_chips=1 << 16)
            # bucket-uniform base cost (FIRST spec's geometry, like
            # per_chip): all damage scaling prices off the same base so
            # a bucket's chips compare like with like
            base_ms[key] = model.session_cost_ms(
                spec.width, spec.height, n_chips=norm_chips)
            allowed_ms[key] = model.headroom * 1000.0 / max(
                float(spec.fps), 1.0)
        need = chips_per[key]
        if need > 1 or model.per_chip_override > 0:
            # count-based rule for two cases damage charging must not
            # touch: multi-chip (sharded) sessions claim their chip
            # group whole either way, and a per-chip OVERRIDE is the
            # operator declaring the count — cost bins don't outvote it
            if need > 1:
                cap = chips.get(key, 0) // need
            else:
                cap = chips.get(key, 0) * per_chip[key]
            if len(placed.get(key, ())) >= cap:
                if free < need:
                    shed.append(spec)
                    continue
                free -= need
                chips[key] = chips.get(key, 0) + need
            placed.setdefault(key, []).append(spec)
            continue
        # damage-scaled heterogeneous packing: each chip is a cost bin
        # of the headroom-derated frame budget.  A session's charge is
        # base x damage_factor(damage); each chip reserves the largest
        # single-session (base - charged) gap so any ONE co-tenant
        # spiking to full damage still fits the budget (all damage=1.0
        # degenerates to the uniform count model exactly)
        base = base_ms[key]
        d = spec.damage
        if d is None or d >= 1.0:
            charge = base
        else:
            from ..ops.damage_mask import damage_factor
            charge = base * damage_factor(d)
        reserve_s = max(base - charge, 0.0)
        ld = loads.setdefault(key, [])
        rs = reserves.setdefault(key, [])
        budget = allowed_ms[key]
        eps = 1e-9 * max(budget, 1.0)   # absorbs summation ulps only
        slot = None
        for i in range(len(ld)):
            if ld[i] + charge + max(rs[i], reserve_s) <= budget + eps:
                slot = i
                break
        if slot is None:
            if free < 1:
                shed.append(spec)
                continue
            free -= 1
            chips[key] = chips.get(key, 0) + 1
            # a freshly-claimed chip always takes the session (the
            # serve-degraded posture: one session per chip minimum,
            # even when its base cost alone exceeds the budget)
            ld.append(0.0)
            rs.append(0.0)
            slot = len(ld) - 1
        ld[slot] += charge
        rs[slot] = max(rs[slot], reserve_s)
        placed.setdefault(key, []).append(spec)
    buckets: Dict[Tuple[int, int], BucketPlan] = {}
    for key in sorted(placed):
        n = chips[key]
        # a sharded session's coded height follows its mesh (the rows
        # are padded until the shards divide them)
        mesh = replan_mesh(len(placed[key]), n,
                           coded_height(key[0], chips_per[key]),
                           want_nx=chips_per[key])
        buckets[key] = BucketPlan(
            key=key, chips=n, mesh=mesh,
            sessions=tuple(s.sid for s in placed[key]),
            per_chip=per_chip[key],
            chips_per_session=chips_per[key],
            chip_load_ms=tuple(round(v, 6) for v in loads.get(key, ())),
            chip_reserve_ms=tuple(round(v, 6)
                                  for v in reserves.get(key, ())))
    # shed list reported in strict victim order, not placement order
    return Plan(buckets=buckets,
                shed=tuple(s.sid for s in shed_order(shed)),
                n_chips=n_chips, seed=seed)


def migration_moves(old: Plan, new: Plan) -> List[dict]:
    """The moves turning ``old`` into ``new``: every session whose
    bucket changed (checkpoint/restore + recovery IDR on arrival), plus
    explicit shed/admit deltas.  The session SETS of both plans must
    match — the planner never invents or loses a session; callers feed
    both plans the same spec list."""
    o = old.assignment()
    n = new.assignment()
    moves: List[dict] = []
    for sid in sorted(set(o) & set(n)):
        if o[sid] != n[sid]:
            moves.append({"sid": sid, "action": "migrate",
                          "from": o[sid], "to": n[sid]})
    for sid in sorted(set(o) - set(n)):
        moves.append({"sid": sid, "action": "shed", "from": o[sid]})
    for sid in sorted(set(n) - set(o)):
        moves.append({"sid": sid, "action": "admit", "to": n[sid]})
    return moves


def drain_chip(sessions: Sequence[SessionSpec], n_chips: int,
               model: Optional[CapacityModel] = None,
               seed: int = 0) -> Plan:
    """The N-1 plan for draining one chip: same deterministic planner
    over one fewer chip.  Either every session fits (feasible drain) or
    the shed list says EXACTLY who must go — never a silent drop.  The
    cost window was measured on the CURRENT pool, so normalization stays
    at ``n_chips`` while the plan targets N-1 (otherwise feasibility is
    optimistic by n/(n-1) and the cordon sheds sessions it promised it
    would not)."""
    return plan_placement(sessions, max(n_chips - 1, 0),
                          model=model, seed=seed,
                          measured_chips=n_chips)
