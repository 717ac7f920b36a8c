"""Fleet admission & overload protection (fleet/ + web/server wiring).

Seeded property tests for the placement planner (ISSUE 6 satellite: no
Hypothesis dependency — a seeded rng sweep pins the same invariants),
scheduler state-machine tests with injected clocks, and websocket-level
admission tests against the server with a protocol-double session (no
JAX compile — fast tier)."""

import asyncio
import dataclasses
import json
import random

import pytest
from aiohttp import ClientSession

from docker_nvidia_glx_desktop_tpu.fleet.capacity import (
    CapacityModel, mb_count)
from docker_nvidia_glx_desktop_tpu.fleet.placement import (
    SessionSpec, drain_chip, migration_moves, plan_placement, shed_order)
from docker_nvidia_glx_desktop_tpu.fleet.scheduler import (
    Busy, FleetScheduler, render_fleet_text)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 30))
    finally:
        loop.close()


def _specs(rnd, n, geometries=((1920, 1080), (1280, 720))):
    out = []
    for i in range(n):
        w, h = geometries[rnd.randrange(len(geometries))]
        out.append(SessionSpec(sid=f"s{i}", width=w, height=h,
                               fps=rnd.choice((30.0, 60.0)),
                               tier=rnd.randrange(3),
                               joined_at=rnd.random() * 100.0))
    return out


def _fresh_model(**kw):
    """A CapacityModel on an EMPTY ledger: the default model reads the
    process-global budget ledger, which earlier tests in a full run
    feed with measured frames — a prior-anchored assertion must not
    depend on suite ordering."""
    from docker_nvidia_glx_desktop_tpu.obs.budget import BudgetLedger
    return CapacityModel(ledger=BudgetLedger(), **kw)


class TestCapacityModel:
    def test_prior_anchors_1080p_to_one_session_per_chip(self):
        # BENCH_r05 anchor: 10.9 ms at 1080p against a 16.7 ms budget
        # with 0.85 headroom -> exactly the BASELINE config-5 shape
        m = _fresh_model()
        assert m.sessions_per_chip(1920, 1080, 60.0) == 1
        assert m.fleet_capacity(8, 1920, 1080, 60.0) == 8

    def test_cost_scales_with_macroblocks(self):
        m = CapacityModel()
        c1080 = m.session_cost_ms(1920, 1080)
        c720 = m.session_cost_ms(1280, 720)
        ratio = mb_count(1920, 1080) / mb_count(1280, 720)
        assert c1080 / c720 == pytest.approx(ratio, rel=1e-6)

    def test_measured_cost_overrides_prior(self):
        from docker_nvidia_glx_desktop_tpu.obs.budget import BudgetLedger
        led = BudgetLedger()
        led.set_context(1920, 1080, 60.0)
        # one frame at 8 ms total with no sub-stages
        led._on_trace("marks", (1, [("a", 0.0), ("total", 0.008)], None))
        m = CapacityModel(ledger=led)
        assert m.measured_us_per_mb() == pytest.approx(
            8e3 / mb_count(1920, 1080), rel=1e-3)
        # 8 ms against 16.7*0.85 -> still 1/chip, but now measured
        assert m.snapshot(1, 1920, 1080, 60.0)["us_per_mb_source"] \
            == "measured"

    def test_overrides(self):
        m = CapacityModel(max_sessions_override=5, per_chip_override=3)
        assert m.sessions_per_chip(64, 64, 60.0) == 3
        assert m.fleet_capacity(4, 64, 64, 60.0) == 5   # total wins

    def test_measured_cost_normalizes_mesh_parallelism(self):
        # the batch path records ONE span per tick for the whole mesh:
        # n chips in parallel means total chip-time = p50 x n, so the
        # per-chip-per-MB unit must carry the chip factor — without it
        # capacity overestimates ~x n_chips once measurements replace
        # the prior
        from docker_nvidia_glx_desktop_tpu.obs.budget import BudgetLedger
        led = BudgetLedger()
        led.set_context(1920, 1080, 60.0, sessions=8)
        led._on_trace("marks", (1, [("a", 0.0), ("total", 0.008)], None))
        m = CapacityModel(ledger=led)
        assert m.measured_us_per_mb(8) == pytest.approx(
            8 * m.measured_us_per_mb(1), rel=1e-9)
        assert m.fleet_capacity(8, 1920, 1080, 60.0) \
            <= 8 * m.sessions_per_chip(1920, 1080, 60.0, n_chips=8)


class TestPlacementProperties:
    """Seeded sweep over random session populations (the planner is
    pure, so 200 cases run in milliseconds)."""

    CASES = 60

    def test_never_exceeds_modeled_chip_capacity(self):
        rnd = random.Random(42)
        for case in range(self.CASES):
            m = CapacityModel(per_chip_override=rnd.randrange(1, 4))
            specs = _specs(rnd, rnd.randrange(1, 25))
            chips = rnd.randrange(1, 9)
            plan = plan_placement(specs, chips, model=m, seed=case)
            used = sum(b.chips for b in plan.buckets.values())
            assert used <= chips
            for b in plan.buckets.values():
                assert len(b.sessions) <= b.chips * b.per_chip, \
                    f"case {case}: bucket {b.key} over capacity"
                ns, nx = b.mesh
                assert 1 <= ns * nx <= b.chips

    def test_same_seed_same_plan(self):
        rnd = random.Random(7)
        for case in range(self.CASES):
            m = CapacityModel(per_chip_override=2)
            specs = _specs(rnd, rnd.randrange(1, 20))
            chips = rnd.randrange(1, 6)
            a = plan_placement(specs, chips, model=m, seed=case)
            b = plan_placement(list(reversed(specs)), chips, model=m,
                               seed=case)
            assert a.assignment() == b.assignment()
            assert a.shed == b.shed

    def test_plan_partitions_session_set_exactly(self):
        rnd = random.Random(3)
        for case in range(self.CASES):
            m = CapacityModel(per_chip_override=1)
            specs = _specs(rnd, rnd.randrange(1, 30))
            plan = plan_placement(specs, rnd.randrange(0, 5),
                                  model=m, seed=case)
            placed = plan.placed()
            everything = sorted(placed + plan.shed)
            assert everything == sorted(s.sid for s in specs), \
                "no drop, no dup"
            assert len(set(placed)) == len(placed)

    def test_migration_preserves_session_set(self):
        rnd = random.Random(11)
        for case in range(self.CASES):
            m = CapacityModel(per_chip_override=2)
            specs = _specs(rnd, rnd.randrange(2, 20))
            old = plan_placement(specs, 6, model=m, seed=case)
            new = drain_chip(specs, 6, model=m, seed=case)
            moves = migration_moves(old, new)
            # every session accounted for across the two plans
            assert sorted(old.placed() + old.shed) \
                == sorted(new.placed() + new.shed)
            sheds = {mv["sid"] for mv in moves
                     if mv["action"] == "shed"}
            assert sheds == set(old.placed()) - set(new.placed())

    def test_drain_feasible_or_explicit_shed(self):
        rnd = random.Random(23)
        for case in range(self.CASES):
            per_chip = rnd.randrange(1, 3)
            m = CapacityModel(per_chip_override=per_chip)
            specs = _specs(rnd, rnd.randrange(1, 16),
                           geometries=((1920, 1080),))
            chips = rnd.randrange(2, 8)
            plan = drain_chip(specs, chips, model=m, seed=case)
            if len(specs) <= (chips - 1) * per_chip:
                assert not plan.shed, "feasible N-1 plan must not shed"
            assert sorted(plan.placed() + plan.shed) \
                == sorted(s.sid for s in specs)

    def test_drain_normalizes_measured_cost_at_current_pool(self):
        # the ledger window was measured on N chips; the N-1 drain plan
        # must normalize the measured cost at N, not at the hypothetical
        # smaller pool — otherwise per-session cost is understated by
        # (N-1)/N and /debug/fleet calls a cordon "feasible" that sheds
        from docker_nvidia_glx_desktop_tpu.obs.budget import BudgetLedger
        led = BudgetLedger()
        led.set_context(1920, 1080, 60.0, sessions=8)
        led._on_trace("marks", (1, [("a", 0.0), ("total", 0.008)], None))
        m = CapacityModel(ledger=led)
        rnd = random.Random(5)
        specs = [SessionSpec(sid=f"s{i}", fps=60.0,
                             tier=rnd.randrange(3),
                             joined_at=rnd.random() * 100.0)
                 for i in range(12)]
        n = 8
        drained = drain_chip(specs, n, model=m, seed=0)
        explicit = plan_placement(specs, n - 1, model=m, seed=0,
                                  measured_chips=n)
        assert drained.assignment() == explicit.assignment()
        assert drained.shed == explicit.shed
        for b in drained.buckets.values():
            assert b.per_chip == m.sessions_per_chip(
                1920, 1080, 60.0, n_chips=n)

    def test_shed_order_is_lowest_tier_newest_first(self):
        specs = [
            SessionSpec(sid="old-vip", tier=2, joined_at=1.0),
            SessionSpec(sid="new-vip", tier=2, joined_at=9.0),
            SessionSpec(sid="old-free", tier=0, joined_at=2.0),
            SessionSpec(sid="new-free", tier=0, joined_at=8.0),
        ]
        order = [s.sid for s in shed_order(specs)]
        assert order == ["new-free", "old-free", "new-vip", "old-vip"]


class TestDamagePlacement:
    """Damage-scaled cost-bin packing (ISSUE 20): each chip is a cost
    bin of the headroom-derated frame budget; a session is charged
    ``base x damage_factor(damage)`` and every chip reserves the
    largest single-session spike gap, so any ONE co-tenant jumping to
    full damage still fits the budget without displacing anyone."""

    CASES = 60

    @staticmethod
    def _dmg_specs(rnd, n, geometries=((1920, 1080), (1280, 720))):
        out = []
        for i in range(n):
            w, h = geometries[rnd.randrange(len(geometries))]
            out.append(SessionSpec(
                sid=f"s{i}", width=w, height=h, fps=60.0,
                tier=rnd.randrange(3), joined_at=rnd.random() * 100.0,
                damage=rnd.choice((0.0, 0.02, 0.1, 0.4, 0.8, 1.0))))
        return out

    def test_charged_load_plus_reserve_never_exceeds_budget(self):
        """The capacity invariant AND the spike guarantee in one
        inequality: load + reserve <= budget means removing any
        co-tenant's charge and re-adding its full base still fits."""
        rnd = random.Random(20)
        budget = 0.85 * 1000.0 / 60.0
        for case in range(self.CASES):
            m = _fresh_model()
            specs = self._dmg_specs(rnd, rnd.randrange(1, 25))
            chips = rnd.randrange(1, 9)
            plan = plan_placement(specs, chips, model=m, seed=case)
            for b in plan.buckets.values():
                base = m.session_cost_ms(b.key[1], b.key[0],
                                         n_chips=chips)
                assert len(b.chip_load_ms) == b.chips
                assert len(b.chip_reserve_ms) == b.chips
                for ld, rs in zip(b.chip_load_ms, b.chip_reserve_ms):
                    assert (ld + rs <= budget + 1e-6
                            or ld <= base + 1e-6), \
                        (f"case {case}: chip over budget "
                         f"({ld} + {rs} > {budget})")

    def test_all_full_damage_degenerates_to_count_model(self):
        """damage=1.0 everywhere must price every session at its full
        base cost: no chip ever packs denser than sessions_per_chip."""
        rnd = random.Random(21)
        for case in range(self.CASES):
            m = _fresh_model()
            specs = [SessionSpec(sid=f"s{i}", width=1280, height=720,
                                 fps=60.0, tier=rnd.randrange(3),
                                 joined_at=rnd.random() * 100.0,
                                 damage=1.0)
                     for i in range(rnd.randrange(1, 20))]
            chips = rnd.randrange(1, 9)
            plan = plan_placement(specs, chips, model=m, seed=case)
            per = m.sessions_per_chip(1280, 720, 60.0, n_chips=chips)
            base = m.session_cost_ms(1280, 720, n_chips=chips)
            for b in plan.buckets.values():
                assert len(b.sessions) <= b.chips * per
                for ld in b.chip_load_ms:
                    assert int(round(ld / base)) <= per, \
                        f"case {case}: denser than the count model"

    def test_idle_sessions_pack_denser_with_spike_headroom(self):
        """The fleet-cost half of the perf claim: idle (damage 0)
        sessions pack beyond the count model — but only as far as the
        spike reserve allows.  720p@60 off the prior: base 4.81 ms,
        budget 14.17 ms, count model 2/chip; at the 0.35 floor the
        charge is 1.68 ms with a 3.12 ms reserve -> 6/chip."""
        specs = [SessionSpec(sid=f"s{i}", width=1280, height=720,
                             fps=60.0, joined_at=float(i), damage=0.0)
                 for i in range(12)]
        m = _fresh_model()
        plan = plan_placement(specs, 8, model=m, seed=1)
        assert not plan.shed
        b = plan.buckets[(720, 1280)]
        count_chips = -(-12 // m.sessions_per_chip(1280, 720, 60.0,
                                                   n_chips=8))
        assert b.chips < count_chips, \
            "idle sessions should pack denser than the count model"
        budget = m.headroom * 1000.0 / 60.0
        for ld, rs in zip(b.chip_load_ms, b.chip_reserve_ms):
            assert ld + rs <= budget + 1e-6

    def test_spike_never_sheds_before_backpressure(self):
        """A damage spike must engage the backpressure ladder, never
        the shed list.  Two halves: (a) in the idle-packed plan, any
        ONE session re-priced at full base still fits in place (the
        reserve is sized for exactly this) and a spiked replan places
        the whole population with chips to spare; (b) the shed path's
        arithmetic — fleet_capacity — is damage-BLIND: telemetry can
        only scale per-session placement charges, never the admitted-
        session count."""
        specs = [SessionSpec(sid=f"s{i}", width=1280, height=720,
                             fps=60.0, joined_at=float(i), damage=0.0)
                 for i in range(12)]
        m = _fresh_model()
        p1 = plan_placement(specs, 8, model=m, seed=3)
        assert not p1.shed
        spiked = [dataclasses.replace(s, damage=1.0)
                  if s.sid == "s4" else s for s in specs]
        p2 = plan_placement(spiked, 8, model=m, seed=3)
        assert not p2.shed, "spike must never shed a session"
        assert sorted(p2.placed()) == sorted(p1.placed())
        budget = m.headroom * 1000.0 / 60.0
        base = m.session_cost_ms(1280, 720)
        for b in p2.buckets.values():
            for ld, rs in zip(b.chip_load_ms, b.chip_reserve_ms):
                # the spike invariant restated post-spike: every chip
                # could still absorb ANOTHER co-tenant going hot
                assert ld + rs <= budget + 1e-6 or ld <= base + 1e-6
        # (b) the capacity verdict ignores damage telemetry entirely
        from docker_nvidia_glx_desktop_tpu.obs.content import PLANE
        cap0 = m.fleet_capacity(4, 1280, 720, 60.0)
        PLANE.record("dmg-spike-test", {"damage_fraction": 1.0})
        try:
            assert m.fleet_capacity(4, 1280, 720, 60.0) == cap0
        finally:
            PLANE.drop("dmg-spike-test")

    def test_scheduler_feeds_content_plane_charge(self):
        """The admission spec's damage field comes from the content
        plane's damage_charge: max(latest, p95) of the rolling window,
        clamped to [0, 1]; no samples -> full-cost None."""
        from docker_nvidia_glx_desktop_tpu.obs.content import (
            ContentPlane)
        plane = ContentPlane()
        assert plane.damage_charge("nope") is None
        for d in (0.2, 0.05, 0.9, 0.1, 0.0):
            plane.record("sid1", {"damage_fraction": d})
        got = plane.damage_charge("sid1")
        vals = [0.2, 0.05, 0.9, 0.1, 0.0]
        import numpy as _np
        want = min(max(vals[-1], float(_np.percentile(vals, 95))), 1.0)
        assert got == pytest.approx(want)
        # spike-proof: a single full-damage frame dominates the charge
        plane.record("sid1", {"damage_fraction": 1.0})
        assert plane.damage_charge("sid1") == 1.0


class TestMultiChipSessions:
    """ISSUE 12: a session may cost MORE than one chip (spatial
    sharding).  Admission and drain must charge it its whole chip
    group and treat it atomically — never split across a cordon."""

    CASES = 40

    # prior 1.4 us/MB: 1080p60 fits one chip (11.4 ms vs 14.2
    # allowed); 4K30 (32400 MBs = 45.4 ms vs 28.3 allowed) needs
    # ceil=2 and gets 2: the coded height follows the mesh (native
    # 4K's 135 MB rows are coded as 136, 68 a shard:
    # feasible_spatial_shards); 4K60 (vs 14.2) needs ceil=4 and gets
    # 4 (34 rows a shard).
    PRIOR = 1.4

    def _model(self):
        return _fresh_model(prior_us_per_mb=self.PRIOR)

    def test_chips_for_session_model(self):
        m = self._model()
        assert m.chips_for_session(1920, 1080, 60.0) == 1
        assert m.chips_for_session(3840, 2160, 30.0) == 2
        assert m.chips_for_session(3840, 2160, 60.0) == 4
        # operator per-chip pin declares the chip sufficient
        assert _fresh_model(per_chip_override=2).chips_for_session(
            3840, 2160, 60.0) == 1

    def test_fleet_capacity_divides_by_chip_group(self):
        m = self._model()
        # 8 chips of 2-chip 4K30 sessions = 4 sessions, not 8
        assert m.fleet_capacity(8, 3840, 2160, 30.0) == 4
        assert m.fleet_capacity(2, 3840, 2160, 30.0) == 1
        assert m.snapshot(8, 3840, 2160, 60.0)[
            "chips_per_session"] == 4

    def test_modeled_capacity_never_exceeded_with_multichip(self):
        rnd = random.Random(31)
        m = self._model()
        for case in range(self.CASES):
            specs = _specs(rnd, rnd.randrange(1, 14),
                           geometries=((1920, 1080), (3840, 2160)))
            chips = rnd.randrange(1, 9)
            plan = plan_placement(specs, chips, model=m, seed=case)
            used = sum(b.chips for b in plan.buckets.values())
            assert used <= chips
            for b in plan.buckets.values():
                need = b.chips_per_session
                if need > 1:
                    # whole chip groups: sessions x group <= chips
                    assert len(b.sessions) * need <= b.chips, \
                        f"case {case}: bucket {b.key} over-packed"
                else:
                    assert len(b.sessions) <= b.chips * b.per_chip
            assert sorted(plan.placed() + plan.shed) \
                == sorted(s.sid for s in specs)

    def test_drain_keeps_sharded_session_atomic(self):
        """Draining a chip under a sharded session either refits the
        WHOLE session on the survivors or sheds it whole — a plan
        never leaves it straddling the cordon with a partial group."""
        m = self._model()
        fourk = [SessionSpec(sid="uhd", width=3840, height=2160,
                             fps=30.0, tier=1, joined_at=1.0)]
        # 3 chips: N-1 = 2 still fits the 2-chip 4K30 session
        plan = drain_chip(fourk, 3, model=m, seed=0)
        assert plan.placed() == ("uhd",) and not plan.shed
        b = next(iter(plan.buckets.values()))
        assert b.chips == 2 and b.chips_per_session == 2
        # mesh realizes the spatial extent the session is charged for
        # (135 MB rows coded as 136 -> a (1, 2) mesh)
        assert b.mesh == (1, 2)
        # 2 chips: N-1 = 1 cannot host a 2-chip session — shed whole
        plan = drain_chip(fourk, 2, model=m, seed=0)
        assert plan.shed == ("uhd",) and not plan.placed()

    def test_mixed_mesh_1080p_and_4k(self):
        """The ISSUE 12 shape: 1080p sessions one-per-chip on the
        session axis AND a multi-chip 4K session on the same pool."""
        m = self._model()
        specs = [SessionSpec(sid=f"hd{i}", joined_at=float(i))
                 for i in range(4)]
        specs.append(SessionSpec(sid="uhd", width=3840, height=2160,
                                 fps=30.0, tier=2, joined_at=0.5))
        plan = plan_placement(specs, 6, model=m, seed=3)
        assert sorted(plan.placed()) == sorted(s.sid for s in specs)
        uhd = plan.buckets[(2160, 3840)]
        assert uhd.chips == 2 and uhd.chips_per_session == 2
        assert uhd.mesh == (1, 2)
        hd = plan.buckets[(1088, 1920)]
        assert hd.chips == 4 and len(hd.sessions) == 4

    def test_migration_preserves_set_with_multichip(self):
        rnd = random.Random(37)
        m = self._model()
        for case in range(20):
            specs = _specs(rnd, rnd.randrange(2, 10),
                           geometries=((1920, 1080), (3840, 2160)))
            old = plan_placement(specs, 8, model=m, seed=case)
            new = drain_chip(specs, 8, model=m, seed=case)
            moves = migration_moves(old, new)
            assert sorted(old.placed() + old.shed) \
                == sorted(new.placed() + new.shed)
            sheds = {mv["sid"] for mv in moves
                     if mv["action"] == "shed"}
            assert sheds == set(old.placed()) - set(new.placed())


class TestScheduler:
    def _sched(self, **kw):
        kw.setdefault("model", CapacityModel(per_chip_override=1))
        kw.setdefault("chips_fn", lambda: 2)
        kw.setdefault("geometry", (128, 96))
        kw.setdefault("fps", 30.0)
        kw.setdefault("queue_depth", 2)
        kw.setdefault("queue_timeout_s", 0.2)
        kw.setdefault("retry_after_s", 1.0)
        return FleetScheduler(**kw)

    def test_admit_queue_reject_full(self):
        async def go():
            s = self._sched()
            a = [await s.acquire() for _ in range(2)]
            assert all(x.admitted for x in a) and s.at_capacity
            w1 = asyncio.ensure_future(s.acquire())
            w2 = asyncio.ensure_future(s.acquire())
            await asyncio.sleep(0.02)
            assert s.queued == 2
            rej = await s.acquire()
            assert isinstance(rej, Busy) and rej.reason == "queue_full"
            assert rej.payload()["retry_after_s"] > 0
            # retry_after stretches with queue depth
            assert rej.retry_after_s > s.retry_after_base_s
            s.release(a[0])
            s.release(a[1])
            b1, b2 = await w1, await w2
            assert b1.admitted and b2.admitted
            return s

        s = run(go())
        assert s.active == 2

    def test_queue_timeout_rejects_with_retry_after(self):
        async def go():
            s = self._sched()
            a = [await s.acquire() for _ in range(2)]
            rej = await s.acquire()          # waits 0.2 s, then busy
            assert isinstance(rej, Busy)
            assert rej.reason == "queue_timeout"
            assert rej.retry_after_s > 0
            for x in a:
                s.release(x)

        run(go())

    def test_higher_tier_promoted_first(self):
        async def go():
            s = self._sched(queue_depth=4, queue_timeout_s=5.0)
            a = [await s.acquire() for _ in range(2)]
            lo = asyncio.ensure_future(s.acquire(tier=0))
            await asyncio.sleep(0.02)
            hi = asyncio.ensure_future(s.acquire(tier=1))
            await asyncio.sleep(0.02)
            s.release(a[0])
            await asyncio.sleep(0.02)
            assert hi.done() and not lo.done(), \
                "tier 1 must jump the tier-0 waiter"
            s.release(a[1])
            await lo

        run(go())

    def test_capacity_drop_sheds_newest_lowest_tier_first(self):
        async def go():
            chips = [3]
            s = self._sched(chips_fn=lambda: chips[0], queue_depth=0)
            evicted = []
            adms = []
            for tier in (1, 0, 0):           # joined in this order
                adm = await s.acquire(tier=tier)
                adm.evict = (lambda retry, a=adm:
                             evicted.append((a.sid, retry)))
                adms.append(adm)
            assert s.active == 3
            chips[0] = 2                     # one chip died
            s.refresh()
            assert s.capacity == 2 and s.active == 2
            # victim = the NEWEST tier-0 session (last joined)
            assert [sid for sid, _ in evicted] == [adms[2].sid]
            assert evicted[0][1] > 0         # carries retry_after
            return s

        s = run(go())
        assert s.sheds == 1

    def test_model_capacity_dip_needs_patience(self):
        class _StubModel:
            def __init__(self):
                self.cap = 2

            def fleet_capacity(self, n_chips, width, height, fps):
                return self.cap

        async def go():
            stub = _StubModel()
            s = FleetScheduler(model=stub, chips_fn=lambda: 2,
                               queue_depth=0, shed_patience_ticks=3)
            a = [await s.acquire() for _ in range(2)]
            evicted = []
            for adm in a:
                adm.evict = (lambda r, sid=adm.sid:
                             evicted.append(sid))
            stub.cap = 1                 # model-driven dip (p50 noise)
            s.refresh()
            s.refresh()
            assert not evicted, "noise dip must not shed immediately"
            s.refresh()                  # sustained 3 ticks -> shed
            assert len(evicted) == 1
            stub.cap = 2                 # recovery resets the counter
            s.refresh()
            assert s._over_cap_ticks == 0

        run(go())

    def test_migrate_preferred_over_evict(self):
        async def go():
            chips = [2]
            s = self._sched(chips_fn=lambda: chips[0], queue_depth=0)
            a1 = await s.acquire()
            a2 = await s.acquire()
            moved, killed = [], []
            a2.migrate = lambda: moved.append(a2.sid) or True
            a2.evict = lambda retry: killed.append(a2.sid)
            a1.evict = lambda retry: killed.append(a1.sid)
            chips[0] = 1
            s.refresh()
            assert moved == [a2.sid] and not killed
            assert s.migrations == 1 and s.sheds == 0

        run(go())

    def test_backpressure_walks_degrade_ladder_then_restores(self):
        async def go():
            now = [0.0]
            levels = []
            s = self._sched(queue_depth=4, queue_timeout_s=30.0,
                            on_degrade=levels.append,
                            max_degrade_level=2,
                            backpressure_cooldown_s=1.0,
                            clock=lambda: now[0])
            a = [await s.acquire() for _ in range(2)]
            waiters = [asyncio.ensure_future(s.acquire())
                       for _ in range(3)]
            await asyncio.sleep(0.02)
            now[0] += 2.0
            s.backpressure_tick()
            assert s.backpressure_level == 1
            now[0] += 2.0
            s.backpressure_tick()
            assert s.backpressure_level == 2 and levels == [1, 2]
            now[0] += 0.5
            s.backpressure_tick()            # cooldown holds
            assert s.backpressure_level == 2
            # queue drains -> restore one level per cooldown
            for x in a:
                s.release(x)
            got = [await w for w in waiters[:2]]
            waiters[2].cancel()
            for g in got:
                s.release(g)
            s._waiters.clear()
            now[0] += 2.0
            s.backpressure_tick()
            assert s.backpressure_level == 1 and levels[-1] == 1
            return s

        run(go())

    def test_snapshot_shape(self):
        async def go():
            s = self._sched()
            await s.acquire()
            snap = s.snapshot()
            for key in ("capacity", "active", "queued", "at_capacity",
                        "retry_after_s", "backpressure_level", "model",
                        "sessions", "drain_one_chip"):
                assert key in snap
            assert snap["model"]["sessions_per_chip"] == 1

        run(go())

    def test_snapshot_drain_feasibility_off_live_planner(self):
        """/debug/fleet pre-computes the N-1 drain plan for the live
        session set: feasible while the survivors can hold everyone,
        else the exact lowest-tier/newest-first shed list."""
        async def go():
            s = self._sched(chips_fn=lambda: 3)   # capacity 3 at 1/chip
            a1 = await s.acquire(tier=1)
            await s.acquire(tier=1)
            d = s.snapshot()["drain_one_chip"]
            assert d["feasible"] and d["chips_after"] == 2
            assert d["would_shed"] == []
            a3 = await s.acquire(tier=0)          # newest, lowest tier
            d = s.snapshot()["drain_one_chip"]
            assert not d["feasible"]
            assert d["would_shed"] == [a3.sid]
            text = render_fleet_text(s)
            assert "drain one chip" in text and a3.sid in text
            assert a1.sid not in d["would_shed"]

        run(go())


class TestAdmissionOverWebsocket:
    """End-to-end /ws admission against the real server wiring with a
    protocol-double session (no JAX, fast tier): busy payloads carry
    retry_after_s, /healthz reports at_capacity, /debug/fleet renders."""

    def _cfg(self, **extra):
        from docker_nvidia_glx_desktop_tpu.utils.config import from_env
        env = {"ENABLE_BASIC_AUTH": "false", "LISTEN_ADDR": "127.0.0.1",
               "LISTEN_PORT": "0", "FLEET_ENABLE": "true",
               "FLEET_MAX_SESSIONS": "1", "FLEET_QUEUE_DEPTH": "1",
               "FLEET_QUEUE_TIMEOUT_S": "0.3",
               "FLEET_RETRY_AFTER_S": "1.5"}
        env.update(extra)
        return from_env(env)

    def test_admit_then_busy_with_retry_after(self):
        from docker_nvidia_glx_desktop_tpu.web.server import (
            bound_port, serve)
        from tests.test_web import DummySession

        async def go():
            cfg = self._cfg()
            runner = await serve(cfg, DummySession())
            port = bound_port(runner)
            try:
                async with ClientSession() as http:
                    ws1 = await http.ws_connect(
                        f"http://127.0.0.1:{port}/ws", max_msg_size=0)
                    hello = await ws1.receive_json(timeout=5)
                    assert hello["type"] == "hello"
                    # second join: queue (depth 1) -> timeout -> busy
                    ws2 = await http.ws_connect(
                        f"http://127.0.0.1:{port}/ws", max_msg_size=0)
                    busy = await ws2.receive_json(timeout=5)
                    assert busy["type"] == "busy"
                    assert busy["reason"] == "queue_timeout"
                    assert busy["retry_after_s"] >= 1.5
                    await ws2.close()
                    # third join while ws1 holds: healthz says FULL but
                    # stays 200 and distinct from degraded/draining
                    async with http.get(
                            f"http://127.0.0.1:{port}/healthz") as r:
                        assert r.status == 200
                        body = await r.json()
                        assert body["state"] == "at_capacity"
                        assert body["ok"] is True
                        assert body["fleet"]["capacity"] == 1
                        assert body["fleet"]["retry_after_s"] > 0
                    # /debug/fleet: text + json views, auth-exempt
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/fleet") as r:
                        assert r.status == 200
                        text = await r.text()
                        assert "AT CAPACITY" in text
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/fleet"
                            "?format=json") as r:
                        snap = await r.json()
                        assert snap["enabled"] and snap["active"] == 1
                    await ws1.close()
                    # slot freed: a fresh join admits again
                    await asyncio.sleep(0.05)
                    ws3 = await http.ws_connect(
                        f"http://127.0.0.1:{port}/ws", max_msg_size=0)
                    hello3 = await ws3.receive_json(timeout=5)
                    assert hello3["type"] == "hello"
                    await ws3.close()
            finally:
                await runner.cleanup()

        run(go())

    def test_queued_join_admitted_when_slot_frees(self):
        from docker_nvidia_glx_desktop_tpu.web.server import (
            bound_port, serve)
        from tests.test_web import DummySession

        async def go():
            cfg = self._cfg(FLEET_QUEUE_TIMEOUT_S="5")
            runner = await serve(cfg, DummySession())
            port = bound_port(runner)
            try:
                async with ClientSession() as http:
                    ws1 = await http.ws_connect(
                        f"http://127.0.0.1:{port}/ws", max_msg_size=0)
                    assert (await ws1.receive_json(
                        timeout=5))["type"] == "hello"

                    async def queued_join():
                        ws2 = await http.ws_connect(
                            f"http://127.0.0.1:{port}/ws",
                            max_msg_size=0)
                        msg = await ws2.receive_json(timeout=10)
                        await ws2.close()
                        return msg

                    task = asyncio.ensure_future(queued_join())
                    await asyncio.sleep(0.2)     # parked in the queue
                    assert not task.done()
                    await ws1.close()            # frees the slot
                    msg = await task
                    assert msg["type"] == "hello", \
                        "queued joiner must be admitted, not dropped"
            finally:
                await runner.cleanup()

        run(go())

    def test_fleet_disabled_leaves_ws_contract_unchanged(self):
        from docker_nvidia_glx_desktop_tpu.web.server import (
            bound_port, serve)
        from tests.test_web import DummySession

        async def go():
            cfg = self._cfg(FLEET_ENABLE="false")
            runner = await serve(cfg, DummySession())
            port = bound_port(runner)
            try:
                assert runner.app["fleet"] is None
                async with ClientSession() as http:
                    for _ in range(3):           # no admission ceiling
                        ws = await http.ws_connect(
                            f"http://127.0.0.1:{port}/ws",
                            max_msg_size=0)
                        assert (await ws.receive_json(
                            timeout=5))["type"] == "hello"
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/fleet") as r:
                        assert (await r.json())["enabled"] is False
            finally:
                await runner.cleanup()

        run(go())

    def test_busy_payload_is_json_serializable(self):
        b = Busy("queue_full", 2.5, 3)
        payload = json.loads(json.dumps(b.payload()))
        assert payload == {"type": "busy", "reason": "queue_full",
                           "retry_after_s": 2.5, "queue_depth": 3}


class TestSchedulerTimeline:
    """ISSUE 13: admission decisions and sheds land on the fleet event
    timeline (frame-frontier-anchored) and a shed trips the flight
    recorder — the journey-id lineage the shed interrupts is the one
    the postmortem dump names."""

    def test_admit_shed_emit_events_and_flight_dump(self):
        from docker_nvidia_glx_desktop_tpu.obs import events as obsev
        from docker_nvidia_glx_desktop_tpu.obs import flight as obsf
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj

        async def go():
            book = obsj.JourneyBook("fleet-tl")
            obsf.FLIGHT.clear()
            n0 = len(obsev.EVENTS.recent())
            try:
                book.mint(101)               # the live frame frontier
                chips = [2]
                s = FleetScheduler(
                    model=CapacityModel(per_chip_override=1),
                    chips_fn=lambda: chips[0], geometry=(128, 96),
                    fps=30.0, queue_depth=0, queue_timeout_s=0.2,
                    retry_after_s=1.0)
                adms = [await s.acquire() for _ in range(2)]
                for adm in adms:
                    adm.evict = lambda r: None
                chips[0] = 1                 # chip died -> shed
                s.refresh()
                evs = obsev.EVENTS.recent()[n0:]
                kinds = [e["kind"] for e in evs]
                assert kinds.count("admit") == 2
                assert "shed" in kinds
                shed = next(e for e in evs if e["kind"] == "shed")
                assert shed["mode"] == "evicted"
                # anchored to the live journey frontier
                assert shed["frontier"].get("fleet-tl") == 101
                # the shed tripped a flight dump carrying the journeys
                dump = obsf.FLIGHT.find_dump("shed")
                assert dump is not None
                assert "fleet-tl" in dump["journeys"]
            finally:
                book.close_book()
                obsf.FLIGHT.clear()

        run(go())
