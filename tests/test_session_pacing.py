"""How a turn of the session loop ends (web/session.py:_await_frame), on a
clock that no wall time moves: ``StreamSession._run`` itself, in the test's
own thread, with the module's ``time`` replaced by a fake whose ``sleep``
advances it, a 60 Hz counter as the source and an encoder whose only work
is to advance the clock.  Six xdist workers cannot make any of it late."""

import math
import types

import pytest

from docker_nvidia_glx_desktop_tpu.rfb.source import (NumpySource,
                                                       SyntheticSource)
from docker_nvidia_glx_desktop_tpu.utils.config import from_env
from docker_nvidia_glx_desktop_tpu.web import session as session_mod
from docker_nvidia_glx_desktop_tpu.web.session import StreamSession

REFRESH = 1 / 60
STEP = StreamSession.TAKE_STEP_S
EPS = 0.0002        # a sleep's overshoot and a few reads of the clock


class FakeTime:
    """``perf_counter``, ``monotonic`` and ``sleep`` of one made-up clock.
    A sleep overshoots as the kernel's does; a read costs a microsecond, so
    that a loop which only reads the clock still gets somewhere."""

    def __init__(self, overshoot=0.00006):
        self.t, self.overshoot, self.sleeps = 50.0, overshoot, []

    def perf_counter(self):
        self.t += 1e-6
        return self.t

    monotonic = perf_counter

    def sleep(self, dt):
        self.sleeps.append(dt)
        self.t += max(dt, 0.0) + self.overshoot


class Counter60:
    """A display: frame ``k`` is swapped in at ``k / hz`` whatever the loop
    does.  As ``benchmark/display.py`` it has no peek and notes the first
    look that found each frame."""

    width, height = 64, 48

    def __init__(self, clock, hz=60.0, until=10.0, static=False):
        self.clock, self.hz, self.static = clock, hz, static
        self.t0 = clock.t
        self.until = self.t0 + until
        self.seen = {}                   # k -> clock at the first look
        self.session = None

    def frame(self):
        now = self.clock.perf_counter()
        if now >= self.until:
            self.session._stop.set()
        k = 0 if self.static else int((now - self.t0) * self.hz)
        self.seen.setdefault(k, now)
        return k, k                      # the "picture" is its own index

    def age(self, k):
        return self.seen[k] - (self.t0 + k / self.hz)


class Work:
    """An encoder front that costs time and nothing else: ``cost(n)``
    seconds in the loop's ``n``-th turn, 0.7 of it in the submit."""

    pipeline_depth = 2

    def __init__(self, clock, cost):
        self.clock, self.cost, self.taken = clock, cost, []

    def encode_submit(self, k):
        self.taken.append(k)
        self.clock.t += 0.7 * self.cost(len(self.taken))
        return k

    def encode_collect(self, k):
        self.clock.t += 0.3 * self.cost(len(self.taken))
        return types.SimpleNamespace(data=b"au", keyframe=False,
                                     encode_ms=1.0)

    def request_keyframe(self):
        pass

    def export_state(self):
        return {}


def counters():
    return (session_mod._M_LOCKED_TAKES.value,
            session_mod._M_TAKE_LOOKS.value)


def drive(monkeypatch, cost=lambda n: 0.010, seconds=10.0, overshoot=0.00006,
          subscribers=True, end_of_turn=None, fps_cap=None, static=False,
          work=Work, prepare=None):
    """Run the loop for ``seconds`` of the fake clock; what it took and
    when.  ``prepare(sess)``: a test's own hooks, before the run."""
    clock = FakeTime(overshoot)
    monkeypatch.setattr(session_mod, "time", clock)
    cfg = from_env({"PASSWD": "pw", "SIZEW": "64", "SIZEH": "48",
                    "REFRESH": "60", "WEBRTC_ENCODER": "tpumjpegenc",
                    "ENCODER_PREWARM": "false"})
    source = Counter60(clock, until=seconds, static=static)
    sess = StreamSession(cfg, source)
    source.session = sess
    sess.encoder = work = work(clock, cost)
    sess.PIPELINE_DEPTH = work.pipeline_depth
    sess._post = lambda *a, **k: None
    sess._fps_cap = fps_cap
    if subscribers:
        sess.subscribe()
    if end_of_turn is not None:
        monkeypatch.setattr(StreamSession, "_await_frame", end_of_turn)
    if prepare is not None:
        prepare(sess)
    before = counters()
    try:
        sess._run()
    finally:
        sess.close()
        # the process-wide ring keeps no frame of a made-up clock: full, it
        # would count every later test's frames as overwritten
        sess._tracer.clear()
    locked, looks = (b - a for a, b in zip(before, counters()))
    return types.SimpleNamespace(
        clock=clock, source=source, taken=work.taken, work=work,
        ages=[source.age(k) for k in work.taken],
        locked=locked, looks=looks)


def relative_sleep(self, t0, frame_interval):
    """The end of a turn as it was before PR 33."""
    left = frame_interval - (session_mod.time.perf_counter() - t0)
    if left > 0:
        session_mod.time.sleep(left)


def test_every_take_is_within_a_step_of_its_swap_and_none_is_skipped(
        monkeypatch):
    run = drive(monkeypatch)
    assert len(run.taken) >= 598
    assert run.taken[12:] == list(range(run.taken[12], run.taken[-1] + 1))
    assert max(run.ages[12:]) <= STEP + EPS
    assert run.locked >= len(run.taken) - 13
    assert run.looks / len(run.taken) <= 4.0


def test_the_relative_sleep_it_replaced_was_a_sawtooth(monkeypatch):
    """The regression this file guards: a turn of one refresh plus the
    sleep's overshoot slides against the display, the age sweeps the whole
    refresh and a frame goes by unseen every hundred-odd turns."""
    run = drive(monkeypatch, overshoot=0.00015, end_of_turn=relative_sleep)
    ages = sorted(run.ages)
    assert ages[len(ages) // 2] > 0.25 * REFRESH
    assert ages[-1] > 0.9 * REFRESH
    skipped = run.taken[-1] - run.taken[0] + 1 - len(run.taken)
    assert 3 <= skipped <= 8             # one in ~110 of 600
    assert run.looks == 0


def test_work_over_the_refresh_takes_the_newest_frame_at_once(monkeypatch):
    run = drive(monkeypatch, cost=lambda n: 0.0202, seconds=4.0)
    # the first turn fills the pipeline (a submit alone) and may wait
    first = math.ceil(
        (StreamSession.TAKE_GUARD_S + REFRESH / 4) / STEP)
    assert run.looks <= first and run.locked <= 1
    assert len([dt for dt in run.clock.sleeps if dt > 0]) <= first
    gaps = {b - a for a, b in zip(run.taken, run.taken[1:])}
    assert gaps <= {1, 2}                # 49.5 of 60 a second, the newest each


def test_one_long_turn_is_made_up_by_the_slack_and_skips_nothing_more(
        monkeypatch):
    at = 200
    run = drive(monkeypatch,
                cost=lambda n: 0.040 if n == at + 1 else 0.010, seconds=6.0)
    # a 40 ms turn spans two refreshes: the frame after its own is the loss
    assert run.taken[at + 1] == run.taken[at] + 2
    rest = run.taken[at + 1:]
    assert rest == list(range(rest[0], rest[-1] + 1))
    slack = REFRESH - 0.010
    back = math.ceil(REFRESH / slack)
    assert max(run.ages[at + 1 + back:]) <= STEP + EPS
    assert run.ages[at + 1] > 0.005      # it did carry the overrun's age


def test_a_static_source_with_nothing_pending_idles_without_a_look(
        monkeypatch):
    run = drive(monkeypatch, seconds=2.0, static=True)
    assert run.taken == [0]              # the joiner's keyframe
    # the one frame's turn and the turn that drained it end in the wait,
    # each at its limit; from there the idle poll, a quarter refresh a time
    polls = [dt for dt in run.clock.sleeps
             if dt == pytest.approx(REFRESH / 4)]
    assert len(polls) > 400
    assert run.looks <= 2 * math.ceil(
        (StreamSession.TAKE_GUARD_S + REFRESH / 4) / STEP)
    assert run.locked == 0


def test_the_limit_ends_the_wait_of_a_source_that_never_changes(monkeypatch):
    clock = FakeTime()
    monkeypatch.setattr(session_mod, "time", clock)
    sess = types.SimpleNamespace(
        _last_seq=0, _behind=0.0,
        _stop=types.SimpleNamespace(is_set=lambda: False),
        TAKE_GUARD_S=StreamSession.TAKE_GUARD_S, TAKE_STEP_S=STEP,
        _source_seq=lambda: 0, _probe_ready=lambda: None)
    t0 = clock.t
    StreamSession._await_frame(sess, t0, REFRESH)
    assert REFRESH < clock.t - t0 <= REFRESH * 1.25 + EPS
    assert clock.sleeps[0] == pytest.approx(
        REFRESH - StreamSession.TAKE_GUARD_S, abs=1e-5)


def test_a_cap_under_the_sources_rate_sets_the_rate(monkeypatch):
    run = drive(monkeypatch, seconds=10.0, fps_cap=30.0)
    # the frame is there long before the guard: taken at the cap's rate
    # (a guard short of its interval), never locked
    assert 295 <= len(run.taken) <= 316
    assert run.locked == 0
    assert run.looks <= len(run.taken)


def test_without_subscribers_the_loop_throttles_and_never_waits(monkeypatch):
    run = drive(monkeypatch, seconds=4.0, subscribers=False)
    assert run.looks == 0
    # a 10 ms turn leaves 6.7 ms of the refresh, and sleeps four times that
    assert len(run.taken) < 4.0 / (0.010 + 4 * 0.006)
    assert any(dt > REFRESH for dt in run.clock.sleeps)


def test_a_source_with_a_peek_is_looked_at_through_it(monkeypatch):
    clock = FakeTime()
    monkeypatch.setattr(session_mod, "time", clock)
    frames = []

    class Peeking(NumpySource):
        def frame(self):
            frames.append(1)
            return super().frame()

    src = Peeking(64, 48)
    sess = types.SimpleNamespace(source=src)
    assert StreamSession._source_seq(sess) == 0 and not frames
    sess.source = Counter60(clock)
    assert StreamSession._source_seq(sess) == 0 and sess.source.seen


def test_the_synthetic_sources_peek_agrees_with_its_frame(monkeypatch):
    """``SyntheticSource.frame()`` paints a whole picture a call; a look of
    the wait costs a read of the clock."""
    from docker_nvidia_glx_desktop_tpu.rfb import source as source_mod
    now = [100.0]
    monkeypatch.setattr(source_mod, "time",
                        types.SimpleNamespace(monotonic=lambda: now[0]))
    src = SyntheticSource(64, 48, fps=60.0)
    base, src._base = src._base, None   # a peek that painted would raise
    seqs = []
    for _ in range(6):
        now[0] += 0.0123
        seqs.append(src.seq())
    src._base = base
    assert src.frame()[1] == seqs[-1] == int(6 * 0.0123 * 60.0)
    assert seqs == sorted(seqs) and len(set(seqs)) > 3
    assert NumpySource(64, 48).seq() == NumpySource(64, 48).frame()[1] == 0


# -- how long a finished frame waits for its collect (PR 38) ------------------

def probed(device_s):
    """An encoder front whose device finishes a frame ``device_s`` after its
    submit's end and answers ``token_ready`` (None where ``device_s`` is):
    it notes the first yes a frame and when each collect began."""

    class Probed(Work):
        def __init__(self, clock, cost):
            super().__init__(clock, cost)
            self.done_at, self.first_yes, self.collected = {}, {}, {}
            self.asked = 0

        def encode_submit(self, k):
            super().encode_submit(k)
            self.done_at[k] = self.clock.t + (device_s or 0.0)
            return k

        def token_ready(self, k):
            self.asked += 1
            if device_s is None:
                return None
            assert k not in self.first_yes      # asked no more after a yes
            if self.clock.t < self.done_at[k]:
                return False
            self.first_yes[k] = self.clock.t
            return True

        def encode_collect(self, k):
            self.collected[k] = self.clock.t
            return super().encode_collect(k)

    return Probed


def ready_wait():
    h = session_mod._M_READY_WAIT_MS._default
    return h.count, h.sum


def test_a_frame_finished_at_a_look_waited_from_that_look_to_its_collect(
        monkeypatch):
    """Submit ends 7 ms into a 10 ms turn and the device needs 8.5 ms more:
    finished 15.5 ms in, between the wait's first look (15.2) and its
    second.  The sample is the collect's start less that second look."""
    n0, s0 = ready_wait()
    run = drive(monkeypatch, seconds=4.0, work=probed(0.0085))
    n, total = (b - a for a, b in zip((n0, s0), ready_wait()))
    w = run.work
    assert n == len(w.collected) >= 236
    waits = [(w.collected[k] - w.first_yes[k]) * 1e3 for k in w.collected]
    # (a collect begins two reads of the clock after ``tc`` was taken)
    assert total == pytest.approx(sum(waits), abs=0.01 * n)
    steady = sorted(waits[12:])
    assert REFRESH * 1e3 + 7.0 - 15.5 - (STEP + EPS) * 1e3 <= steady[0]
    assert steady[-1] <= REFRESH * 1e3 + 7.0 - 15.5 + 0.01
    # the end of the turn, the look after the sleep, the look that said
    # yes: asked three times a frame and never again
    assert w.asked <= 3 * len(w.taken) + 12


@pytest.mark.parametrize("device_s", [0.0127, 0.030],
                         ids=["first_yes_at_the_collect", "never_by_then"])
def test_a_frame_the_thread_waits_for_reads_zero(monkeypatch, device_s):
    """Finished between the next turn's top and its collect's start (7 ms
    + 12.7 = 19.7 ms after its turn began, 3 ms into the next), or after
    it: the thread is the one that waits, and the sample is 0.0."""
    n0, s0 = ready_wait()
    run = drive(monkeypatch, seconds=2.0, work=probed(device_s))
    n, total = (b - a for a, b in zip((n0, s0), ready_wait()))
    assert n == len(run.work.collected) >= 116
    assert total == 0.0
    if device_s < 0.02:
        at = [run.work.first_yes[k] - run.work.collected[k]
              for k in run.work.first_yes]
        assert len(at) >= n - 2 and all(-1e-5 < d <= 0.0 for d in at[2:])
    else:
        assert not run.work.first_yes


def test_an_encoder_that_cannot_say_gives_no_sample(monkeypatch):
    before = ready_wait()
    run = drive(monkeypatch, seconds=2.0, work=probed(None))
    assert ready_wait() == before
    # asked once a frame: None ends the asking
    assert len(run.taken) - 2 <= run.work.asked <= len(run.taken)
    before = ready_wait()
    drive(monkeypatch, seconds=1.0)            # no ``token_ready`` at all
    assert ready_wait() == before


def test_the_probes_change_no_take(monkeypatch):
    plain = drive(monkeypatch, seconds=4.0)
    asked = drive(monkeypatch, seconds=4.0, work=probed(0.0085))
    assert asked.taken == plain.taken and len(plain.taken) >= 238
    # the stamp of the first yes is one more read of the clock a frame, a
    # microsecond here: which look finds a frame may change, its bound not
    for run in (plain, asked):
        assert max(run.ages[12:]) <= STEP + EPS
        assert run.locked >= len(run.taken) - 13
    assert abs(asked.looks - plain.looks) <= 0.02 * plain.looks


def test_with_tracing_off_nothing_is_asked_and_nothing_observed(monkeypatch):
    from docker_nvidia_glx_desktop_tpu.obs import trace as obst
    turn = session_mod._M_TURN_MS._default
    before = (ready_wait(), turn.count)
    obst.set_enabled(False)
    try:
        run = drive(monkeypatch, seconds=1.0, work=probed(0.0085))
    finally:
        obst.set_enabled(True)
    assert len(run.taken) > 50 and run.work.asked == 0
    assert (ready_wait(), turn.count) == before


def test_the_turn_histogram_has_one_sample_a_turn_that_took_a_frame(
        monkeypatch):
    turn = session_mod._M_TURN_MS._default
    n0, s0 = turn.count, turn.sum
    run = drive(monkeypatch, seconds=2.0)
    n = turn.count - n0
    assert n == len(run.taken)
    # 10 ms of work a turn (7 in the first, a submit alone), not the wait
    assert (turn.sum - s0) / n == pytest.approx(10.0, abs=0.1)


# -- the order of a turn: a finished frame goes out between the halves of the
# -- next frame's submit (PR 39) ---------------------------------------------

def two_part(device_s, one_piece=False, depth=2):
    """An encoder front whose submit comes in two halves (0.3 and 0.4 of the
    turn's cost; the collect is the other 0.3), with the caller's
    ``between_halves`` called between them, and whose device finishes a
    frame ``device_s`` after its dispatch (None: it cannot say).  Every
    call is noted in order.  ``one_piece``: the hook is there and this
    submit calls nothing (a ring's)."""

    class TwoPart(Work):
        pipeline_depth = depth
        between_halves = None

        def __init__(self, clock, cost):
            super().__init__(clock, cost)
            self.calls, self.done_at, self.asked = [], {}, 0

        def spend(self, share):
            self.clock.t += share * self.cost(len(self.taken))

        def encode_submit(self, k):
            self.taken.append(k)
            self.calls.append(("begin", k))
            self.spend(0.3)
            if not one_piece:
                self.between_halves()
            self.calls.append(("dispatch", k))
            self.spend(0.4)
            self.done_at[k] = self.clock.t + (device_s or 0.0)
            return k

        def token_ready(self, k):
            self.asked += 1
            if device_s is None:
                return None
            return self.clock.t >= self.done_at[k]

        def encode_collect(self, k):
            self.calls.append(("collect", k))
            assert self.between_halves is None or \
                self.calls[-2][0] == "begin"       # set for the call alone
            return super().encode_collect(k)

    return TwoPart


def early_collects():
    return session_mod._M_EARLY_COLLECTS.value


def kinds_by_turn(calls):
    """The calls of each turn, as a string: a turn begins with its frame's
    ``begin``."""
    turns = []
    for what, _ in calls:
        if what == "begin" or not turns:
            turns.append([])
        turns[-1].append(what)
    return [" ".join(t) for t in turns]


def test_a_finished_frame_is_collected_between_the_halves(monkeypatch):
    """10 ms of work a turn and a device that needs 2 ms: frame ``k`` is
    finished long before turn ``k+1`` has its planes, so every turn but the
    first runs begin(k+1), collect(k), dispatch(k+1)."""
    n0 = early_collects()
    run = drive(monkeypatch, seconds=2.0, work=two_part(0.002))
    turns = kinds_by_turn(run.work.calls)
    assert turns[0] == "begin dispatch" and len(turns) >= 118
    assert set(turns[1:]) == {"begin collect dispatch"}
    assert early_collects() - n0 == len(turns) - 1
    # each collect is of the frame before the one begun
    calls = run.work.calls
    for (a, k1), (b, k0), (c, k2) in zip(calls[2::3], calls[3::3],
                                         calls[4::3]):
        assert (a, b, c) == ("begin", "collect", "dispatch")
        assert k1 == k2 and k0 == run.taken[run.taken.index(k1) - 1]
    # and the order moved no take
    plain = drive(monkeypatch, seconds=2.0)
    assert run.taken == plain.taken


@pytest.mark.parametrize("work, turn", [
    (two_part(0.030), "begin dispatch collect"),
    (two_part(None), "begin dispatch collect"),
    (two_part(0.002, one_piece=True), "begin dispatch collect"),
    (probed(0.002), None),
], ids=["not_finished", "cannot_say", "one_piece_submit", "no_hook"])
def test_any_other_turn_keeps_todays_order(monkeypatch, work, turn):
    n0 = early_collects()
    run = drive(monkeypatch, seconds=2.0, work=work)
    assert early_collects() == n0 and len(run.taken) >= 100
    if turn is None:         # an encoder without the hook: submit, collect
        w = run.work         # (a collect starts where the next submit ended)
        assert all(w.collected[k] >= w.done_at[nxt] - 0.002 - 1e-9
                   for k, nxt in zip(run.taken, run.taken[1:])
                   if k in w.collected)
    else:
        turns = kinds_by_turn(run.work.calls)
        assert set(turns[1:]) == {turn}, set(turns)


def test_a_collect_not_yet_owed_is_not_made_early(monkeypatch):
    """Three frames in flight: the turn that begins the second owes no
    collect, whatever the device says; the third turn owes the first
    frame's, and makes it between its halves."""
    run = drive(monkeypatch, seconds=1.0, work=two_part(0.002, depth=3))
    turns = kinds_by_turn(run.work.calls)
    assert turns[:3] == ["begin dispatch", "begin dispatch",
                         "begin collect dispatch"]
    assert set(turns[2:]) == {"begin collect dispatch"}
    assert [k for what, k in run.work.calls if what == "collect"] == \
        run.taken[:len(run.taken) - 2]


def test_the_drain_of_a_quiet_source_is_no_early_collect(monkeypatch):
    n0 = early_collects()
    run = drive(monkeypatch, seconds=1.0, static=True, work=two_part(0.002))
    assert run.work.calls == [("begin", 0), ("dispatch", 0), ("collect", 0)]
    assert early_collects() == n0


def test_the_order_does_not_depend_on_tracing(monkeypatch):
    from docker_nvidia_glx_desktop_tpu.obs import trace as obst
    on = drive(monkeypatch, seconds=1.0, work=two_part(0.002))
    n0 = early_collects()
    obst.set_enabled(False)
    try:
        off = drive(monkeypatch, seconds=1.0, work=two_part(0.002))
        slow = drive(monkeypatch, seconds=1.0, work=two_part(0.030))
    finally:
        obst.set_enabled(True)
    assert off.work.calls == on.work.calls
    assert early_collects() - n0 == len(off.taken) - 1
    # one look a turn, the order's own, and none of the others
    assert off.work.asked == len(off.taken) - 1
    assert set(kinds_by_turn(slow.work.calls)[1:]) == {
        "begin dispatch collect"}


@pytest.mark.parametrize("device_s", [0.002, 0.030],
                         ids=["early", "late"])
def test_the_submit_sample_holds_no_part_of_the_collect(monkeypatch,
                                                        device_s):
    """7 ms of the turn's 10 are the submit's halves, 3 the collect: the
    histogram reads 7 whichever order the turn took, and the turn 10."""
    sub = session_mod._M_SUBMIT_MS._default
    turn = session_mod._M_TURN_MS._default
    before = (sub.count, sub.sum, turn.count, turn.sum)
    run = drive(monkeypatch, seconds=2.0, work=two_part(device_s))
    n, ms, tn, tms = (b - a for a, b in zip(
        before, (sub.count, sub.sum, turn.count, turn.sum)))
    assert n == tn == len(run.taken)
    assert ms / n == pytest.approx(7.0, abs=0.05)
    assert tms / tn == pytest.approx(10.0, abs=0.1)
