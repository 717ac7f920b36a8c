"""Per-frame pipeline tracing: ring buffer in, Chrome trace-event JSON out.

Every frame gets a process-monotonic frame id at capture; each pipeline
stage appends ``(stage, t0, dur)`` spans tagged with that id to a named
:class:`TraceRecorder` ring buffer.  ``/debug/trace`` exports the merged
buffers as Chrome trace-event JSON — drop it into ``chrome://tracing`` or
Perfetto and the capture → device-submit → device-collect → bitstream →
publish → rtp-sent pipeline renders as nested tracks per recorder.

Spans may carry a small ``meta`` tuple of ``(key, value)`` pairs — the
frame-journey layer (obs/journey) stamps ``session`` / ``chunk`` /
``slot`` / ``shards`` so a chunked super-step frame or a spatially
sharded 4K session reads as labeled lanes in the export instead of an
indistinguishable blob.  A ``("session", id)`` pair routes the span to
its own per-session track (tid) at export time.

Hot-path contract (ISSUE acceptance): recording is a single
``deque.append`` of a tuple of numbers + interned constant strings — no
string formatting, no JSON, no allocation beyond the tuple.  All
formatting happens at export time.

Trace loss is NEVER silent: a ring overwrite (the deque evicting its
oldest entry) and a listener raising out of its flush both count into
``dngd_trace_dropped_total{tracer,reason}`` — the serving-budget smoke
asserts the counter stays 0 over its window (obs consumers see every
span through the listener hook, so a non-zero count means the budget
ledger's view is incomplete).

Stage spans (:func:`stage`) name the work INSIDE a frame's marks —
capture, colour conversion, dispatch, pull, assembly — where it happens,
and the rest of the session thread's turn round them (``TURN_STAGES``:
the content statistics' pull, the loop's tail after the muxer, the wait
at the turn's end), so that a turn is spans from end to end.
A stage span is a ``jax.profiler.TraceAnnotation("dngd.<name>")`` for its
duration, so whenever a profiler session is open it lies on the device
trace's clock, and on exit it observes its milliseconds into an
unlabelled histogram of its own, ``dngd_stage_<name>_ms``.  It never
touches a recorder: the per-frame marks, their ring and their listeners
are as they were.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import metrics as obsm

__all__ = ["TraceRecorder", "tracer", "tracers", "next_frame_id",
           "export_chrome_trace", "set_enabled", "enabled",
           "dropped_total", "DEFAULT_CAPACITY", "stage", "STAGES",
           "CABAC_STAGES", "MESH_STAGES", "MASK_STAGES", "MASK_CABAC_STAGES",
           "TURN_STAGES",
           "STAGE_BUCKETS_MS", "M_WS_SEND_MS"]

DEFAULT_CAPACITY = 4096      # spans per recorder (ring; oldest evicted)

_frame_ids = itertools.count(1)

_M_DROPPED = obsm.counter(
    "dngd_trace_dropped_total",
    "Trace entries lost by tracer and reason: ring_overwrite = the "
    "ring buffer evicted an un-exported entry, listener_error = a "
    "flush listener raised and its view of that entry is gone",
    ("tracer", "reason"))

# Master switch: False turns record_span/record_marks and stage spans
# into early returns, so tracing on against tracing off is measurable on
# the identical serving path.
_ENABLED = True


def set_enabled(flag: bool) -> None:
    global _ENABLED
    _ENABLED = bool(flag)


def enabled() -> bool:
    return _ENABLED


def dropped_total() -> float:
    """Sum of dngd_trace_dropped_total over all children (the
    serving-budget smoke gate)."""
    return sum(child.value for _, child in _M_DROPPED.series())


# -- stage spans ------------------------------------------------------

# Fine enough for a p50 of a stage that takes 0.3 to 30 ms of a frame;
# the last three tell a late frame from a compile in the serving thread.
STAGE_BUCKETS_MS: Tuple[float, ...] = (
    0.25, 0.5, 0.75, 1.0, 1.5, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 10.0, 12.0,
    15.0, 20.0, 25.0, 30.0, 40.0, 50.0, 100.0, 250.0, 1000.0)

# The served per-frame path's stages (web/session.py, models/h264.py);
# registered at import, so each family renders from the first scrape.
STAGES = ("capture", "encode_submit", "encode_collect", "colour",
          "dispatch", "pull", "pull_extra", "assemble")
# The CABAC path's own, inside ``assemble``: the host arithmetic engine
# (bitstream/h264_cabac.py).
CABAC_STAGES = ("engine",)
# A spatial mesh's own, inside ``assemble``: the per-shard record streams
# stitched row-wise into one transport buffer (models/h264.py
# ``_sp_collect_bin``, ops/cabac_binarize.stitch_rows).
MESH_STAGES = ("stitch",)
# A damage-mask session's own (DNGD_DAMAGE_MASK), in front of ``dispatch``
# in the first half of a P frame's submit: the host's damage grid of the
# frame's luma against the frame before and the row plan made of it
# (models/h264.py ``_damage_plan``, ops/damage_mask.damage_grid_np); one
# sample a planned P frame, none on an IDR.
MASK_STAGES = ("damage_grid",)
# ... and, under the CABAC stream, inside ``assemble`` beside ``engine``:
# making or fetching the slice data the frame's unplanned rows leave as
# (bitstream/h264_cabac.py ``encode_p_rows_from_binstream``); one sample a
# frame of the CABAC row program.
MASK_CABAC_STAGES = ("skip_slices",)
# The rest of a turn of the session thread (PR 38), so that the device's
# idle gaps fall under a span wherever the host is: ``stats``, the content
# statistics' pull that ends ``H264Encoder.encode_collect`` (one sample a
# collected frame, the span of an early return where the content plane is
# off); ``publish``, the loop's tail from the muxer's ``assemble`` to the
# end of the collect branch (one sample a delivered frame); ``await``,
# ``StreamSession._await_frame`` whole (one sample a turn that has time
# left, so not every frame's: that is why these are not in ``STAGES``).
TURN_STAGES = ("stats", "publish", "await")

_stage_defs: Dict[str, tuple] = {}     # name -> (histogram, span name)
_annotation = None                     # jax.profiler.TraceAnnotation, lazily
_carry = threading.local()             # a split stage's first part, in ms


def _load_annotation():
    global _annotation
    try:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    except Exception:
        # obs/ works where JAX cannot be imported: a span of nothing
        _annotation = contextlib.nullcontext
    return _annotation


def _stage_def(name: str) -> tuple:
    st = _stage_defs.get(name)
    if st is None:
        st = _stage_defs[name] = (
            obsm.histogram(f"dngd_stage_{name}_ms",
                           f"Milliseconds in the frame stage '{name}' "
                           "(one sample a frame; obs/trace.stage)",
                           buckets=STAGE_BUCKETS_MS),
            "dngd." + name)
    return st


class _StageSpan:
    """One use of :func:`stage`.  ``ms`` is the span's duration once it
    has closed, and 0.0 under ``set_enabled(False)``."""

    __slots__ = ("_hist", "_span_name", "_more", "_ann", "_t0", "ms")

    def __init__(self, hist, span_name: str, more: bool) -> None:
        self._hist = hist
        self._span_name = span_name
        self._more = more
        self._ann = None
        self.ms = 0.0

    def __enter__(self):
        if _ENABLED:
            ann = self._ann = (_annotation or _load_annotation())(
                self._span_name)
            ann.__enter__()
            self._t0 = time.perf_counter()
        return self

    @contextlib.contextmanager
    def suspended(self):
        """The open stage's clock stops for the block: what runs inside is
        none of its milliseconds (the session's early collect between the
        halves of ``encode_submit``).  The profiler span stays open round
        it, so the block's own spans nest in it."""
        t = time.perf_counter()
        try:
            yield
        finally:
            if self._ann is not None:
                self._t0 += time.perf_counter() - t

    def __exit__(self, *exc) -> bool:
        ann = self._ann
        if ann is not None:
            self.ms = ms = (time.perf_counter() - self._t0) * 1e3
            ann.__exit__(*exc)
            carried = _carry.__dict__
            if self._more:
                carried[self._span_name] = ms
            else:
                self._hist.observe(ms + carried.pop(self._span_name, 0.0))
        return False


def stage(name: str, more: bool = False) -> _StageSpan:
    """``with stage("pull"): ...`` — the block is the profiler span
    ``dngd.pull`` and one sample of ``dngd_stage_pull_ms``; an early
    return under ``set_enabled(False)``.  ``more=True`` is for a stage
    whose work lies in two places on one thread (``assemble``: the
    encoder's Annex-B assembly, then the session's muxer): the first part
    is its own profiler span and hands its milliseconds to the part that
    closes the stage, which takes the frame's one sample.

    Host stages: ``STAGES`` (the frame's own work), ``CABAC_STAGES`` and
    ``MESH_STAGES`` (inside ``assemble``), ``MASK_STAGES`` (in front of
    ``dispatch``), ``MASK_CABAC_STAGES`` (inside ``assemble``),
    ``TURN_STAGES`` (``stats``,
    ``publish``, ``await``: the rest of the session thread's turn)."""
    return _StageSpan(*_stage_def(name), more)


for _name in (STAGES + CABAC_STAGES + MESH_STAGES + MASK_STAGES
              + MASK_CABAC_STAGES + TURN_STAGES):
    _stage_def(_name)

# The one stage that crosses threads, so it is no profiler span: stamped
# by StreamSession._post on the encode thread, closed by web/server.py's
# media pump once ``ws.send_bytes`` has returned.
M_WS_SEND_MS = obsm.histogram(
    "dngd_ws_publish_to_send_ms",
    "Encode thread's publish to the websocket write's return, per "
    "fragment and client: thread hand-off + event-loop queue + socket "
    "write", buckets=STAGE_BUCKETS_MS)


def next_frame_id() -> int:
    """Process-monotonic frame id; tags every span of one frame across
    recorders (encode thread, event loop, webrtc) for correlation."""
    return next(_frame_ids)


class TraceRecorder:
    """One named ring buffer of spans.

    ``record_span(stage, t0, dur, frame_id)`` — one complete span;
    ``record_marks(frame_id, marks)`` — a frame's ordered (stage, t)
    stage marks (a :class:`..utils.timing.StageTimer` flush); consecutive
    marks become spans at export time, named after the mark they END on,
    so the recorder never formats strings per frame.  Both accept an
    optional ``meta`` tuple of (key, value) pairs merged into the Chrome
    export's ``args`` (and used for per-session track routing).
    """

    def __init__(self, name: str, capacity: int = DEFAULT_CAPACITY):
        self.name = name
        # span entries: (stage, t0_s, dur_s, frame_id, pts, meta)
        self._spans: deque = deque(maxlen=capacity)
        # mark entries: (frame_id, ((stage, t_s), ...), pts, meta)
        self._marks: deque = deque(maxlen=capacity)
        # live consumers (the serving-budget ledger): called synchronously
        # on the recording thread with the stored tuple — listeners must
        # be append-only cheap, mirroring the ring buffer's contract
        self._listeners: List = []
        # dropped-entry children resolved once (hot path must not format
        # label strings per drop)
        self._m_overwrite = _M_DROPPED.labels(name, "ring_overwrite")
        self._m_listener = _M_DROPPED.labels(name, "listener_error")

    def add_listener(self, fn) -> None:
        """Register ``fn(kind, entry)`` called on every record:
        kind 'span' with (stage, t0, dur, frame_id, pts, meta), or kind
        'marks' with (frame_id, ((stage, t), ...), pts, meta).  The ring
        buffer only keeps the last ``capacity`` entries; a listener sees
        every one.  A listener that raises loses that entry only for
        itself — the error is counted (listener_error), never propagated
        into the recording thread."""
        if fn not in self._listeners:
            self._listeners.append(fn)

    def remove_listener(self, fn) -> None:
        if fn in self._listeners:
            self._listeners.remove(fn)

    def _notify(self, kind: str, entry) -> None:
        for fn in self._listeners:
            try:
                fn(kind, entry)
            except Exception:
                # a raising listener must not kill the encode thread,
                # and its missed entry must not vanish silently
                self._m_listener.inc()

    def record_span(self, stage: str, t0: float, dur: float,
                    frame_id: int = 0,
                    pts: Optional[int] = None,
                    meta: Optional[tuple] = None) -> None:
        if not _ENABLED:
            return
        entry = (stage, t0, dur, frame_id, pts, meta)
        if len(self._spans) == self._spans.maxlen:
            self._m_overwrite.inc()
        self._spans.append(entry)
        self._notify("span", entry)

    def record_marks(self, frame_id: int,
                     marks: Sequence[Tuple[str, float]],
                     pts: Optional[int] = None,
                     meta: Optional[tuple] = None) -> None:
        if not _ENABLED:
            return
        entry = (frame_id, tuple(marks), pts, meta)
        if len(self._marks) == self._marks.maxlen:
            self._m_overwrite.inc()
        self._marks.append(entry)
        self._notify("marks", entry)

    def __len__(self) -> int:
        return len(self._spans) + len(self._marks)

    def clear(self) -> None:
        self._spans.clear()
        self._marks.clear()

    # -- export (scrape-time only) -------------------------------------

    def chrome_events(self, tid: int = 0, tid_of=None) -> List[dict]:
        """Complete ('ph': 'X') events, ts/dur in microseconds (the
        Chrome trace-event contract).  ``args.pts`` (when recorded) is
        the cross-track correlation key: the encode thread and the
        webrtc sender tag spans of the same frame with the same pts.
        ``meta`` pairs land in ``args`` verbatim — ``chunk``/``slot``
        name a super-step frame's chunk, ``shards`` its spatial extent.
        ``tid_of(meta) -> tid`` (when given) routes spans to
        per-session tracks."""
        def args(fid, pts, meta):
            a = {"frame": fid} if pts is None else {"frame": fid,
                                                   "pts": pts}
            if meta:
                a.update(meta)
            return a

        def tid_for(meta):
            if tid_of is not None:
                t = tid_of(meta)
                if t is not None:
                    return t
            return tid

        out = []
        for stage, t0, dur, fid, pts, meta in list(self._spans):
            out.append({"name": stage, "cat": self.name, "ph": "X",
                        "ts": t0 * 1e6, "dur": dur * 1e6,
                        "pid": 0, "tid": tid_for(meta),
                        "args": args(fid, pts, meta)})
        for fid, marks, pts, meta in list(self._marks):
            for (_, t_a), (stage_b, t_b) in zip(marks, marks[1:]):
                out.append({"name": stage_b, "cat": self.name, "ph": "X",
                            "ts": t_a * 1e6, "dur": (t_b - t_a) * 1e6,
                            "pid": 0, "tid": tid_for(meta),
                            "args": args(fid, pts, meta)})
        return out


_tracers: Dict[str, TraceRecorder] = {}
_lock = threading.Lock()


def tracer(name: str, capacity: int = DEFAULT_CAPACITY) -> TraceRecorder:
    """Get-or-create the process-wide recorder ``name`` (one per
    pipeline: 'pipeline', 'webrtc', 'batch', ...)."""
    rec = _tracers.get(name)
    if rec is None:
        with _lock:
            rec = _tracers.get(name)
            if rec is None:
                rec = _tracers[name] = TraceRecorder(name, capacity)
    return rec


def tracers() -> Iterable[TraceRecorder]:
    return list(_tracers.values())


def export_chrome_trace(
        which: Optional[Iterable[TraceRecorder]] = None) -> dict:
    """The `/debug/trace` payload: Chrome trace-event JSON object form.

    Thread names come from metadata events so Perfetto labels each
    recorder's track; ts stays on the perf_counter timebase (Chrome only
    needs monotonicity, not wall-clock).  Spans stamped with a
    ``("session", id)`` meta pair get their own per-session track
    (``<recorder>:<session>``) so a multi-session capture reads as N
    lanes instead of one interleaved blob."""
    recs = list(which) if which is not None else tracers()
    events: List[dict] = []
    # base tids are assigned per recorder; per-session lanes extend past
    # them.  The allocator is shared across recorders so every
    # (recorder, session) pair is a distinct, stable lane.
    next_tid = len(recs)
    lanes: Dict[tuple, int] = {}
    for tid, rec in enumerate(recs):
        events.append({"name": "thread_name", "ph": "M", "pid": 0,
                       "tid": tid, "args": {"name": rec.name}})

        def tid_of(meta, _rec=rec, _base=tid):
            nonlocal next_tid
            if not meta:
                return _base
            sid = next((v for k, v in meta if k == "session"), None)
            if sid is None:
                return _base
            key = (_rec.name, sid)
            lane = lanes.get(key)
            if lane is None:
                lane = lanes[key] = next_tid
                next_tid += 1
                events.append({"name": "thread_name", "ph": "M",
                               "pid": 0, "tid": lane,
                               "args": {"name": f"{_rec.name}:{sid}"}})
            return lane

        events.extend(rec.chrome_events(tid=tid, tid_of=tid_of))
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"exported_at": time.time()}}
