"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

What a v5e trace holds (looked at by hand, PR 24): a plane ``/device:TPU:<n>``
for each chip with the lines ``XLA Modules`` (one event per execution of a
compiled program, named ``jit_<function>(<fingerprint>)``) and ``XLA Ops``
(one event per device operation, named by its HLO text, about 850,000 a
second here), and a plane ``/host:CPU`` with one line per host thread, which
holds the ``bench.*`` TraceAnnotations that run.py wraps around the encoder's
submit and collect and the display's ``frame()``.  All on one clock, in
nanoseconds.

Busy is the union of the program executions on a chip, averaged over the
chips: the device is idle when no program runs on it.  The window is the
traced span: first to last event among programs and ``bench.*`` spans.  An
idle gap is labelled with the ``bench.*`` span that covers most of it, or
``between spans``.  Operations are summed by ``<program>/<op>``, the op named
by the left side of its HLO text.  A frame is one execution of a program whose
name starts with ``jit_encode_`` (the host tracer starts later and stops
earlier than the device's, so host spans undercount them).
"""

from __future__ import annotations

import bisect

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
FRAME_PROGRAM_PREFIX = "jit_encode_"     # one execution for each frame


def union(intervals) -> list:
    """Merge (start, end) intervals; returns the disjoint sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def load(path: str) -> dict:
    """{plane name: {line name: [(name, start_ns, end_ns)]}}."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns)))
    return planes


def short_module(name: str) -> str:
    return name.split("(", 1)[0]


def short_op(hlo: str) -> str:
    """``%fusion.439 = s32[...] fusion(...), kind=kCustom`` -> ``%fusion.439``."""
    return hlo.split(" = ", 1)[0].strip()[:60]


def reduce_planes(planes: dict) -> dict:
    devices = sorted(p for p in planes if p.startswith(DEVICE_PREFIX))
    spans = [(n, s, e) for p, lines in planes.items() if p not in devices
             for evs in lines.values() for (n, s, e) in evs
             if n.startswith(SPAN_PREFIX)]
    per_device_busy, op_time, module_time, gaps = [], {}, {}, []
    lo = hi = None
    frames = 0
    for dev in devices:
        modules = sorted(planes[dev].get(MODULES_LINE, []),
                         key=lambda ev: ev[1])
        starts = [m[1] for m in modules]
        if dev == devices[0]:
            frames = sum(1 for n, _, _ in modules
                         if n.startswith(FRAME_PROGRAM_PREFIX))
        for n, s, e in modules:
            key = "program " + short_module(n)
            module_time[key] = module_time.get(key, 0.0) + (e - s)
        for n, s, e in planes[dev].get(OPS_LINE, []):
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < modules[i][2]
            key = (short_module(modules[i][0]) if inside else "?") \
                + "/" + short_op(n)
            op_time[key] = op_time.get(key, 0.0) + (e - s)
        merged = union((s, e) for _, s, e in modules)
        per_device_busy.append(sum(e - s for s, e in merged))
        if merged:
            lo = merged[0][0] if lo is None else min(lo, merged[0][0])
            hi = merged[-1][1] if hi is None else max(hi, merged[-1][1])
        if dev == devices[0]:
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    for _, s, e in spans:
        lo = s if lo is None else min(lo, s)
        hi = e if hi is None else max(hi, e)
    window_ns = (hi - lo) if lo is not None else 0.0
    busy_ns = (sum(per_device_busy) / len(per_device_busy)
               if per_device_busy else 0.0)
    # idle time by what the host was in at the time
    spans.sort(key=lambda ev: ev[1])
    span_starts = [sp[1] for sp in spans]
    idle_by = {}
    for g0, g1 in gaps:
        best, best_ns = "between spans", 0.0
        i = bisect.bisect_left(span_starts, g1)
        for n, s, e in spans[max(0, i - 8):i]:
            o = overlap(g0, g1, s, e)
            if o > best_ns:
                best, best_ns = n, o
        if best_ns < 0.5 * (g1 - g0):
            best = "between spans"
        idle_by[best] = idle_by.get(best, 0.0) + (g1 - g0)
    top = sorted(list(module_time.items())
                 + sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
                 key=lambda kv: -kv[1])
    return {
        "busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
        "frames": frames,
        "device_ops": [[n, t / 1e9] for n, t in top[:10]],
        "idle_gaps": [[n, t / 1e9] for n, t in
                      sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]],
        "devices": len(devices),
    }


def reduce(path: str) -> dict:
    return reduce_planes(load(path))


def describe(path: str, n: int = 6) -> str:
    """A trace's planes and lines with their first events, to look at one by
    hand before trusting the reduction."""
    out = []
    for pname, lines in load(path).items():
        out.append(f"PLANE {pname}")
        for lname, evs in lines.items():
            out.append(f"  LINE {lname}: {len(evs)} events")
            for name, s, e in evs[:n]:
                out.append(f"     {name[:90]} start={s:.0f} dur={e - s:.0f}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
