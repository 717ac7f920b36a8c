"""From a profiler trace (``.xplane.pb``) to the device's totals: busy time,
the traced window, frames.  Who the time belongs to (programs, named stages,
idle gaps by host span) is ``stage_reduce``'s.

What a v5e trace holds (looked at by hand, PR 24): a plane ``/device:TPU:<n>``
for each chip with the lines ``XLA Modules`` (one event per execution of a
compiled program, named ``jit_<function>(<fingerprint>)``) and ``XLA Ops``
(one event per device operation, about 850,000 a second here), and a plane
``/host:CPU`` with one line per host thread, which holds the program's stage
spans (``obs/trace.stage``: ``dngd.encode_submit``, ``dngd.colour``, ...).
All on one clock, in nanoseconds.

Busy is the union of the program executions on a chip, averaged over the
chips: the device is idle when no program runs on it.  The window is the
traced span: first to last event among programs and stage spans (a device
that idles at an edge of the trace while the host is inside a stage is idle
inside the window).  A frame is one execution of a program whose name starts
with ``jit_encode_`` (the host tracer starts later and stops earlier than the
device's, so host spans undercount them).
"""

from __future__ import annotations

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# the program's stage spans; ``bench.`` is what run.py wrapped round submit,
# collect and the display before PR 27, and what the older recorded trace in
# testdata/ holds
SPAN_PREFIXES = ("dngd.", "bench.")
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


def load(path: str, ops: bool = True) -> dict:
    """{plane name: {line name: [(name, start_ns, end_ns)]}}; without ``ops``
    the devices' ``XLA Ops`` lines (nearly all of the file) are passed over."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            if not ops and line.name == OPS_LINE:
                continue
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns)))
    return planes


def reduce_planes(planes: dict) -> dict:
    devices = sorted(p for p in planes if p.startswith(DEVICE_PREFIX))
    edges = [(s, e) for p, lines in planes.items() if p not in devices
             for evs in lines.values() for (n, s, e) in evs
             if n.startswith(SPAN_PREFIXES)]
    per_device_busy, frames = [], 0
    for dev in devices:
        modules = planes[dev].get(MODULES_LINE, [])
        if dev == devices[0]:
            frames = sum(1 for n, _, _ in modules
                         if n.startswith(FRAME_PROGRAM_PREFIX))
        merged = union((s, e) for _, s, e in modules)
        per_device_busy.append(sum(e - s for s, e in merged))
        if merged:
            edges.append((merged[0][0], merged[-1][1]))
    window_ns = (max(e for _, e in edges) - min(s for s, _ in edges)
                 if edges else 0.0)
    busy_ns = (sum(per_device_busy) / len(per_device_busy)
               if per_device_busy else 0.0)
    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "frames": frames, "devices": len(devices)}


def reduce(path: str) -> dict:
    return reduce_planes(load(path, ops=False))


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
