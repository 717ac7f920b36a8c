"""From a profiler trace (``.xplane.pb``) to device time by named stage.

    python3 -m benchmark.stage_reduce <file.xplane.pb>

``run.py`` reduces every traced run with it (``run["stages"]``: the stage
readers under ``layer_metrics/`` and the result's ``breakdown`` read that); by
hand it reads what ``run.py --trace 1 --keep-trace <file>`` keeps.

How a stage reaches the trace (looked at by hand on a v5e, PR 25).  The
program wraps its stages in ``jax.named_scope("dngd.<stage>")``; XLA keeps the
name stack as each instruction's ``op_name``.  Under ``run.py``'s
``ProfileOptions`` (``enable_hlo_proto = False``) the device plane has no
name-scope line; the ``op_name`` is the stat ``tf_op`` of the event METADATA
of every event on the line ``XLA Ops``
(``jit(encode_p_cavlc_frame)/dngd.me_int/while/body/...``).
``jax.profiler.ProfileData`` hands out an event's own stats only, so this
file reads the protocol buffer itself, through the few fields it needs.  An
executable served by a persistent compile cache that another tree filled
carries that tree's metadata: no scopes, and everything reads ``(no scope)``.

Device time by stage is SELF time: an operation's time less the operations
nested in it on the same line (a ``while`` holds its body's), summed by
program and by the innermost ``dngd.`` scope of its name stack.  A frame is
one execution of a program whose name starts with ``jit_encode_``, as in
``trace_reduce``.  Host spans are the program's own ``dngd.*``
``TraceAnnotation``s (obs/trace.stage); an idle gap of the device (between
two program executions) is labelled with the innermost such span that covers
over half of it, else ``between spans``: the rule of ``trace_reduce``, one
level further in.
"""

from __future__ import annotations

import bisect

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SCOPE_PREFIX = "dngd."
NO_SCOPE = "(no scope)"
FRAME_PROGRAM_PREFIX = "jit_encode_"
OP_NAME_STAT = "tf_op"

_SCHEMA = None


def _schema():
    """The XSpace message, declared here field by field (tsl's
    ``xplane.proto``; only what is read) so that nothing but
    ``google.protobuf`` is imported."""
    global _SCHEMA
    if _SCHEMA is not None:
        return _SCHEMA
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="dngd_stage_reduce_xplane.proto", package="dngd_xplane",
        syntax="proto3")

    def message(name, *fields):
        msg = fd.message_type.add(name=name)
        for fname, number, ftype, extra in fields:
            f = msg.field.add(name=fname, number=number, type=ftype,
                              label=extra.get("label", T.LABEL_OPTIONAL))
            if "type_name" in extra:
                f.type_name = ".dngd_xplane." + extra["type_name"]
        return msg

    rep = {"label": T.LABEL_REPEATED}
    message("XStat", ("metadata_id", 1, T.TYPE_INT64, {}),
            ("str_value", 5, T.TYPE_STRING, {}),
            ("ref_value", 7, T.TYPE_UINT64, {}))
    message("XEvent", ("metadata_id", 1, T.TYPE_INT64, {}),
            ("offset_ps", 2, T.TYPE_INT64, {}),
            ("duration_ps", 3, T.TYPE_INT64, {}))
    message("XLine", ("name", 2, T.TYPE_STRING, {}),
            ("timestamp_ns", 3, T.TYPE_INT64, {}),
            ("events", 4, T.TYPE_MESSAGE, dict(rep, type_name="XEvent")))
    message("XEventMetadata", ("id", 1, T.TYPE_INT64, {}),
            ("name", 2, T.TYPE_STRING, {}),
            ("stats", 5, T.TYPE_MESSAGE, dict(rep, type_name="XStat")))
    message("XStatMetadata", ("id", 1, T.TYPE_INT64, {}),
            ("name", 2, T.TYPE_STRING, {}))
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        m = message(entry, ("key", 1, T.TYPE_INT64, {}),
                    ("value", 2, T.TYPE_MESSAGE, {"type_name": value}))
        m.options.map_entry = True
    message("XPlane", ("name", 2, T.TYPE_STRING, {}),
            ("lines", 3, T.TYPE_MESSAGE, dict(rep, type_name="XLine")),
            ("event_metadata", 4, T.TYPE_MESSAGE,
             dict(rep, type_name="EventMetadataEntry")),
            ("stat_metadata", 5, T.TYPE_MESSAGE,
             dict(rep, type_name="StatMetadataEntry")))
    message("XSpace",
            ("planes", 1, T.TYPE_MESSAGE, dict(rep, type_name="XPlane")))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _SCHEMA = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("dngd_xplane.XSpace"))
    return _SCHEMA


def load(path: str) -> dict:
    """{plane name: {line name: [(name, start_ps, end_ps, op_name)]}}; the
    ``op_name`` is the event metadata's ``tf_op`` stat, ``""`` without."""
    space = _schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = {}
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        for k, md in plane.event_metadata.items():
            op_name = ""
            for st in md.stats:
                if stat_names.get(st.metadata_id) == OP_NAME_STAT:
                    op_name = (st.str_value
                               or stat_names.get(st.ref_value, ""))
            meta[k] = (md.name, op_name)
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                name, op_name = meta.get(ev.metadata_id, ("", ""))
                start = base + ev.offset_ps
                evs.append((name, start, start + ev.duration_ps, op_name))
    return planes


def scope_of(op_name: str) -> str:
    """The innermost ``dngd.`` scope of a name stack."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return NO_SCOPE


def short_module(name: str) -> str:
    return name.split("(", 1)[0]


def self_times(events: list) -> list:
    """[(event, self picoseconds)]: each event's duration less the events
    nested in it (same line, so properly nested or disjoint)."""
    out, stack = [], []                # stack of [event, end, child time]
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and ev[1] >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[0][2] - done[0][1] - done[2]))
        if stack:
            stack[-1][2] += ev[2] - ev[1]
        stack.append([ev, ev[2], 0])
    while stack:
        done = stack.pop()
        out.append((done[0], done[0][2] - done[0][1] - done[2]))
    return out


def reduce_planes(planes: dict) -> dict:
    devices = sorted(p for p in planes if p.startswith(DEVICE_PREFIX))
    # one chip's view (the cells' chip): its programs, operations and gaps
    dev_lines = planes[devices[0]] if devices else {}
    modules = sorted(dev_lines.get(MODULES_LINE, []), key=lambda ev: ev[1])
    starts = [m[1] for m in modules]
    frames = sum(1 for m in modules if m[0].startswith(FRAME_PROGRAM_PREFIX))
    programs, merged = {}, []
    for name, s, e, _ in modules:
        prog = programs.setdefault(
            short_module(name), {"device_ps": 0, "runs": 0, "scopes": {}})
        prog["device_ps"] += e - s
        prog["runs"] += 1
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    for ev, self_ps in self_times(dev_lines.get(OPS_LINE, [])):
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i < 0 or ev[1] >= modules[i][2]:
            continue                   # outside every traced execution
        scopes = programs[short_module(modules[i][0])]["scopes"]
        scope = scope_of(ev[3])
        scopes[scope] = scopes.get(scope, 0) + self_ps
    spans = sorted(((n, s, e) for p, lines in planes.items()
                    if p not in devices for evs in lines.values()
                    for (n, s, e, _) in evs if n.startswith(SCOPE_PREFIX)),
                   key=lambda sp: sp[1])
    span_starts = [sp[1] for sp in spans]
    idle_by = {}
    for g0, g1 in gaps:
        best, best_len = "between spans", None
        i = bisect.bisect_left(span_starts, g1)
        for n, s, e in spans[max(0, i - 24):i]:
            covered = max(0, min(g1, e) - max(g0, s))
            if covered > 0.5 * (g1 - g0) and (
                    best_len is None or e - s < best_len):
                best, best_len = n, e - s
        idle_by[best] = idle_by.get(best, 0) + (g1 - g0)
    scoped = sum(ps for p in programs.values()
                 for sc, ps in p["scopes"].items() if sc != NO_SCOPE)
    in_ops = sum(ps for p in programs.values() for ps in p["scopes"].values())
    return {
        "frames": frames,
        "programs": {
            name: {"device_s": p["device_ps"] / 1e12, "runs": p["runs"],
                   "scopes": {sc: ps / 1e12 for sc, ps in sorted(
                       p["scopes"].items(), key=lambda kv: -kv[1])}}
            for name, p in sorted(programs.items(),
                                  key=lambda kv: -kv[1]["device_ps"])},
        "scoped_share": scoped / in_ops if in_ops else 0.0,
        "host_spans": len(spans),
        "idle_gaps": [[n, ps / 1e12] for n, ps in sorted(
            idle_by.items(), key=lambda kv: -kv[1])],
    }


def reduce(path: str) -> dict:
    return reduce_planes(load(path))


def device_ops(red: dict) -> list:
    """[[name, seconds]], largest first: every program, and every
    ``<program>/<scope>`` that is not all but the whole of its program.  The
    names stay the same from compile to compile, which an operation's do
    not."""
    out = []
    for name, p in red["programs"].items():
        out.append([name, p["device_s"]])
        out += [[f"{name}/{scope}", s] for scope, s in p["scopes"].items()
                if s < 0.95 * p["device_s"]]
    return sorted(out, key=lambda kv: -kv[1])


def table(red: dict) -> str:
    """Milliseconds a frame by program and stage, as PERF.md prints it."""
    frames = max(red["frames"], 1)
    out = [f"{red['frames']} frames; "
           f"{100 * red['scoped_share']:.1f}% of the operations' device "
           f"time lies under a {SCOPE_PREFIX} scope; "
           f"{red['host_spans']} host spans"]
    for name, p in red["programs"].items():
        in_ops = sum(p["scopes"].values())
        out.append(f"{name}: {1e3 * p['device_s'] / frames:.3f} ms a frame "
                   f"({p['runs']} runs; {1e3 * in_ops / frames:.3f} ms in "
                   "operations)")
        for scope, s in p["scopes"].items():
            out.append(f"    {scope:24s} {1e3 * s / frames:8.3f} ms")
    out.append("idle gaps of the device, by the host span that covers them:")
    for name, s in red["idle_gaps"]:
        out.append(f"    {name:24s} {1e3 * s / frames:8.3f} ms a frame")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(table(reduce(sys.argv[1])))
