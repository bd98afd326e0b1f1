#!/usr/bin/env python3
"""Cut a profiler trace down to a small test fixture.

    python3 bench/tests/trim_trace.py <in.xplane.pb[.gz]> <out.xplane.pb.gz> \
        <start_ms> <end_ms>

Keeps, between ``start_ms`` and ``end_ms`` of the trace's own clock, the
events of the TPU planes' ``XLA Ops`` and ``Async XLA Ops`` lines and the
harness's ``bench.*`` host spans (clipped to the cut), and shortens each
operation's name to its HLO instruction name. Written against the XSpace
protocol buffer schema (tsl/profiler/protobuf/xplane.proto), declared here
because no compiled copy of it is installed.
"""

import gzip
import sys

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

KEEP_LINES = ("XLA Ops", "Async XLA Ops")


def _schema():
    f = descriptor_pb2.FileDescriptorProto(name="xplane_cut.proto", package="xcut",
                                           syntax="proto3")
    T = descriptor_pb2.FieldDescriptorProto

    def msg(name, fields, nested=()):
        m = f.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            fd = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                fd.type_name = tname
        for n in nested:
            m.nested_type.add().CopyFrom(n)
        return m

    opt, rep = T.LABEL_OPTIONAL, T.LABEL_REPEATED
    i64, u64, dbl, s, b, m_ = (T.TYPE_INT64, T.TYPE_UINT64, T.TYPE_DOUBLE,
                               T.TYPE_STRING, T.TYPE_BYTES, T.TYPE_MESSAGE)

    def entry(name, vtype):
        e = descriptor_pb2.DescriptorProto(name=name)
        e.field.add(name="key", number=1, type=i64, label=opt)
        e.field.add(name="value", number=2, type=m_, label=opt, type_name=vtype)
        e.options.map_entry = True
        return e

    msg("XStat", [("metadata_id", 1, i64, opt, None), ("double_value", 2, dbl, opt, None),
                  ("uint64_value", 3, u64, opt, None), ("int64_value", 4, i64, opt, None),
                  ("str_value", 5, s, opt, None), ("bytes_value", 6, b, opt, None),
                  ("ref_value", 7, u64, opt, None)])
    msg("XEvent", [("metadata_id", 1, i64, opt, None), ("offset_ps", 2, i64, opt, None),
                   ("duration_ps", 3, i64, opt, None), ("stats", 4, m_, rep, ".xcut.XStat"),
                   ("num_occurrences", 5, i64, opt, None)])
    msg("XLine", [("id", 1, i64, opt, None), ("name", 2, s, opt, None),
                  ("timestamp_ns", 3, i64, opt, None), ("events", 4, m_, rep, ".xcut.XEvent"),
                  ("duration_ps", 9, i64, opt, None), ("display_id", 10, i64, opt, None),
                  ("display_name", 11, s, opt, None)])
    msg("XEventMetadata", [("id", 1, i64, opt, None), ("name", 2, s, opt, None),
                           ("metadata", 3, b, opt, None), ("display_name", 4, s, opt, None),
                           ("stats", 5, m_, rep, ".xcut.XStat"),
                           ("child_id", 6, i64, rep, None)])
    msg("XStatMetadata", [("id", 1, i64, opt, None), ("name", 2, s, opt, None),
                          ("description", 3, s, opt, None)])
    msg("XPlane", [("id", 1, i64, opt, None), ("name", 2, s, opt, None),
                   ("lines", 3, m_, rep, ".xcut.XLine"),
                   ("event_metadata", 4, m_, rep, ".xcut.XPlane.EventMetadataEntry"),
                   ("stat_metadata", 5, m_, rep, ".xcut.XPlane.StatMetadataEntry"),
                   ("stats", 6, m_, rep, ".xcut.XStat")],
        nested=[entry("EventMetadataEntry", ".xcut.XEventMetadata"),
                entry("StatMetadataEntry", ".xcut.XStatMetadata")])
    msg("XSpace", [("planes", 1, m_, rep, ".xcut.XPlane"), ("errors", 2, s, rep, None),
                   ("warnings", 3, s, rep, None), ("hostnames", 4, s, rep, None)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("xcut.XSpace"))


def trim(data: bytes, start_ns: float, end_ns: float) -> bytes:
    space = _schema()()
    space.ParseFromString(data)
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        host = plane.name.startswith("/host:CPU")
        names = {k: v.name for k, v in plane.event_metadata.items()}
        used = set()
        kept_lines = []
        for line in plane.lines:
            if not ((device and line.name in KEEP_LINES) or host):
                continue
            t0 = line.timestamp_ns * 1000
            events = []
            for ev in line.events:
                s = (t0 + ev.offset_ps) / 1000.0
                e = s + ev.duration_ps / 1000.0
                name = names.get(ev.metadata_id, "")
                if host and not name.startswith("bench."):
                    continue
                if e <= start_ns or s >= end_ns:
                    continue
                if host:   # clip the span to the cut
                    cs, ce = max(s, start_ns), min(e, end_ns)
                    ev.offset_ps = int(round(cs * 1000 - t0))
                    ev.duration_ps = int(round((ce - cs) * 1000))
                del ev.stats[:]
                events.append(ev)
                used.add(ev.metadata_id)
            if events:
                del line.events[:]
                line.events.extend(events)
                kept_lines.append(line)
        del plane.lines[:]
        plane.lines.extend(kept_lines)
        for k in list(plane.event_metadata):
            if k not in used:
                del plane.event_metadata[k]
            else:
                md = plane.event_metadata[k]
                md.name = md.name.split(" = ", 1)[0]
                md.display_name = ""
                md.metadata = b""
                del md.stats[:]
        del plane.stats[:]
    keep = [p for p in space.planes if p.lines]
    del space.planes[:]
    space.planes.extend(keep)
    return space.SerializeToString()


def main(argv):
    src, dst, a, b = argv
    data = open(src, "rb").read()
    if src.endswith(".gz"):
        data = gzip.decompress(data)
    out = trim(data, float(a) * 1e6, float(b) * 1e6)
    with gzip.open(dst, "wb") as f:
        f.write(out)
    print(f"{dst}: {len(out)} bytes before gzip")


if __name__ == "__main__":
    main(sys.argv[1:])
