#!/usr/bin/env python3
"""Cut a trace of the scoped program down to a test fixture that keeps each
operation's name stack.

    python3 bench/tests/cut_scoped_trace.py <in.xplane.pb[.gz]> \
        <out.xplane.pb.gz> <start_ms> <end_ms>

The cut is ``trim_trace.trim``'s (the TPU planes' ``XLA Ops`` and ``Async
XLA Ops`` events and the ``bench.*`` host spans between ``start_ms`` and
``end_ms``), which drops the metadata plane and its HLO. So each kept device
event whose HLO instruction in the train step's program has an op_name
(``scopes.hlo_op_names``) gets it as a ``tf_op`` stat: the stat
``bench/scopes.py`` reads where a trace holds no HLO.
"""

import gzip
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

OP_STAT = "tf_op"


def cut(raw: bytes, start_ns: float, end_ns: float) -> bytes:
    tt = harness.bench_module("tests/trim_trace")
    names = harness.bench_module("scopes").hlo_op_names(raw)
    space = tt._schema()()
    space.ParseFromString(tt.trim(raw, start_ns, end_ns))
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        sid = max(list(plane.stat_metadata) + [0]) + 1
        plane.stat_metadata[sid].id = sid
        plane.stat_metadata[sid].name = OP_STAT
        for line in plane.lines:
            for ev in line.events:
                inst = plane.event_metadata[ev.metadata_id].name.lstrip("%")
                if names.get(inst):
                    ev.stats.add(metadata_id=sid, str_value=names[inst])
    return space.SerializeToString()


def main(argv):
    src, dst, a, b = argv
    raw = pathlib.Path(src).read_bytes()
    if src.endswith(".gz"):
        raw = gzip.decompress(raw)
    out = cut(raw, float(a) * 1e6, float(b) * 1e6)
    with gzip.open(dst, "wb") as f:
        f.write(out)
    print(f"{dst}: {len(out)} bytes before gzip")


if __name__ == "__main__":
    main(sys.argv[1:])
